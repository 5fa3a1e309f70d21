"""Where a traced window's time went: by program, by layer scope, by host phase.

``reduce`` splits a window into device busy and idle time around the
harness's ``bench.*`` spans.  This module reads what it leaves out of the
same ``.xplane.pb``:

* the program's own ``server.*`` spans (``repro.obs.span``), with their args;
* the device's ``XLA Modules`` line: one event per program run, named
  ``jit_<function>(<program id>)`` (``server_decode``, ``server_prefill``);
* each operation's ``op_name`` path, which carries the model's named scopes
  (``stack``, ``attn``, ``mlp``, ``moe``, ``ssm``, ``embed``, ``head``).  The
  trace holds it as the ``tf_op`` stat of the operation's event metadata,
  keyed by program id and instruction.  ``ProfileData`` does not expose
  event metadata, so :func:`op_names` reads it from the protobuf wire format
  itself; nothing is compiled for it.

and reduces them on the clock offset that ``reduce.reduce`` found:

* program time: device time of each program run (its ``XLA Modules``
  event), over the runs that start in the window;
* scope self time: each busy instant goes to the innermost operation
  running then (a ``while`` encloses the operations of its body), and each
  operation's self time to the layer scope of its ``op_name``: the first of
  ``LAYER_SCOPES`` on its path, else ``stack`` (the layer scan's slicing
  and write-back, and operations the compiler added with no ``op_name``).
  Program time that no operation found in the metadata covers is
  ``unattributed``;
* idle by phase: each idle instant of the window goes to the innermost host
  span covering it (``server.*`` or ``bench.*``), ``outside`` where none does;
* admission device time: busy time inside ``server.admit`` spans;
* cache positions: the ``kv_live`` and ``kv_scanned`` args of the
  ``server.decode`` spans.

A trace of a program without these spans, programs or scopes reduces to
empty quantities, and every metric function below then returns None.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chip import reduce
from chip.reduce import Busy, Event, Interval, TraceData

SERVER_PREFIX = "server."
MODULES_LINE = "XLA Modules"
LAYER_SCOPES = ("attn", "mlp", "moe", "ssm", "embed", "head")
STACK = "stack"
NO_OP_NAME = "(no op_name)"
DECODE = "server_decode"
SAMPLE_SPANS = ("server.sample", "server.first_token")
LOOP_SPANS = ("server.step", "server.decode")
TOP = reduce.TOP


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                # seconds, trace clock
    end: float
    args: Tuple[Tuple[str, object], ...] = ()

    def arg(self, key: str, default=0):
        return dict(self.args).get(key, default)


@dataclasses.dataclass
class ServerTrace:
    """What ``reduce.from_xplane`` leaves out of one ``.xplane.pb``."""
    spans: List[Span]                       # server.* spans with their args
    modules: Dict[str, List[Event]]         # device plane -> program runs
    op_names: Dict[Tuple[int, str], str]    # (program id, instruction) -> path


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------


def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varints,
    a memoryview for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        elif kind == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, v


def _map_entries(plane: memoryview, field: int) -> Iterator[memoryview]:
    for f, v in _fields(plane):
        if f == field:
            for k, val in _fields(v):
                if k == 2:
                    yield val


# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7


def op_names(path: Path) -> Dict[Tuple[int, str], str]:
    """``(program id, instruction)`` -> ``op_name`` path of every operation
    in the device planes' event metadata; ``""`` for an operation that has
    a program id but no ``op_name`` (a copy the compiler added)."""
    data = memoryview(Path(path).read_bytes())
    out: Dict[Tuple[int, str], str] = {}
    for f, plane in _fields(data):
        if f != _SPACE_PLANES:
            continue
        name = next((bytes(v).decode() for k, v in _fields(plane)
                     if k == _PLANE_NAME), "")
        if not name.startswith(reduce.DEVICE_PREFIX):
            continue
        stat_names = {}
        for entry in _map_entries(plane, _PLANE_STAT_METADATA):
            d = dict(_fields(entry))
            stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        for meta in _map_entries(plane, _PLANE_EVENT_METADATA):
            instr, program, tf_op = None, None, ""
            for k, v in _fields(meta):
                if k == _META_NAME:
                    instr = reduce.op_name(bytes(v).decode())
                elif k == _META_STATS:
                    stat = dict(_fields(v))
                    key = stat_names.get(stat.get(_STAT_META_ID))
                    if key == "program_id":
                        program = stat.get(_STAT_UINT, stat.get(_STAT_INT))
                    elif key == "tf_op":
                        tf_op = (bytes(stat[_STAT_STR]).decode()
                                 if _STAT_STR in stat
                                 else stat_names.get(stat.get(_STAT_REF), ""))
            if instr is not None and program is not None:
                out[(program, instr)] = tf_op
    return out


def read(path: Path) -> ServerTrace:
    """The ``server.*`` spans, program runs and ``op_name`` map of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans: List[Span] = []
    modules: Dict[str, List[Event]] = {}
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(reduce.DEVICE_PREFIX) \
                    and line.name == MODULES_LINE:
                modules.setdefault(plane.name, []).extend(
                    Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events)
            elif not plane.name.startswith("/device:"):
                spans.extend(Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                                  tuple(e.stats))
                             for e in line.events
                             if e.name.startswith(SERVER_PREFIX))
    return ServerTrace(spans=sorted(spans, key=lambda s: s.start),
                       modules=modules, op_names=op_names(path))


_MODULE = re.compile(r"^jit_(.*)\((\d+)\)$")


def program(module_name: str) -> Tuple[str, Optional[int]]:
    """``jit_server_decode(123)`` -> ``("server_decode", 123)``."""
    m = _MODULE.match(module_name)
    return (m.group(1), int(m.group(2))) if m else (module_name, None)


def scope_path(op_name: str) -> str:
    """``jit(server_decode)/stack/while/body/dynamic_slice:`` ->
    ``stack/while/body/dynamic_slice``: the program's root and the type
    suffix of a ``tf_op`` dropped."""
    if not op_name:
        return NO_OP_NAME
    parts = op_name.split("/")
    if parts[0].startswith("jit("):
        parts = parts[1:]
    if parts and ":" in parts[-1]:
        parts[-1] = parts[-1].rsplit(":", 1)[0]
    return "/".join(p for p in parts if p)


def layer_scope(path: str) -> str:
    """The first layer scope on ``path``; ``stack`` where there is none."""
    return next((p for p in path.split("/") if p in LAYER_SCOPES), STACK)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def _innermost(events: Sequence, intervals: Sequence[Interval]
               ) -> Iterator[Tuple[int, float, Optional[int]]]:
    """Walk the sorted, disjoint ``intervals``: (interval index, seconds,
    index of the innermost event covering those seconds or None).  The
    innermost event is the one that started last; of two that started
    together, the shorter."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    heap: List[Tuple[float, float, int]] = []
    k = 0
    for q, (lo, hi) in enumerate(intervals):
        t = lo
        while t < hi:
            while k < len(order) and events[order[k]].start <= t:
                e = events[order[k]]
                heapq.heappush(heap, (-e.start, e.end, order[k]))
                k += 1
            while heap and heap[0][1] <= t:
                heapq.heappop(heap)
            nxt = hi
            if k < len(order):
                nxt = min(nxt, events[order[k]].start)
            if heap:
                nxt = min(nxt, heap[0][1])
            yield q, nxt - t, (heap[0][2] if heap else None)
            t = nxt


def self_times(events: Sequence[Event]) -> List[float]:
    """Seconds in which each event is the innermost one running.  They add
    up to the union of the events."""
    out = [0.0] * len(events)
    for _, dt, i in _innermost(events, reduce.merge(
            [(e.start, e.end) for e in events])):
        out[i] += dt
    return out


def innermost(spans: Sequence, gaps: Sequence[Interval]
              ) -> List[Dict[str, float]]:
    """For each gap (sorted, disjoint): seconds of it by the name of the
    innermost span covering them, ``outside`` where none does."""
    out: List[Dict[str, float]] = [defaultdict(float) for _ in gaps]
    for q, dt, i in _innermost(spans, gaps):
        out[q][spans[i].name if i is not None else "outside"] += dt
    return [dict(d) for d in out]


def label(by_span: Dict[str, float]) -> str:
    """The span that holds most of a gap; ``outside`` only where no span
    covers any of it."""
    inside = {k: v for k, v in by_span.items() if k != "outside"}
    return max(inside, key=inside.get) if inside else "outside"


@dataclasses.dataclass
class ProgramTime:
    runs: int = 0                           # runs that start in the window
    device_s: float = 0.0                   # their device time
    scopes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float))   # layer scope -> self s
    paths: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float))   # op_name path -> self s
    attributed_s: float = 0.0               # self time of operations found

    @property
    def unattributed_s(self) -> float:
        return self.device_s - self.attributed_s


@dataclasses.dataclass
class Phases:
    programs: Dict[str, ProgramTime]
    steps: int                              # server.step spans in the window
    idle_by_span: Dict[str, float]          # innermost span -> idle seconds
    idle_gaps: List[Tuple[str, float]]      # longest gaps, by innermost span
    admit_busy_s: List[float]               # per server.admit span
    kv_live: int                            # over server.decode spans
    kv_scanned: int


def program_times(ops: Sequence[Event], modules: Sequence[Event],
                  names: Dict[Tuple[int, str], str], a: float, b: float
                  ) -> Dict[str, ProgramTime]:
    """Device time and scope self time of each program's runs that start in
    ``[a, b)``, on one device."""
    runs = sorted((m for m in modules if a <= m.start < b),
                  key=lambda m: m.start)
    starts = [m.start for m in runs]
    out: Dict[str, ProgramTime] = {}
    ids = []
    for m in runs:
        name, pid = program(m.name)
        t = out.setdefault(name, ProgramTime())
        t.runs += 1
        t.device_s += m.end - m.start
        ids.append((name, pid))
    if not runs:
        return out
    for e, own in zip(ops, self_times(ops)):
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.start >= runs[i].end or own <= 0:
            continue
        name, pid = ids[i]
        op = names.get((pid, e.name))
        if op is None:
            continue
        t = out[name]
        path = scope_path(op)
        t.scopes[layer_scope(path)] += own
        t.paths[path] += own
        t.attributed_s += own
    return out


def reduce_phases(trace: TraceData, server: ServerTrace, offset_s: float
                  ) -> Phases:
    """Phases of the traced window of ``trace`` (its ``bench.window``), host
    spans shifted by ``offset_s`` (``reduce.Reduction.offset_s``)."""
    (w,) = [sp for sp in trace.spans if sp.name == reduce.WINDOW]
    a, b = w.start + offset_s, w.end + offset_s
    devices = sorted(trace.device_ops)
    first = devices[0]
    busy = [Busy(trace.device_ops[d]) for d in devices]
    progs = program_times(trace.device_ops[first],
                          server.modules.get(first, []), server.op_names,
                          a, b)
    shifted = [dataclasses.replace(sp, start=sp.start + offset_s,
                                   end=sp.end + offset_s)
               for sp in server.spans]
    inside = [sp for sp in shifted if a <= sp.start < b]
    host = shifted + [
        Event(sp.name, sp.start + offset_s, sp.end + offset_s)
        for sp in trace.spans if sp.name != reduce.WINDOW]
    gaps = busy[0].gaps(a, b)
    split = innermost(host, gaps)
    idle: Dict[str, float] = defaultdict(float)
    for by_span in split:
        for k, v in by_span.items():
            idle[k] += v
    labelled = [(label(s), g[1] - g[0]) for s, g in zip(split, gaps)]
    decodes = [sp for sp in inside if sp.name == "server.decode"]
    return Phases(
        programs=progs,
        steps=sum(sp.name == "server.step" for sp in inside),
        idle_by_span=dict(idle),
        idle_gaps=sorted(labelled, key=lambda g: -g[1])[:TOP],
        admit_busy_s=[sum(x.within(sp.start, sp.end) for x in busy)
                      / len(busy)
                      for sp in inside if sp.name == "server.admit"],
        kv_live=sum(int(sp.arg("kv_live")) for sp in decodes),
        kv_scanned=sum(int(sp.arg("kv_scanned")) for sp in decodes))


def device_scopes(ph: Phases, program_name: str = DECODE
                  ) -> List[Tuple[str, float]]:
    """The program's ``op_name`` paths with the most self time, seconds
    over the window."""
    t = ph.programs.get(program_name)
    if t is None:
        return []
    return sorted(t.paths.items(), key=lambda kv: -kv[1])[:TOP]


# ---------------------------------------------------------------------------
# per-layer quantities, None where the trace holds nothing to read
# ---------------------------------------------------------------------------


def _decode(ph: Phases) -> Optional[ProgramTime]:
    t = ph.programs.get(DECODE)
    return t if t is not None and t.runs else None


def _decode_scope_ms(ph: Phases, *scopes: str) -> Optional[float]:
    t = _decode(ph)
    if t is None:
        return None
    return 1e3 * sum(t.scopes.get(s, 0.0) for s in scopes) / t.runs


def decode_program_ms(ph: Phases) -> Optional[float]:
    """Mean device time of one ``server_decode`` run."""
    t = _decode(ph)
    return None if t is None else 1e3 * t.device_s / t.runs


def decode_attn_ms(ph: Phases) -> Optional[float]:
    return _decode_scope_ms(ph, "attn")


def decode_mlp_ms(ph: Phases) -> Optional[float]:
    return _decode_scope_ms(ph, "mlp", "moe")


def decode_stack_ms(ph: Phases) -> Optional[float]:
    return _decode_scope_ms(ph, STACK)


def decode_unattributed_pct(ph: Phases) -> Optional[float]:
    t = _decode(ph)
    return None if t is None else 100.0 * t.unattributed_s / t.device_s


def admit_program_ms_per_request(ph: Phases) -> Optional[float]:
    if not ph.admit_busy_s:
        return None
    return 1e3 * sum(ph.admit_busy_s) / len(ph.admit_busy_s)


def decode_kv_live_pct(ph: Phases) -> Optional[float]:
    if not ph.kv_scanned:
        return None
    return 100.0 * ph.kv_live / ph.kv_scanned


def _idle_ms_per_step(ph: Phases, names: Sequence[str]) -> Optional[float]:
    if not ph.steps:
        return None
    return 1e3 * sum(ph.idle_by_span.get(n, 0.0) for n in names) / ph.steps


def idle_sample_ms_per_step(ph: Phases) -> Optional[float]:
    return _idle_ms_per_step(ph, SAMPLE_SPANS)


def idle_loop_ms_per_step(ph: Phases) -> Optional[float]:
    return _idle_ms_per_step(ph, LOOP_SPANS)


METRICS = {f.__name__: f for f in (
    decode_program_ms, decode_attn_ms, decode_mlp_ms, decode_stack_ms,
    admit_program_ms_per_request, decode_kv_live_pct,
    idle_sample_ms_per_step, idle_loop_ms_per_step)}
