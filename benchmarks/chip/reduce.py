"""Reduce a profiler trace to device busy time, harness spans and gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Device planes (``/device:TPU:<n>``) carry each executed operation on the
line ``XLA Ops``; the host plane carries the harness's
``jax.profiler.TraceAnnotation`` spans (``bench.*``) on the same clock.

* the device clock and the host clock of a TPU trace disagree by about a
  millisecond (a recorded trace shows each operation before the span that
  launched it), so the host spans are first shifted by the offset at which
  the step spans hold the most device busy time: in the window every
  device operation is launched from inside a step;
* busy time is the union of the device's operation intervals, clipped to
  the traced window (span ``bench.window``), averaged over devices;
* each harness step span (``bench.step``) gets the busy time inside it;
* idle gaps (window minus busy, on the first device) are labelled by the
  harness span that overlaps them most, ``outside`` where none does;
* ``breakdown`` lists the device operations that took most time and the
  longest idle gaps.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW, STEP = "bench.window", "bench.step"
TOP = 10
OFFSET_RANGE_S = 0.01           # clock offsets searched: +-10 ms
OFFSET_GRID_S = 20e-6

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float                # seconds, trace clock
    end: float


@dataclasses.dataclass
class TraceData:
    device_ops: Dict[str, List[Event]]      # device plane -> operations
    spans: List[Event]                      # harness spans


def op_name(event_name: str) -> str:
    """``%fusion.85 = (f32[...]) fusion(...)`` -> ``%fusion.85``: the TPU
    trace names an operation by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0]


def from_xplane(path: Path) -> TraceData:
    """Device operations and harness spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX) and line.name == OPS_LINE:
                ops.setdefault(plane.name, []).extend(
                    Event(op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events)
            elif not plane.name.startswith("/device:"):
                spans.extend(Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return TraceData(device_ops=ops, spans=sorted(spans, key=lambda e: e.start))


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Busy:
    """Merged busy intervals of one device, with overlap queries."""

    def __init__(self, events: Sequence[Event]):
        self.iv = merge([(e.start, e.end) for e in events])
        self._starts = [s for s, _ in self.iv]
        self._cum = [0.0]
        for s, e in self.iv:
            self._cum.append(self._cum[-1] + (e - s))

    def within(self, a: float, b: float) -> float:
        """Busy seconds inside [a, b]."""
        if b <= a or not self.iv:
            return 0.0
        i = max(bisect.bisect_right(self._starts, a) - 1, 0)
        j = bisect.bisect_left(self._starts, b)
        total = self._cum[j] - self._cum[i]
        s, e = self.iv[i]                       # clip the first interval
        total -= max(0.0, min(e, a) - s)
        if j > i:                               # clip the last interval
            s, e = self.iv[j - 1]
            total -= max(0.0, e - max(b, s))
        return max(total, 0.0)

    def gaps(self, a: float, b: float) -> List[Interval]:
        """Idle intervals inside [a, b]."""
        out, t = [], a
        for s, e in self.iv:
            if e <= a:
                continue
            if s >= b:
                break
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < b:
            out.append((t, b))
        return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def label_gaps(gaps: Sequence[Interval], spans: Sequence[Event]
               ) -> List[Tuple[str, float]]:
    """Each gap as (name of the harness span overlapping it most, seconds)."""
    inner = [sp for sp in spans if sp.name != WINDOW]
    starts = [sp.start for sp in inner]
    out = []
    for g in gaps:
        best, label = 0.0, "outside"
        i = max(bisect.bisect_right(starts, g[0]) - 1, 0)
        for sp in inner[i:]:
            if sp.start >= g[1]:
                break
            ov = _overlap(g, (sp.start, sp.end))
            if ov > best:
                best, label = ov, sp.name
        out.append((label, g[1] - g[0]))
    return out


def clock_offset(busy: Busy, steps: Sequence[Event]) -> float:
    """Device clock minus host clock: the middle of the offsets at which the
    step spans, shifted by it, hold the most device busy time."""
    if not steps:
        return 0.0
    n = int(round(OFFSET_RANGE_S / OFFSET_GRID_S))
    cands = [k * OFFSET_GRID_S for k in range(-n, n + 1)]
    score = [sum(busy.within(sp.start + d, sp.end + d) for sp in steps)
             for d in cands]
    top = max(score)
    best = [d for d, sc in zip(cands, score) if sc >= top - 1e-9 * len(steps)]
    return (best[0] + best[-1]) / 2


def _shift(spans: Sequence[Event], d: float) -> List[Event]:
    return [Event(sp.name, sp.start + d, sp.end + d) for sp in spans]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over devices
    step_busy_s: List[float]            # per bench.step span, mean over devices
    step_dur_s: List[float]
    device_ops: List[Tuple[str, float]]  # top operations, seconds per device
    idle_gaps: List[Tuple[str, float]]   # longest gaps with their span
    offset_s: float                      # device clock minus host clock


def reduce(trace: TraceData) -> Reduction:
    windows = [sp for sp in trace.spans if sp.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    if not trace.device_ops:
        raise ValueError("the trace holds no device operations")
    devices = sorted(trace.device_ops)
    busy = [Busy(trace.device_ops[d]) for d in devices]
    n = len(busy)
    w = windows[0]
    steps = [sp for sp in trace.spans
             if sp.name == STEP and w.start <= sp.start < w.end]
    off = clock_offset(busy[0], steps)
    spans, steps = _shift(trace.spans, off), _shift(steps, off)
    a, b = w.start + off, w.end + off
    per_op: Dict[str, float] = defaultdict(float)
    for d in devices:
        for e in trace.device_ops[d]:
            per_op[e.name] += _overlap((e.start, e.end), (a, b)) / n
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = label_gaps(busy[0].gaps(a, b), spans)
    return Reduction(
        window_s=b - a,
        busy_s=sum(x.within(a, b) for x in busy) / n,
        step_busy_s=[sum(x.within(sp.start, sp.end) for x in busy) / n
                     for sp in steps],
        step_dur_s=[sp.end - sp.start for sp in steps],
        device_ops=top,
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:TOP],
        offset_s=off)
