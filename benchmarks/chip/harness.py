"""One run of one cell: set-up, a timed window, a trace, the check.

The window drives ``repro.serve.loop.Server`` through ``submit`` and
``step`` only.  Every time is the harness's own ``time.perf_counter()``,
taken after each ``step`` returns (``step`` ends by copying the argmax to
the host, so the device work it launched is done); tokens are stamped by
watching each request's ``out_tokens`` grow.  Nothing is read from the
program's request timestamps or its latency summaries.

Two loops, chosen by the mix's arrivals:

* ``backlog``: every request is queued before the window.  The first step
  fills every slot with the mix's steady fill (``traffic``): each slot holds
  a request partway through its answer, as slots stand once the loop has
  run for a while, so requests finish and others are admitted all through
  the window.  The window opens after that step and ends with the first
  step that returns after ``seconds``.  ``output_tokens_per_s`` is every
  token emitted in it over its length.
* ``gamma``: an open loop.  Requests are due on a schedule that starts
  ``preroll_s`` before the window; each is submitted at the first loop turn
  after it is due.  Time to first token counts from the due time; a request
  due in the window that has no first token when it closes enters the tail
  with the time it waited.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from chip import cells, check, counts, layer, reduce, traffic, weights
from chip.cells import Cell
from chip.layer import Step

TRACE_SECONDS = 10.0            # longest part of the window a trace records
WARM_NEW = 2                    # tokens per warm-up request: prefill + decode
WARM_UID = 1 << 40              # warm-up request ids, apart from the traffic's
CHECK_WAIT_S = 60.0             # longest wait after the window for the sample


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileLog:
    """Backend compiles and persistent-cache hits, as JAX reports them
    through ``jax.monitoring`` (after ``chip_smoke.CompileLog``)."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.compiles, self.seconds, self.hits, self.misses)

    def since(self, snap) -> Dict[str, float]:
        c, s, h, m = snap
        return {"compiles": self.compiles - c, "compile_s": self.seconds - s,
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """The benchmark measures on the TPU only; nothing falls back."""
    dev = device_info()
    if dev["platform"] != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (first device is "
                         f"{dev['platform']}: {dev['kind']}); refusing to run")
    if dev["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {dev['count']}")
    return dev


def memory_peak_bytes(chips: int) -> Optional[int]:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def percentile(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


class Tracer:
    """Profiler trace of the first ``TRACE_SECONDS`` of the window, with the
    harness's spans.  Disabled, every call is free and records nothing."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.open = False
        self.until = 0.0
        self._window = None
        self.steps: List[Step] = []

    def span(self, name: str):
        if self.enabled and self.open:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def start(self) -> None:
        if self.enabled:
            self.dir = tempfile.mkdtemp(prefix="chip-trace-")
            jax.profiler.start_trace(self.dir)

    def open_window(self, t0: float, seconds: float) -> None:
        if self.enabled:
            self.until = t0 + min(seconds, TRACE_SECONDS)
            self._window = jax.profiler.TraceAnnotation(reduce.WINDOW)
            self._window.__enter__()
            self.open = True

    def record(self, step: Step) -> None:
        """Keep a step of the traced window; close it once it is long enough."""
        if self.open:
            self.steps.append(step)
            if step.end >= self.until:
                self.close()

    def close(self) -> None:
        if self.open:
            self._window.__exit__(None, None, None)
            self.open = False
            jax.profiler.stop_trace()

    def reduction(self) -> reduce.Reduction:
        self.close()
        try:
            return reduce.reduce(reduce.from_xplane(reduce.find_xplane(
                Path(self.dir))))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Stamps:
    """Per-request token counts and host times, from watching ``out_tokens``."""
    lens: Dict[int, int] = dataclasses.field(default_factory=dict)
    first: Dict[int, float] = dataclasses.field(default_factory=dict)
    last: Dict[int, float] = dataclasses.field(default_factory=dict)


def serve_step(server, stamps: Stamps, tracer: Tracer):
    """One ``Server.step``; returns its record and the tokens it emitted."""
    k0 = len(server.completed)
    t0 = time.perf_counter()
    with tracer.span(reduce.STEP):
        server.step()
    t1 = time.perf_counter()
    admitted, ctxs, emitted = [], [], 0
    for r in [r for r in server.active if r is not None] + server.completed[k0:]:
        before, after = stamps.lens.get(r.uid, 0), len(r.out_tokens)
        if after == before:
            continue
        if before == 0:
            admitted.append(len(r.prompt))
            stamps.first[r.uid] = t1
        if after > before + (before == 0):          # decoded in this step
            ctxs.append(len(r.prompt) + after - 1)
        stamps.lens[r.uid] = after
        stamps.last[r.uid] = t1
        emitted += after - before
    step = Step(t0, t1, tuple(admitted), tuple(ctxs))
    tracer.record(step)
    return step, emitted


def make_request(seed: int, uid: int, prompt_len: int, max_new: int,
                 vocab: int):
    from repro.serve.loop import Request
    return Request(uid=uid, prompt=traffic.prompt_tokens(seed, uid, prompt_len,
                                                         vocab),
                   max_new=max_new)


def build_server(cell: Cell, seed: int):
    """The cell's ``Server`` on weights made from the seed."""
    from repro.models import model as lm
    from repro.serve.loop import Server
    cfg = cells.program_config(cell)
    expected = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    params = weights.make(cell.shape, seed)
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(got) != jax.tree.structure(expected) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(got), jax.tree.leaves(expected))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")
    jax.block_until_ready(params)
    server = Server(cfg, params, slots=cell.mix["slots"],
                    cache_len=cell.mix["cache_len"], wall=True)
    return server, params


def warm(server, cell: Cell, seed: int) -> None:
    """Serve one request of every prompt-grid length through ``server`` and
    drain it: compiles prefill, splice, first-token selection and decode."""
    for i, n in enumerate(cell.grid):
        server.submit(make_request(seed, WARM_UID + i, n, WARM_NEW,
                                   cell.shape.vocab))
    server.run_until_drained()
    server.completed.clear()


@dataclasses.dataclass
class WindowResult:
    t0: float
    t_end: float
    steps: List[Step]
    tokens: int                 # emitted in the window
    attempted: int
    ttft_s: List[float]
    tpot_s: List[float]
    late_s: List[float]         # submit time minus due time, open loop
    queued: int = 0             # requests waiting when the window closed
    due: List[float] = dataclasses.field(default_factory=list)  # of ttft_s


def closed_loop(server, cell: Cell, seed: int, seconds: float,
                tracer: Tracer, on_open: Callable[[], None]) -> "WindowResult":
    plans = traffic.plan(cell.mix, seed, seconds)
    for p in plans:
        server.submit(make_request(seed, p.uid, p.prompt_len, p.max_new,
                                   cell.shape.vocab))
    stamps = Stamps()
    serve_step(server, stamps, tracer)          # fills every slot
    tracer.start()
    on_open()
    t0 = time.perf_counter()
    tracer.open_window(t0, seconds)
    steps, tokens = [], 0
    served = set()
    while True:
        if not server.queue:
            raise RuntimeError(f"backlog ran dry after {len(steps)} steps: "
                               f"the mix holds too few requests")
        step, n = serve_step(server, stamps, tracer)
        steps.append(step)
        tokens += n
        if step.end >= t0 + seconds:
            break
    for r in [r for r in server.active if r is not None] + server.completed:
        if stamps.last.get(r.uid, 0.0) > t0:
            served.add(r.uid)
    return WindowResult(t0=t0, t_end=steps[-1].end, steps=steps,
                        tokens=tokens, attempted=len(served),
                        ttft_s=[], tpot_s=[], late_s=[])


def open_loop(server, cell: Cell, seed: int, seconds: float,
              tracer: Tracer, on_open: Callable[[], None]) -> "WindowResult":
    arr = cell.mix["arrivals"]
    plans = traffic.plan(cell.mix, seed, seconds)
    reqs = [make_request(seed, p.uid, p.prompt_len, p.max_new,
                         cell.shape.vocab) for p in plans]
    stamps = Stamps()
    tracer.start()
    origin = time.perf_counter()
    due = [origin + p.due_s for p in plans]
    t0 = origin + arr["preroll_s"]
    opened = False
    steps: List[Step] = []
    submitted: Dict[int, float] = {}
    i, now, tokens = 0, origin, 0
    while True:
        now = time.perf_counter()
        if not opened and now >= t0:
            on_open()
            t0, opened = now, True
            tracer.open_window(t0, seconds)
        if opened and now >= t0 + seconds:
            break
        while i < len(reqs) and due[i] <= now:
            server.submit(reqs[i])
            submitted[reqs[i].uid] = now
            i += 1
        if server.queue or any(r is not None for r in server.active):
            step, n = serve_step(server, stamps, tracer)
            if opened:
                steps.append(step)
                tokens += n
        else:
            wake = min(due[i] if i < len(reqs) else np.inf,
                       t0 + seconds if opened else t0)
            with tracer.span("bench.wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
    t_end = now
    in_window = [k for k in range(len(reqs)) if t0 <= due[k] < t0 + seconds]
    ttft, late = [], []
    for k in in_window:
        uid = reqs[k].uid
        first = stamps.first.get(uid)
        ttft.append((first if first is not None and first <= t_end else t_end)
                    - due[k])
        if uid in submitted:
            late.append(submitted[uid] - due[k])
    tpot = [(stamps.last[r.uid] - stamps.first[r.uid]) / (len(r.out_tokens) - 1)
            for r in server.completed
            if len(r.out_tokens) >= 2 and t0 < stamps.last[r.uid] <= t_end]
    return WindowResult(t0=t0, t_end=t_end, steps=steps, tokens=tokens,
                        attempted=len(in_window), ttft_s=ttft, tpot_s=tpot,
                        late_s=late, queued=len(server.queue),
                        due=[due[k] for k in in_window])


def end_to_end(res: WindowResult, setup_s: float) -> Dict[str, float]:
    """Every end-to-end quantity the harness knows, by metric name."""
    out = {"setup_s": setup_s}
    if res.tokens:
        out["output_tokens_per_s"] = res.tokens / (res.t_end - res.t0)
    if res.ttft_s:
        out["ttft_p95_ms"] = 1e3 * percentile(res.ttft_s, 95)
    if res.tpot_s:
        out["tpot_p95_ms"] = 1e3 * percentile(res.tpot_s, 95)
    return out


def finish_sample(server, k: int) -> None:
    """After the window, keep serving (untimed) until ``k`` requests have
    finished, for at most ``CHECK_WAIT_S``: the check samples finished
    requests, and a short window may have finished too few."""
    deadline = time.perf_counter() + CHECK_WAIT_S
    while (len(server.completed) < k and time.perf_counter() < deadline
           and (server.queue or any(r is not None for r in server.active))):
        server.step()


def finished(server) -> List[check.Served]:
    return [check.Served(np.asarray(r.prompt, np.int32),
                         np.asarray(r.out_tokens, np.int32))
            for r in server.completed]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def prepare(workload: str, root: Path = cells.REPO_ROOT):
    """Resolve the cell, insist on its chips, turn on the compile cache."""
    from repro.launch.compile_cache import enable_compile_cache
    cell = cells.resolve(workload, root)
    dev = require_chips(cell.chips)
    peaks = counts.peaks(dev["kind"])
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device {dev['platform']} {dev['kind']} x{dev['count']}; compile "
        f"cache {cache_dir}")
    return cell, dev, peaks


def measure(cell: Cell, dev: dict, peaks: dict, seed: int, seconds: float,
            trace: bool, started: float):
    """Set-up and the window of one run.  Returns the result line without
    its check, and the sample of finished requests the check compares; the
    program's state is gone when it returns."""
    clock = CompileLog()
    snap = clock.snapshot()
    log(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(trace)}")
    t = time.perf_counter()
    server, params = build_server(cell, seed)
    log(f"weights and server {time.perf_counter() - t:.3f}s "
        f"({weights.nbytes(cell.shape)} bytes of weights)")
    t = time.perf_counter()
    warm(server, cell, seed)
    log(f"warm-up of {len(cell.grid)} prompt lengths "
        f"{time.perf_counter() - t:.3f}s")

    tracer = Tracer(trace)
    opened = {}
    loop = closed_loop if cell.mix["arrivals"]["kind"] == "backlog" \
        else open_loop
    res = loop(server, cell, seed, seconds, tracer,
               lambda: opened.update(clock.since(snap)))
    setup_s = res.t0 - started
    late = clock.since(snap)
    tracer.close()
    finish_sample(server, cell.mix["check_sample"])
    log(f"set-up {setup_s:.3f}s: compiles {opened['compiles']} "
        f"({opened['compile_s']:.3f}s), cache hits {opened['cache_hits']}, "
        f"misses {opened['cache_misses']}")
    log(f"window {res.t_end - res.t0:.3f}s: steps {len(res.steps)}, "
        f"admissions {sum(len(s.admitted) for s in res.steps)}, tokens "
        f"{res.tokens}, requests attempted {res.attempted}, completed "
        f"{len(server.completed)}, queued at close {res.queued}")
    log(f"compiles in window {late['compiles'] - opened['compiles']}, cache "
        f"hits in window {late['cache_hits'] - opened['cache_hits']}")
    if res.late_s:
        log(f"generator late: median {1e3 * percentile(res.late_s, 50):.3f}ms "
            f"p99 {1e3 * percentile(res.late_s, 99):.3f}ms max "
            f"{1e3 * max(res.late_s):.3f}ms over {len(res.late_s)} requests")

    device = dict(dev, memory_peak_bytes=memory_peak_bytes(cell.chips))
    result = {"correct": False, "attempted": res.attempted,
              "failed": len(server.failed_requests)}
    if trace:
        red = tracer.reduction()
        log(f"trace: window {red.window_s:.6f}s, busy {red.busy_s:.6f}s, "
            f"device clock minus host clock {1e3 * red.offset_s:.3f}ms")
        steps = tracer.steps
        matched = len(red.step_busy_s) == len(steps)
        if not matched:
            log(f"trace holds {len(red.step_busy_s)} step spans, the harness "
                f"{len(steps)}: step-based metrics left out")
        win = layer.Window(
            shape=cell.shape, peaks=peaks, window_s=red.window_s,
            busy_s=red.busy_s, steps=steps,
            step_busy_s=red.step_busy_s if matched else None,
            step_dur_s=red.step_dur_s if matched else None)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](win)
            if v is None:
                log(f"{m['name']}: nothing to read in this window")
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": [list(x) for x in red.device_ops],
                               "idle_gaps": [list(x) for x in red.idle_gaps]}
    else:
        known = end_to_end(res, setup_s)
        result["metrics"] = {m["name"]: {"value": known[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    served = check.sample(finished(server), cell.mix["check_sample"], seed)
    log(f"check sample: {len(served)} finished requests")
    del server, params
    gc.collect()
    return result, served


def judge(cell: Cell, seed: int, served, control: bool = False) -> check.Gaps:
    """The reference's reading of the served sample, on weights made anew."""
    t = time.perf_counter()
    gaps = check.gaps(weights.make(cell.shape, seed), served, cell.shape,
                      check.reference_length(cell.mix), control=control)
    log(f"reference over {gaps.requests} requests, {gaps.tokens} served "
        f"tokens: {time.perf_counter() - t:.3f}s")
    return gaps


def compare(cell: Cell, gap: Optional[float]):
    """``correct`` and the numbers compared, each beside its limit: the
    widest gap is at most the configuration's limit.  No reading fails."""
    limit = float(cell.config["check"]["max_logit_gap"])
    return (gap is not None and gap <= limit,
            {"max_logit_gap": {"value": gap, "limit": limit}})


def run(workload: str, seed: int, seconds: float, trace: bool,
        started: float, root: Path = cells.REPO_ROOT) -> dict:
    """Run one cell and return its result line (the contract's object)."""
    cell, dev, peaks = prepare(workload, root)
    result, served = measure(cell, dev, peaks, seed, seconds, trace, started)
    gap = judge(cell, seed, served).max_gap if served else None
    result["correct"], result["check"] = compare(cell, gap)
    for name, c in result["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv: Optional[Sequence[str]] = None, started: float = 0.0) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    result = run(a.workload, a.seed, a.seconds, bool(a.trace),
                 started or time.perf_counter())
    print(json.dumps(result), flush=True)
    return 0
