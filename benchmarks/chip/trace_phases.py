#!/usr/bin/env python3
"""One traced window of a cell, reduced by program, layer scope and host phase.

  python3 benchmarks/chip/trace_phases.py --workload <cell> --seed <n> \\
      --seconds <s> [--keep <dir>]

Run from the root of a checkout, on the chip.  It serves the cell as
``run.py --trace 1`` does (same set-up, warm-up and loop; the profiler
records the first ``harness.TRACE_SECONDS`` of the window), then reduces the
trace twice: with ``reduce`` into the cell's per-layer metrics, and with
``phases`` into program time, decode self time by layer scope, admission
device time, cache positions and idle time by host span.  The steps after
the trace closes run untraced, so the window's decode-only steps give the
tracing overhead.  The last line of standard output is a JSON object; no
correctness check runs.  ``--keep`` copies the ``.xplane.pb`` there.
"""
import time

STARTED = time.perf_counter()

import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT / "src"), str(_ROOT / "benchmarks")]

from chip import cells, harness, layer, phases, reduce  # noqa: E402

SPAN_COST_CALLS = 100_000


class PhaseTracer(harness.Tracer):
    """The harness's tracer, keeping the ``.xplane.pb`` until it is read."""

    def __init__(self, keep=None):
        super().__init__(True)
        self.keep = keep
        self.closed_at = None

    def close(self):
        if self.open:
            self.closed_at = time.perf_counter()
        super().close()

    def read(self):
        self.close()
        try:
            path = reduce.find_xplane(Path(self.dir))
            if self.keep:
                Path(self.keep).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, Path(self.keep) / path.name)
            return reduce.from_xplane(path), phases.read(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span_cost_ns() -> dict:
    """ns per span with no trace running: ``jax.profiler.TraceAnnotation``,
    which ``repro.obs.span`` opens."""
    from jax.profiler import TraceAnnotation
    out = {}
    for label, args in (("no_args", {}),
                        ("three_args", {"live": 24, "kv_live": 18504,
                                        "kv_scanned": 49152})):
        t = time.perf_counter()
        for _ in range(SPAN_COST_CALLS):
            with TraceAnnotation("server.decode", **args):
                pass
        out[label] = 1e9 * (time.perf_counter() - t) / SPAN_COST_CALLS
    return out


def decode_only_ms(steps):
    xs = [s.end - s.start for s in steps if not s.admitted and s.ctxs]
    return (1e3 * sum(xs) / len(xs), len(xs)) if xs else (None, 0)


def run(workload: str, seed: int, seconds: float, keep=None,
        root: Path = cells.REPO_ROOT) -> dict:
    cell, dev, peaks = harness.prepare(workload, root)
    server, params = harness.build_server(cell, seed)
    harness.warm(server, cell, seed)
    tracer = PhaseTracer(keep)
    loop = harness.closed_loop if cell.mix["arrivals"]["kind"] == "backlog" \
        else harness.open_loop
    res = loop(server, cell, seed, seconds, tracer, lambda: None)
    tracer.close()
    setup_s = res.t0 - STARTED
    t = time.perf_counter()
    trace, server_trace = tracer.read()
    red = reduce.reduce(trace)
    ph = phases.reduce_phases(trace, server_trace, red.offset_s)
    reduce_s = time.perf_counter() - t

    steps = tracer.steps
    matched = len(red.step_busy_s) == len(steps)
    win = layer.Window(
        shape=cell.shape, peaks=peaks, window_s=red.window_s,
        busy_s=red.busy_s, steps=steps,
        step_busy_s=red.step_busy_s if matched else None,
        step_dur_s=red.step_dur_s if matched else None)
    traced_ms, n_traced = decode_only_ms(steps)
    untraced_ms, n_untraced = decode_only_ms(
        [s for s in res.steps if s.start > (tracer.closed_at or res.t_end)])
    (w,) = [sp for sp in trace.spans if sp.name == reduce.WINDOW]
    n_spans = sum(w.start <= sp.start < w.end for sp in server_trace.spans)
    decode = ph.programs.get(phases.DECODE)
    result = {
        "device": dev,
        "setup_s": setup_s,
        "reduce_s": reduce_s,
        "window_s": red.window_s, "busy_s": red.busy_s,
        "offset_ms": 1e3 * red.offset_s,
        "steps_traced": len(steps), "server_steps": ph.steps,
        "existing": {m["name"]: cell.readers[m["name"]](win)
                     for m in cell.per_layer},
        "phases": {k: f(ph) for k, f in phases.METRICS.items()},
        "decode_unattributed_pct": phases.decode_unattributed_pct(ph),
        "programs": {k: {"runs": v.runs, "device_ms_per_run":
                         1e3 * v.device_s / v.runs,
                         "scopes_ms_per_run": {s: 1e3 * x / v.runs
                                               for s, x in v.scopes.items()},
                         "unattributed_ms_per_run":
                         1e3 * v.unattributed_s / v.runs}
                     for k, v in ph.programs.items() if v.runs},
        "device_scopes": [list(x) for x in phases.device_scopes(ph)],
        "device_ops": [list(x) for x in red.device_ops],
        "idle_ms_per_step_by_span": {
            k: 1e3 * v / max(ph.steps, 1)
            for k, v in sorted(ph.idle_by_span.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [list(x) for x in ph.idle_gaps],
        "idle_gaps_reduce": [list(x) for x in red.idle_gaps],
        "server_spans_per_step": n_spans / max(ph.steps, 1),
        "admissions_traced": len(ph.admit_busy_s),
        "overhead": {"span_ns_untraced": span_cost_ns(),
                     "decode_step_ms_traced": traced_ms,
                     "decode_steps_traced": n_traced,
                     "decode_step_ms_untraced": untraced_ms,
                     "decode_steps_untraced": n_untraced},
        "decode_runs": decode.runs if decode else 0,
    }
    return result


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    a = ap.parse_args(argv)
    print(json.dumps(run(a.workload, a.seed, a.seconds, a.keep)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
