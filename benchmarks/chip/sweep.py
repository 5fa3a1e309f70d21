#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate the server
sustains without a growing queue.

  python3 benchmarks/chip/sweep.py --workload <open-loop cell> \\
      --rates 2,3,4,5 --seconds 20 --seed 1

One process: set-up once, then one window per rate on the same warmed
``Server``, emptied between rates.  Each rate prints one JSON line: requests
due in the window, first tokens and completions in it, the queue when it
closed, and time to first token over the first and the last third of the
window (a queue that grows makes the last third slower).  Not run by the
benchmark; a benchmark PR runs it once to fix a mix's ``rate_rps``.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT / "src"), str(_ROOT / "benchmarks")]

from chip import harness  # noqa: E402


def thirds(res, seconds):
    """Median time to first token of requests due in the first and in the
    last third of the window."""
    out = []
    for lo, hi in ((0, seconds / 3), (2 * seconds / 3, seconds)):
        xs = [t for t, d in zip(res.ttft_s, res.due) if lo <= d - res.t0 < hi]
        out.append(1e3 * harness.percentile(xs, 50) if xs else None)
    return out


def sweep(workload: str, rates, seconds: float, seed: int,
          root: Path = _ROOT):
    """One line of readings per rate, on one warmed ``Server``."""
    cell, dev, _ = harness.prepare(workload, root)
    server, _ = harness.build_server(cell, seed)
    harness.warm(server, cell, seed)
    harness.log(f"set-up {time.perf_counter() - STARTED:.3f}s")
    for rate in rates:
        arrivals = dict(cell.mix["arrivals"], rate_rps=rate)
        c = dataclasses.replace(cell, mix=dict(cell.mix, arrivals=arrivals))
        res = harness.open_loop(server, c, seed, seconds,
                                harness.Tracer(False), lambda: None)
        first, last = thirds(res, seconds)
        yield {
            "rate_rps": rate, "due": res.attempted,
            "first_tokens": sum(len(s.admitted) for s in res.steps),
            "completed_per_s": len(res.tpot_s) / (res.t_end - res.t0),
            "queued_at_close": res.queued,
            "ttft_p50_ms": 1e3 * harness.percentile(res.ttft_s, 50),
            "ttft_p95_ms": 1e3 * harness.percentile(res.ttft_s, 95),
            "ttft_p50_ms_first_third": first, "ttft_p50_ms_last_third": last,
            "tpot_p95_ms": 1e3 * harness.percentile(res.tpot_s, 95),
            "device": dev}
        # drop what is queued or in flight: every admission overwrites its
        # slot's cache, so the next rate starts from an empty server
        server.queue.clear()
        server.active[:] = [None] * server.slots
        server.completed.clear()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    for line in sweep(a.workload, [float(r) for r in a.rates.split(",")],
                      a.seconds, a.seed):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
