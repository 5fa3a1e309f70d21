#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program's and the control's.

  python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
      --seconds 30

For each seed, in one process: a run's set-up and window at the cell's own
size and load, then the float32 reference over the sample of finished
requests, reading two widest gaps: that of the tokens the program served
(the lower reading, as every run reads it) and that of the tokens the
float8 control would pick at the same positions (the upper reading, which
the limit has to stay below).  Each is judged by the harness's own
comparison: the program's ``correct`` has to come out true and the
control's false.  Not run by the benchmark's own runs.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT / "src"), str(_ROOT / "benchmarks")]

from chip import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args()
    cell, dev, peaks = harness.prepare(a.workload)
    for seed in (int(x) for x in a.seeds.split(",")):
        _, served = harness.measure(cell, dev, peaks, seed, a.seconds, False,
                                    time.perf_counter())
        g = harness.judge(cell, seed, served, control=True)
        line = {"workload": a.workload, "seed": seed, "tokens": g.tokens,
                "requests": g.requests}
        for who, gap in (("program", g.max_gap), ("control", g.control_gap)):
            correct, numbers = harness.compare(cell, gap)
            line[who] = {"correct": correct, "check": numbers}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
