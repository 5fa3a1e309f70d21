#!/usr/bin/env python3
"""Chip benchmark entry point: one run of one cell.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  The last line of standard output is the result object; the
numbers the check compared, each beside its limit, are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell needs, it
exits non-zero and prints no result.
"""
import time

STARTED = time.perf_counter()   # set-up counts from process start

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT / "src"), str(_ROOT / "benchmarks")]

from chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(started=STARTED))
