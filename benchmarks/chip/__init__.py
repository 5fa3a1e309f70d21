"""Chip benchmark of the serving path (``repro.serve.loop.Server``) on TPU.

``run.py`` is the entry point.  Everything that belongs to one model
configuration, one traffic mix or one per-layer metric is a file of its own,
found by the name that ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the published configuration, what the run
  changes from the registry and why, the correctness limit;
* ``traffic/<mix>.json``: arrivals, length distributions, prompt grid, slots;
* ``metrics/<metric>.py``: one reader per per-layer metric.

The yardstick lives here too: traffic generation (``traffic``), the weights
(``weights``), the operation and byte counts (``counts``) with the table of
peaks (``peaks.json``), the reduction of profiler traces (``reduce``), the
float32 reference (``reference``) and the comparison that decides
``correct`` (``check``).  Nothing here imports the program except
``harness`` and ``cells``, which drive the system under test.
"""
