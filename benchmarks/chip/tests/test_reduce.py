"""Trace reduction and the per-layer readers, against hand-computed values.

The trace below is small and written out by hand, on one device:

  window  [0, 100]
  step 1  [10, 30]  admitted one prompt of 64 tokens
  wait    [30, 40]
  step 2  [40, 60]  admitted nothing
  step 3  [60, 90]  admitted nothing
  ops     [12, 20] a, [18, 28] b (overlap: busy 12-28 = 16),
          [45, 55] c (10), [62, 70] c, [72, 80] d (8 + 8), [95, 110] a
          (5 inside the window)

busy = 16 + 10 + 16 + 5 = 47; idle share 53%; steps busy 16, 10, 16;
decode per step (steps 2, 3) = 13; admission = 16 - 13 = 3 for 1 request;
host time per step = mean(20 - 16, 20 - 10, 30 - 16) = 28 / 3.
"""
from __future__ import annotations

import pytest

from chip import counts, layer, reduce
from chip.layer import Step
from chip.reduce import Event, TraceData
from chip.shapes import Shape


def trace() -> TraceData:
    ops = [Event("a", 12, 20), Event("b", 18, 28), Event("c", 45, 55),
           Event("c", 62, 70), Event("d", 72, 80), Event("a", 95, 110)]
    spans = [Event("bench.window", 0, 100), Event("bench.step", 10, 30),
             Event("bench.wait", 30, 40), Event("bench.step", 40, 60),
             Event("bench.step", 60, 90)]
    return TraceData(device_ops={"/device:TPU:0": ops}, spans=spans)


def test_busy_is_the_union_of_operations():
    r = reduce.reduce(trace())
    assert r.window_s == 100
    assert r.busy_s == pytest.approx(47)
    assert r.step_busy_s == pytest.approx([16, 10, 16])
    assert r.step_dur_s == pytest.approx([20, 20, 30])


def test_idle_gaps_are_attributed_to_harness_spans():
    r = reduce.reduce(trace())
    # gaps: [0,12] 12 (step 10-30 overlaps 2, outside 10 -> step),
    # [28,45] 17 (wait 10 > step 5, step 2), [55,62] 7, [70,72] 2,
    # [80,95] 15 (step 3 overlaps 10, then nothing)
    assert r.idle_gaps == [("bench.wait", 17), ("bench.step", 15),
                           ("bench.step", 12), ("bench.step", 7),
                           ("bench.step", 2)]


def test_gap_outside_every_span():
    t = TraceData(device_ops={"/device:TPU:0": [Event("x", 5, 6)]},
                  spans=[Event("bench.window", 0, 10)])
    assert reduce.reduce(t).idle_gaps == [("outside", 5), ("outside", 4)]


def test_top_operations_clip_to_the_window():
    r = reduce.reduce(trace())
    assert dict(r.device_ops) == pytest.approx(
        {"a": 13, "b": 10, "c": 18, "d": 8})
    assert r.device_ops[0] == ("c", 18)


def test_busy_averages_over_devices():
    t = trace()
    t.device_ops["/device:TPU:1"] = [Event("z", 0, 100)]
    r = reduce.reduce(t)
    assert r.busy_s == pytest.approx((47 + 100) / 2)


def test_busy_within_clips_partial_intervals():
    b = reduce.Busy([Event("x", 0, 10), Event("y", 20, 30)])
    assert b.within(5, 25) == pytest.approx(10)
    assert b.within(12, 18) == 0
    assert b.within(-5, 40) == pytest.approx(20)
    assert b.within(2, 4) == pytest.approx(2)
    assert b.gaps(5, 25) == [(10, 20)]


def test_no_device_operations_is_an_error():
    t = TraceData(device_ops={}, spans=[Event("bench.window", 0, 1)])
    with pytest.raises(ValueError, match="no device operations"):
        reduce.reduce(t)


def window() -> layer.Window:
    s = Shape(family="decoder", layers=2, d_model=64, vocab=500, norm_eps=1e-6,
              heads=4, kv_heads=2, head_dim=16, d_ff=128, rope_theta=1e6,
              qk_norm=True)
    r = reduce.reduce(trace())
    steps = [Step(10, 30, (64,), (65, 20)), Step(40, 60, (), (66, 21)),
             Step(60, 90, (), (67, 22))]
    return layer.Window(shape=s, peaks={"bf16_flops_per_s": 1e9,
                                        "hbm_bytes_per_s": 1e6},
                        window_s=r.window_s, busy_s=r.busy_s, steps=steps,
                        step_busy_s=r.step_busy_s, step_dur_s=r.step_dur_s)


def test_admission_and_decode_split():
    w = window()
    assert layer.device_idle_pct(w) == pytest.approx(53)
    assert layer.decode_ms_per_step(w) == pytest.approx(13e3)
    assert layer.admit_ms_per_request(w) == pytest.approx(3e3)
    assert layer.server_host_ms_per_step(w) == pytest.approx(28e3 / 3)


def test_roofline_and_mfu_from_counts():
    w = window()
    s = w.shape
    need = counts.decode_step_bytes(s, (66, 21)) + \
        counts.decode_step_bytes(s, (67, 22))
    assert layer.decode_roofline(w) == pytest.approx(100 * need / 1e6 / 26)
    flops = counts.prefill_flops(s, 64) + sum(
        counts.decode_token_flops(s, c) for c in (65, 20, 66, 21, 67, 22))
    assert layer.step_mfu_pct(w) == pytest.approx(100 * flops / (100 * 1e9))
    assert layer.admit_mfu_pct(w) == pytest.approx(
        100 * counts.prefill_flops(s, 64) / (3 * 1e9))


def test_readers_return_none_with_nothing_to_read():
    w = window()
    w.steps = [Step(0, 1, (8,), (9,))]      # every step admitted
    w.step_busy_s, w.step_dur_s = [0.5], [1.0]
    assert layer.decode_ms_per_step(w) is None
    assert layer.admit_ms_per_request(w) is None
    assert layer.decode_roofline(w) is None
    w.step_busy_s = None                     # trace and record disagree
    assert layer.server_host_ms_per_step(w) is None


def test_spans_read_back_from_a_recorded_trace(tmp_path):
    """A trace the profiler records here (CPU, so no device plane): the
    harness's spans come back in order and nested in the window."""
    import time

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation(reduce.STEP):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    t = reduce.from_xplane(reduce.find_xplane(tmp_path))
    names = [s.name for s in t.spans]
    assert names == [reduce.WINDOW] + [reduce.STEP, "bench.wait"] * 3
    win = t.spans[0]
    assert all(win.start <= s.start <= s.end <= win.end for s in t.spans[1:])
    waits = [s.end - s.start for s in t.spans if s.name == "bench.wait"]
    assert min(waits) >= 0.002
    assert t.device_ops == {}


def test_recorded_chip_trace():
    """``tests/data/probe.xplane.pb``: recorded on one TPU v5 lite, three
    steps each running one 90.1 us fusion (plus a copy-start/copy-done of
    16 ns) and a wait.  By hand from the trace: each operation sits 0.97-
    1.05 ms before the start of the step span that launched it, so the
    clock offset lies in [-1.784, -1.047] ms, the intersection of the
    three steps' feasible shifts."""
    from pathlib import Path
    t = reduce.from_xplane(Path(__file__).parent / "data" / "probe.xplane.pb")
    assert sorted({e.name for e in t.device_ops["/device:TPU:0"]}) == [
        "%convolution_reduce_fusion", "%copy-done", "%copy-start"]
    r = reduce.reduce(t)
    assert -1.784e-3 <= r.offset_s <= -1.047e-3
    assert r.step_busy_s == pytest.approx([90.124e-6, 90.123e-6, 90.125e-6],
                                          abs=2e-9)
    assert r.busy_s == pytest.approx(270.372e-6, abs=5e-9)
    assert r.window_s == pytest.approx(20013.474e-6, abs=1e-9)
    # the waits hold the longest gaps
    assert [g[0] for g in r.idle_gaps[:3]] == ["bench.wait"] * 3
