"""Program time, scope self time and idle time by host phase (``phases``).

The hand-made trace below runs on one device, clock offset 0:

  window   [0, 100]
  modules  server_decode (id 7) [10, 30] and [50, 70]; server_prefill (id 9)
           [40, 45]
  decode   %while.1 [10, 28] holds %fusion.1 [11, 15] (attn), %fusion.2
  (run 1)  [15, 18] (mlp), %ds [18, 20] (the scan's dynamic_slice), %copy.3
           [20, 21] (no op_name); then %fusion.9 [28, 29.5] (head).  Self
           time: while 8, slice 2, copy 1 (stack 11), attn 4, mlp 3, head
           1.5; [29.5, 30] runs nothing: 0.5 unattributed.  Run 2 is run 1
           shifted by 40.
  prefill  %fusion.1 [40, 44], an attn operation of program 9.
  host     bench.step [8, 34] > server.step [8.5, 33.5] > server.decode
           [9, 10], server.sample [10, 33];
           bench.step [36, 74] > server.step [36.5, 73.5] > server.admit
           [37, 47] (> prefill [37, 39], splice [39, 40], first_token
           [40, 46.5]), server.decode [48, 50], server.sample [50, 73]

Idle: [0, 10], [29.5, 40], [44, 50], [69.5, 100].  By innermost span:
sample 3.5 + 3.5, first_token 2.5, server.step 0.5 * 4 + 1, decode 1 + 2,
prefill 2, splice 1, admit 0.5, bench.step 0.5 * 4, outside 8 + 2 + 26.
"""
from __future__ import annotations

from pathlib import Path

import pytest

from chip import family, layer, phases, reduce
from chip.layer import Step
from chip.phases import ServerTrace, Span
from chip.reduce import Event, TraceData

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"
DECODE_OPS = {"%while.1": "jit(server_decode)/stack/while",
              "%fusion.1": "jit(server_decode)/stack/while/body/closed_call/"
                           "attn/dot_general:",
              "%fusion.2": "jit(server_decode)/stack/while/body/closed_call/"
                           "mlp/dot_general:",
              "%ds": "jit(server_decode)/stack/while/body/dynamic_slice:",
              "%copy.3": "",
              "%fusion.9": "jit(server_decode)/head/dot_general:"}


def decode_run(t0: float):
    return [Event("%while.1", t0, t0 + 18), Event("%fusion.1", t0 + 1, t0 + 5),
            Event("%fusion.2", t0 + 5, t0 + 8), Event("%ds", t0 + 8, t0 + 10),
            Event("%copy.3", t0 + 10, t0 + 11),
            Event("%fusion.9", t0 + 18, t0 + 19.5)]


BENCH = [Event("bench.window", 0, 100), Event("bench.step", 8, 34),
         Event("bench.step", 36, 74)]
SERVER = [
    Span("server.step", 8.5, 33.5, (("iter", 1),)),
    Span("server.decode", 9, 10, (("live", 2), ("kv_live", 30),
                                  ("kv_scanned", 100))),
    Span("server.sample", 10, 33),
    Span("server.step", 36.5, 73.5, (("iter", 2),)),
    Span("server.admit", 37, 47, (("uid", 5), ("prompt_len", 64))),
    Span("server.prefill", 37, 39), Span("server.splice", 39, 40),
    Span("server.first_token", 40, 46.5),
    Span("server.decode", 48, 50, (("live", 3), ("kv_live", 45),
                                   ("kv_scanned", 100))),
    Span("server.sample", 50, 73),
]


def trace() -> TraceData:
    ops = decode_run(10) + [Event("%fusion.1", 40, 44)] + decode_run(50)
    return TraceData(device_ops={DEV: ops}, spans=list(BENCH))


def server_trace(spans=SERVER) -> ServerTrace:
    names = {(7, k): v for k, v in DECODE_OPS.items()}
    names[(9, "%fusion.1")] = "jit(server_prefill)/stack/while/body/attn/" \
        "dot_general:"
    mods = [Event("jit_server_decode(7)", 10, 30),
            Event("jit_server_prefill(9)", 40, 45),
            Event("jit_server_decode(7)", 50, 70)]
    return ServerTrace(spans=list(spans), modules={DEV: mods}, op_names=names)


def test_self_time_goes_to_the_innermost_operation():
    ops = decode_run(0) + [Event("x", 30, 32)]
    assert phases.self_times(ops) == pytest.approx([8, 4, 3, 2, 1, 1.5, 2])
    # they add up to the union of the operations
    assert sum(phases.self_times(ops)) == pytest.approx(
        reduce.Busy(ops).within(0, 40))


def test_self_time_of_operations_starting_together():
    ops = [Event("outer", 0, 10), Event("inner", 0, 4), Event("nested", 1, 2)]
    assert phases.self_times(ops) == pytest.approx([6, 3, 1])


def test_program_time_and_scope_self_time():
    ph = phases.reduce_phases(trace(), server_trace(), 0.0)
    dec = ph.programs["server_decode"]
    assert (dec.runs, dec.device_s) == (2, pytest.approx(40))
    assert dict(dec.scopes) == pytest.approx(
        {"stack": 22, "attn": 8, "mlp": 6, "head": 3})
    assert dec.unattributed_s == pytest.approx(1)
    pre = ph.programs["server_prefill"]
    assert (pre.runs, pre.device_s, dict(pre.scopes)) == (
        1, pytest.approx(5), pytest.approx({"attn": 4}))
    assert phases.decode_program_ms(ph) == pytest.approx(20e3)
    assert phases.decode_attn_ms(ph) == pytest.approx(4e3)
    assert phases.decode_mlp_ms(ph) == pytest.approx(3e3)
    assert phases.decode_stack_ms(ph) == pytest.approx(11e3)
    assert phases.decode_unattributed_pct(ph) == pytest.approx(2.5)
    assert phases.device_scopes(ph)[:3] == [
        ("stack/while", pytest.approx(16)),
        ("stack/while/body/closed_call/attn/dot_general", pytest.approx(8)),
        ("stack/while/body/closed_call/mlp/dot_general", pytest.approx(6))]
    assert dict(phases.device_scopes(ph))["(no op_name)"] == pytest.approx(2)


def test_operations_missing_from_the_metadata_are_unattributed():
    t = trace()
    t.device_ops[DEV].append(Event("%unknown", 21, 22))   # in the while
    ph = phases.reduce_phases(t, server_trace(), 0.0)
    dec = ph.programs["server_decode"]
    assert dec.unattributed_s == pytest.approx(1 + 1)
    assert dec.scopes["stack"] == pytest.approx(22 - 1)


def test_runs_count_where_they_start():
    t = trace()
    t.spans[0] = Event("bench.window", 0, 60)        # run 2 starts inside
    ph = phases.reduce_phases(t, server_trace(), 0.0)
    assert ph.programs["server_decode"].runs == 2
    t.spans[0] = Event("bench.window", 0, 45)        # run 2 starts after
    ph = phases.reduce_phases(t, server_trace(), 0.0)
    assert ph.programs["server_decode"].runs == 1


def test_idle_time_goes_to_the_innermost_span():
    ph = phases.reduce_phases(trace(), server_trace(), 0.0)
    assert ph.idle_by_span == pytest.approx({
        "server.sample": 7, "server.first_token": 2.5, "server.step": 3,
        "server.decode": 3, "server.prefill": 2, "server.splice": 1,
        "server.admit": 0.5, "bench.step": 2, "outside": 36})
    assert sum(ph.idle_by_span.values()) == pytest.approx(100 - 43)
    assert ph.steps == 2
    assert phases.idle_sample_ms_per_step(ph) == pytest.approx(9.5e3 / 2)
    assert phases.idle_loop_ms_per_step(ph) == pytest.approx(6e3 / 2)
    # a gap is named by the span that holds most of it; outside only
    # where no span covers any of it
    assert ph.idle_gaps == [("server.sample", 30.5), ("server.sample", 10.5),
                            ("server.decode", 10),
                            ("server.first_token", 6)]


def test_gap_outside_every_span():
    gaps = [(0.0, 5.0), (6.0, 9.0)]
    split = phases.innermost([Event("bench.step", 7, 8)], gaps)
    assert split == [{"outside": 5}, {"outside": 2, "bench.step": 1}]
    assert [phases.label(s) for s in split] == ["outside", "bench.step"]


def test_admission_device_time_and_cache_positions():
    ph = phases.reduce_phases(trace(), server_trace(), 0.0)
    assert ph.admit_busy_s == pytest.approx([4])
    assert phases.admit_program_ms_per_request(ph) == pytest.approx(4e3)
    assert (ph.kv_live, ph.kv_scanned) == (75, 200)
    assert phases.decode_kv_live_pct(ph) == pytest.approx(37.5)


def test_host_spans_shift_by_the_clock_offset():
    """Spans one unit late on the host clock, reduced with offset -1, read
    as the trace above."""
    late = [Span(s.name, s.start + 1, s.end + 1, s.args) for s in SERVER]
    t = trace()
    t.spans = [Event(s.name, s.start + 1, s.end + 1) for s in BENCH]
    a = phases.reduce_phases(trace(), server_trace(), 0.0)
    b = phases.reduce_phases(t, server_trace(late), -1.0)
    assert b.idle_by_span == pytest.approx(a.idle_by_span)
    assert b.admit_busy_s == pytest.approx(a.admit_busy_s)


def test_a_trace_without_server_spans_reads_nothing():
    """The program before named programs, scopes and spans: every metric
    function returns None, and nothing raises."""
    bare = ServerTrace(spans=[], modules={DEV: [
        Event("jit__lambda(3)", 10, 30)]}, op_names={})
    ph = phases.reduce_phases(trace(), bare, 0.0)
    assert {k: f(ph) for k, f in phases.METRICS.items()} == {
        k: None for k in phases.METRICS}
    assert phases.device_scopes(ph) == []


def test_program_and_scope_names():
    assert phases.program("jit_server_decode(14530554794882571194)") == (
        "server_decode", 14530554794882571194)
    assert phases.program("jit__lambda(12)") == ("_lambda", 12)
    assert phases.scope_path(
        "jit(server_decode)/stack/while/body/closed_call/attn/kv_write/"
        "scatter:") == "stack/while/body/closed_call/attn/kv_write/scatter"
    assert phases.layer_scope("stack/while/body/dynamic_slice") == "stack"
    assert phases.layer_scope("stack/while/body/ssm/dot_general") == "ssm"
    assert phases.scope_path("") == phases.NO_OP_NAME


def window(t: TraceData) -> layer.Window:
    s = family.find("decoder").Shape(
        family="decoder", layers=2, d_model=64, vocab=500, norm_eps=1e-6,
        heads=4, kv_heads=2, head_dim=16, d_ff=128, rope_theta=1e6,
        qk_norm=True)
    r = reduce.reduce(t)
    # one step per step span: the first admits a prompt, the rest decode
    steps = [Step(0, 1, (64,) if i == 0 else (), (30 + i, 20 + i))
             for i in range(len(r.step_busy_s))]
    return layer.Window(shape=s, peaks={"bf16_flops_per_s": 1e9,
                                        "hbm_bytes_per_s": 1e6},
                        window_s=r.window_s, busy_s=r.busy_s, steps=steps,
                        step_busy_s=r.step_busy_s, step_dur_s=r.step_dur_s)


FIVE = (layer.device_idle_pct, layer.server_host_ms_per_step,
        layer.decode_ms_per_step, layer.decode_roofline, layer.step_mfu_pct)


@pytest.mark.parametrize("source", ["hand-made", "recorded"])
def test_existing_readers_ignore_server_spans(source):
    """The five accepted per-layer metrics read the same with the program's
    ``server.*`` spans in the trace as without them."""
    if source == "hand-made":
        bare = trace()
        spans = SERVER
    else:
        bare = reduce.from_xplane(DATA / "serve.xplane.pb")
        spans = phases.read(DATA / "serve.xplane.pb").spans
    with_spans = TraceData(
        device_ops=bare.device_ops,
        spans=sorted(bare.spans + [Event(s.name, s.start, s.end)
                                   for s in spans], key=lambda e: e.start))
    before = [f(window(bare)) for f in FIVE]
    after = [f(window(with_spans)) for f in FIVE]
    assert before == after
    assert all(v is not None for v in before)


def test_recorded_serve_trace():
    """``data/serve.xplane.pb`` (``record_serve_trace.py``; one TPU v5 lite):
    4 traced steps of a 2-layer qwen3-1.7b ``Server``, 4 slots of 256, the
    second step admitting request 4 (32 prompt tokens)."""
    path = DATA / "serve.xplane.pb"
    trace = reduce.from_xplane(path)
    server = phases.read(path)
    ids = dict(phases.program(m.name)
               for m in server.modules["/device:TPU:0"])
    assert {"server_decode", "server_prefill"} <= set(ids)
    assert not any("lambda" in n for n in ids)
    # the op_name map: each program's instructions carry its own root
    for (pid, _), op in server.op_names.items():
        if op.startswith("jit(server_"):
            assert pid == ids[op[len("jit("):op.index(")")]]
    decode_ops = [op for op in server.op_names.values()
                  if op.startswith("jit(server_decode)/")]
    assert {phases.layer_scope(phases.scope_path(op)) for op in decode_ops} \
        == {"stack", "embed", "attn", "mlp", "head"}
    assert any("/attn/kv_write/" in op for op in decode_ops)

    red = reduce.reduce(trace)
    ph = phases.reduce_phases(trace, server, red.offset_s)
    dec, pre = ph.programs["server_decode"], ph.programs["server_prefill"]
    assert (dec.runs, pre.runs) == (4, 1)
    # scope self times add up to program time within 1%
    for t in (dec, pre):
        assert 0 <= t.unattributed_s < 0.01 * t.device_s
        assert sum(t.scopes.values()) == pytest.approx(t.attributed_s)
    assert phases.decode_program_ms(ph) == pytest.approx(1.361, abs=0.005)
    assert sum(phases.METRICS[m](ph) for m in (
        "decode_attn_ms", "decode_mlp_ms", "decode_stack_ms")) \
        < phases.decode_program_ms(ph)

    # span args read back
    steps = [s for s in server.spans if s.name == "server.step"]
    assert [s.arg("iter") for s in steps] == [3, 4, 5, 6]
    (admit,) = [s for s in server.spans if s.name == "server.admit"]
    assert (admit.arg("uid"), admit.arg("prompt_len")) == (4, 32)
    decodes = [s for s in server.spans if s.name == "server.decode"]
    assert [(s.arg("live"), s.arg("kv_live"), s.arg("kv_scanned"))
            for s in decodes] == [(3, 105, 1024), (4, 141, 1024),
                                  (4, 145, 1024), (4, 149, 1024)]
    assert ph.steps == 4 and len(ph.admit_busy_s) == 1
    assert phases.decode_kv_live_pct(ph) == pytest.approx(
        100 * (105 + 141 + 145 + 149) / 4096)
    # the admission's device work runs inside its span
    assert ph.admit_busy_s[0] >= pre.device_s
    assert sum(ph.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s)
    assert all(phases.METRICS[m](ph) is not None for m in phases.METRICS)
