"""Spans, counters and named programs of the serving loop.

``Server`` opens ``server.*`` spans (``repro.obs.span``) around the phases of
each iteration, feeds the same numbers to its metrics registry, and jits its
programs as ``server_decode`` and ``server_prefill``.  The model's layers
carry named scopes (``stack``, ``attn``, ``mlp``, ``moe``, ``ssm``,
``embed``, ``head``) into every operation's ``op_name``.  These tests record
a profiler trace on the CPU and read it back with ``ProfileData``.
"""
from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get
from repro.models import model as lm
from repro.obs import MetricsRegistry
from repro.serve.loop import Request, Server

SLOTS, CACHE_LEN = 2, 32
PROMPTS = (8, 12, 8)            # three requests: the third waits for a slot
MAX_NEW = 3

# span -> (parent, args)
CATALOG = {
    "server.step": (None, {"iter"}),
    "server.admit": ("server.step", {"uid", "prompt_len"}),
    "server.prefill": ("server.admit", set()),
    "server.splice": ("server.admit", set()),
    "server.first_token": ("server.admit", set()),
    "server.decode": ("server.step", {"live", "kv_live", "kv_scanned"}),
    "server.sample": ("server.step", set()),
}
LAYER_SCOPES = ("attn", "mlp", "moe", "ssm", "head")


@pytest.fixture(scope="module")
def qwen3():
    cfg = get("qwen3-1.7b").reduced()
    return cfg, lm.init(cfg, jax.random.key(0))


def requests():
    rng = np.random.default_rng(7)
    return [Request(uid=u, prompt=rng.integers(0, 500, n).astype(np.int32),
                    max_new=MAX_NEW) for u, n in enumerate(PROMPTS)]


def serve(cfg, params, metrics=None):
    srv = Server(cfg, params, slots=SLOTS, cache_len=CACHE_LEN,
                 metrics=metrics)
    for r in requests():
        srv.submit(r)
    srv.run_until_drained()
    return {r.uid: list(r.out_tokens) for r in srv.completed}


def server_spans(trace_dir: Path):
    """The ``server.*`` spans of the trace, in start order: (name, start,
    end, args)."""
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("server."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def traced(qwen3, tmp_path_factory):
    """Tokens served under a profiler trace, the spans, and the registry."""
    cfg, params = qwen3
    serve(cfg, params)                  # compile outside the trace
    d = tmp_path_factory.mktemp("trace")
    reg = MetricsRegistry()
    jax.profiler.start_trace(str(d))
    try:
        tokens = serve(cfg, params, metrics=reg)
    finally:
        jax.profiler.stop_trace()
    return tokens, server_spans(d), reg


def parent_of(spans, i):
    """Name of the innermost span that encloses span ``i``."""
    _, s, e, _ = spans[i]
    best = None
    for j, (name, s2, e2, _) in enumerate(spans):
        if j != i and s2 <= s and e <= e2 and (
                best is None or s2 >= spans[best][1]):
            best = j
    return None if best is None else spans[best][0]


def test_every_span_nests_with_its_args(traced):
    _, spans, _ = traced
    assert {s[0] for s in spans} == set(CATALOG)
    for i, (name, _, _, args) in enumerate(spans):
        parent, keys = CATALOG[name]
        assert parent_of(spans, i) == parent, name
        assert set(args) == keys, name
    steps = [s for s in spans if s[0] == "server.step"]
    assert [s[3]["iter"] for s in steps] == list(range(1, len(steps) + 1))
    admits = [s[3] for s in spans if s[0] == "server.admit"]
    assert [(a["uid"], a["prompt_len"]) for a in admits] == list(
        enumerate(PROMPTS))
    # within an admission: prefill, then splice, then the first token
    for i, s in enumerate(spans):
        if s[0] == "server.admit":
            kids = [k[0] for k in spans[i + 1:i + 4]]
            assert kids == ["server.prefill", "server.splice",
                            "server.first_token"]


def test_decode_args_count_cache_positions(traced):
    """kv_live is pos + 1 summed over live slots; kv_scanned every position
    of every slot's cache."""
    _, spans, _ = traced
    decodes = [s[3] for s in spans if s[0] == "server.decode"]
    assert all(d["kv_scanned"] == SLOTS * CACHE_LEN for d in decodes)
    # first step: both slots hold a prompt (8 and 12), each decodes at
    # position len(prompt), attending over len(prompt) + 1 positions
    assert decodes[0] == {"live": 2, "kv_live": 9 + 13,
                          "kv_scanned": SLOTS * CACHE_LEN}
    assert decodes[1]["kv_live"] == 10 + 14
    assert all(0 < d["kv_live"] <= d["kv_scanned"] for d in decodes)


def test_tokens_are_the_same_with_a_trace_running(qwen3, traced):
    cfg, params = qwen3
    tokens, _, _ = traced
    assert serve(cfg, params) == tokens
    assert sorted(tokens) == [0, 1, 2]
    assert all(len(t) == MAX_NEW for t in tokens.values())


def test_counters_equal_the_span_args(traced):
    _, spans, reg = traced
    snap = reg.snapshot()
    counters = {k: v["value"] for k, v in snap.items()
                if v.get("type") == "counter"}
    admits = [s[3] for s in spans if s[0] == "server.admit"]
    decodes = [s[3] for s in spans if s[0] == "server.decode"]
    assert counters["serve.admissions"] == len(admits) == len(PROMPTS)
    assert counters["serve.prefill_tokens"] == sum(
        a["prompt_len"] for a in admits) == sum(PROMPTS)
    assert counters["serve.decode_steps"] == len(decodes)
    assert counters["serve.kv_live_positions"] == sum(
        d["kv_live"] for d in decodes)
    assert counters["serve.kv_scanned_positions"] == sum(
        d["kv_scanned"] for d in decodes)
    assert "serve.live_slots" not in snap


def op_names(hlo_text: str):
    return re.findall(r'op_name="([^"]+)"', hlo_text)


def layer_scope(op_name: str):
    parts = op_name.split("/")
    return next((p for p in parts if p in LAYER_SCOPES), None)


def compiled_decode(cfg, params):
    srv = Server(cfg, params, slots=SLOTS, cache_len=CACHE_LEN)
    toks = jnp.zeros((SLOTS, 1), jnp.int32)
    pos = jnp.zeros((SLOTS,), jnp.int32)
    return srv, srv._decode.lower(params, toks, pos, srv.caches).compile()


def test_programs_are_named(qwen3):
    cfg, params = qwen3
    srv, decode = compiled_decode(cfg, params)
    assert decode.as_text().startswith("HloModule jit_server_decode")
    prefill = srv._prefill_one.lower(
        params, jnp.zeros((1, 8), jnp.int32)).compile()
    assert prefill.as_text().startswith("HloModule jit_server_prefill")
    names = [n for n in op_names(prefill.as_text()) if n.startswith("jit(")]
    assert names and all(n.startswith("jit(server_prefill)/") for n in names)
    assert {layer_scope(n) for n in names} >= {"attn", "mlp", "head"}
    assert any("/embed/" in n for n in names)


@pytest.mark.parametrize("arch,scopes", [
    ("qwen3-1.7b", {"attn", "mlp", "head"}),
    ("mixtral-8x22b", {"attn", "moe", "head"}),
    ("deepseek-v3-671b", {"attn", "mlp", "moe", "head"}),
    ("mamba2-370m", {"ssm", "head"}),
    ("zamba2-2.7b", {"ssm", "attn", "mlp", "head"}),
])
def test_every_decode_matmul_names_its_layer(arch, scopes):
    """Every dot_general of the optimized decode program carries the scope
    of the layer it belongs to; the scan over layers is ``stack``."""
    cfg = get(arch).reduced()
    params = lm.init(cfg, jax.random.key(0))
    _, decode = compiled_decode(cfg, params)
    names = op_names(decode.as_text())
    dots = [n for n in names if n.endswith("/dot_general")]
    assert dots and all(layer_scope(n) is not None for n in dots), [
        n for n in dots if layer_scope(n) is None]
    assert {layer_scope(n) for n in dots} == scopes
    assert any(n.startswith("jit(server_decode)/stack/") for n in names)
    assert any("/embed/" in n for n in names)


def test_decode_cache_write_is_scoped(qwen3):
    cfg, params = qwen3
    _, decode = compiled_decode(cfg, params)
    writes = [n for n in op_names(decode.as_text()) if "/kv_write/" in n]
    assert writes and all("/attn/kv_write/" in n for n in writes)
    assert any(n.endswith("/scatter") for n in writes)
