"""The float32 references agree with ``Server`` prefill + decode on logits.

At the registry's ``reduced()`` sizes, in float32: every logit row the
server computes (the prefill's last position, then each decode step through
the slot cache or state) matches the reference's full forward at the same
position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import check, family, weights
from chip.shapes import Shape


def shape_of(cfg) -> Shape:
    if cfg.family == "ssm":
        s = cfg.ssm
        return family.find("mamba2").Shape(
            family="mamba2", layers=cfg.n_layers, d_model=cfg.d_model,
            vocab=cfg.vocab_size, norm_eps=cfg.norm_eps, d_state=s.d_state,
            d_conv=s.d_conv, expand=s.expand, ssm_head_dim=s.head_dim,
            n_groups=s.n_groups)
    return family.find("decoder").Shape(
        family="decoder", layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab_size, norm_eps=cfg.norm_eps, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_, d_ff=cfg.d_ff,
        rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm)


def serve_logits(cfg, params, prompts, max_new):
    """Serve ``prompts`` through ``Server``; returns each request's served
    tokens and the logit rows that chose them."""
    from repro.serve.loop import Request, Server
    srv = Server(cfg, params, slots=len(prompts), cache_len=64)
    rows = {i: [] for i in range(len(prompts))}
    prefill, decode = srv._prefill_one, srv._decode
    prefilled = []

    def prefill_rec(p, toks):
        logits, cache = prefill(p, toks)
        prefilled.append(np.asarray(logits[0]))
        return logits, cache

    def decode_rec(p, t, ps, c):
        logits, cache = decode(p, t, ps, c)
        for i, r in enumerate(srv.active):
            if r is not None:
                rows[r.uid].append(np.asarray(logits[i]))
        return logits, cache

    srv._prefill_one, srv._decode = prefill_rec, decode_rec
    orig_admit = srv._admit

    def admit():           # admission is FIFO: prefills come in queue order
        queued = list(srv.queue)
        prefilled.clear()
        orig_admit()
        for r, logits in zip([r for r in queued if r.out_tokens], prefilled):
            rows[r.uid].append(logits)
    srv._admit = admit
    reqs = [Request(uid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    return reqs, rows


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-370m"])
def test_server_matches_reference_logits(arch):
    from repro.configs.base import get
    cfg = get(arch).reduced()
    shape = shape_of(cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make(shape, 7))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, shape.vocab, n, dtype=np.int32)
               for n in (7, 12)]
    reqs, rows = serve_logits(cfg, params, prompts, max_new=6)
    fam = family.of(shape)
    table = fam.head(params, shape)
    for r in reqs:
        seq = np.concatenate([r.prompt, r.out_tokens])[None]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(fam.hidden(params, jnp.asarray(seq), shape)
                             @ table.T)
        p = len(r.prompt)
        got = np.stack(rows[r.uid])[:, :shape.vocab]
        want = ref[0, p - 1:p - 1 + len(got)]
        assert got.shape == want.shape == (len(r.out_tokens), shape.vocab)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        # the check's own reading of the same request: no gap in float32
        g = check.gaps(params, [check.Served(r.prompt,
                                             np.asarray(r.out_tokens))],
                       shape, 128)
        assert g.tokens == len(r.out_tokens)
        assert g.max_gap < 1e-3
