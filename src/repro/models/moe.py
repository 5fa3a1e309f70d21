"""Mixture-of-Experts with grouped one-hot dispatch (Switch/T5X style).

Tokens are split into small groups (dim G, sharded over the DP axes); each
group dispatches into a per-group, per-expert capacity buffer via one-hot
einsums.  This formulation shards cleanly under SPMD:

   combine  (G, S, E, C)    G on 'data'   (E on 'model' for EP)
   buffers  (E, G, C, d)    the G<->E transpose IS the EP all-to-all

unlike scatter-based dispatch, whose arbitrary flat indices force the
partitioner to replicate the buffer.  Dispatch-einsum overhead is
2*s*E*C_g*d FLOPs ~ a few % of expert compute for C_g ~ 1.25*S*k/E.

Sharding modes (cfg.moe.sharding):
  * "ep": experts shard 'model' (E % 16 == 0; deepseek).
  * "tp": expert-internal tensor parallelism (mixtral: 8 experts on a
    16-way axis); buffers stay token-sharded, expert d_ff shards 'model'.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import dense_init, mlp, mlp_init, out_constrain
from repro.sharding.context import constrain

GROUP_SIZE = 256


def moe_init(key, cfg: ArchConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    scale = d ** -0.5

    def expert_bank(k):
        k1, k2, k3 = jax.random.split(k, 3)
        e, f = m.num_experts, m.d_ff_expert
        return {
            "wi": jax.random.normal(k1, (e, d, f), dtype) * scale,
            "wg": jax.random.normal(k2, (e, d, f), dtype) * scale,
            "wo": jax.random.normal(k3, (e, f, d), dtype) * f ** -0.5,
        }

    p = {"router": dense_init(ks[0], d, m.num_experts, dtype),
         "experts": expert_bank(ks[1])}
    if m.n_shared:
        p["shared"] = mlp_init(ks[2], d, m.d_ff_expert * m.n_shared,
                               cfg.act, dtype)
    return p


def _group(s: int, target: int = GROUP_SIZE) -> int:
    g = max(1, s // target)
    while s % g:
        g -= 1
    return g


def moe_apply(p, x, cfg: ArchConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,T,d) -> (y, aux_loss)."""
    m = cfg.moe
    b, t, d = x.shape
    s = b * t
    e, k = m.num_experts, m.top_k
    g = _group(s)
    sg = s // g
    cap = max(int(m.capacity_factor * sg * k / e), 1)
    ep = m.sharding == "ep"

    xg = x.reshape(g, sg, d)
    xg = constrain(xg, "batch", None, None)
    logits = (xg @ p["router"]["w"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)                     # (G,S,E)
    gate_vals, idx = jax.lax.top_k(probs, k)               # (G,S,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)

    # load-balance auxiliary (Switch-style), computed pre-drop
    frac = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1, 2))
    aux = m.aux_loss_weight * e * jnp.sum(frac * probs.mean((0, 1)))

    # position-in-expert with earlier top-k choices taking priority.
    # NOTE (known, standard artifact): capacity dropping is *not causal* at
    # train time — whether a token keeps its slot depends on other tokens in
    # the group, including later positions (the k-th-choice offset counts
    # the whole group's earlier-choice acceptances, as in Switch/T5X).
    # Decode has no future tokens, so serving is unaffected; see
    # tests/test_model_properties.py::test_causality (MoE runs with ample
    # capacity to assert causality of the *network* itself).
    # combine/dispatch ride in the compute dtype (bf16): their cotangents
    # are what the EP backward all-reduces — f32 here doubles that term
    cdt = x.dtype
    combine = jnp.zeros((g, sg, e, cap), cdt)
    base = jnp.zeros((g, 1, e), jnp.float32)
    for i in range(k):
        oh = jax.nn.one_hot(idx[..., i], e, dtype=jnp.float32)  # (G,S,E)
        pos = jnp.cumsum(oh, axis=1) - oh + base
        ok = (pos < cap).astype(jnp.float32) * oh
        slot = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                              dtype=cdt)                       # (G,S,E,C)
        combine = combine + (gate_vals[..., i, None, None].astype(cdt)
                             * (ok[..., None].astype(cdt) * slot))
        base = base + ok.sum(1, keepdims=True)   # accepted so far per expert
    combine = constrain(combine, "batch", None,
                        "model" if ep else None, None)
    dispatch = (combine > 0).astype(x.dtype)

    # dispatch: (G,S,E,C) x (G,S,d) -> (E,G,C,d) — the EP all-to-all
    buf = jnp.einsum("gsec,gsd->egcd", dispatch, xg)
    buf = constrain(buf, "model" if ep else None, "batch", None, None)

    w = p["experts"]
    h = jnp.einsum("egcd,edf->egcf", buf, w["wi"].astype(x.dtype))
    hg = jnp.einsum("egcd,edf->egcf", buf, w["wg"].astype(x.dtype))
    h = jax.nn.silu(hg) * h
    h = constrain(h, "model" if ep else None, "batch", None,
                  None if ep else "model")
    out = jnp.einsum("egcf,efd->egcd", h, w["wo"].astype(x.dtype))
    out = constrain(out, "model" if ep else None, "batch", None, None)

    y = jnp.einsum("gsec,egcd->gsd", combine, out)
    y = y.reshape(b, t, d)
    if m.n_shared:
        y = y + mlp(p["shared"], x, cfg.act, cfg.policy)
    return out_constrain(y, cfg.policy), aux
