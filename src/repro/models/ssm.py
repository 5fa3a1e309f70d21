"""Mamba2 (SSD) block — the state-space arch whose recurrent state update is
the paper's outer-product accumulation (rank-1 updates into a resident
accumulator; DESIGN.md §5).

Train/prefill run the chunked SSD scan (kernels/ssd_scan or its jnp twin);
decode advances the recurrence one step with O(1) state:
  conv_state (B, d_conv-1, conv_dim), ssm_state (B, H, N, P).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.layers import Backend, XLA, apply_norm, dense, dense_init, norm_init, out_constrain
from repro.sharding.context import constrain


def _dims(cfg: ArchConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, nheads, conv_dim


def mamba_init(key, cfg: ArchConfig, dtype):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, nheads, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 4)
    d_proj = 2 * d_inner + 2 * s.n_groups * s.d_state + nheads  # z,x,B,C,dt
    return {
        "in_proj": dense_init(ks[0], d, d_proj, dtype),
        "conv_w": jax.random.normal(ks[1], (s.d_conv, conv_dim), dtype)
        * s.d_conv ** -0.5,
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.zeros((nheads,), jnp.float32),          # A = -exp(a_log)
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "norm": norm_init(d_inner, dtype),
        "out_proj": dense_init(ks[2], d_inner, d, dtype),
    }


def _split(cfg: ArchConfig, proj):
    s = cfg.ssm
    d_inner, nheads, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    z, xc, bc, cc, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + gn, 2 * d_inner + 2 * gn], -1)
    return z, xc, bc, cc, dt


def mamba_make_state(cfg: ArchConfig, batch: int, dtype,
                     layers: Optional[int] = None) -> Dict:
    s = cfg.ssm
    d_inner, nheads, conv_dim = _dims(cfg)
    cs = (batch, s.d_conv - 1, conv_dim)
    ss = (batch, nheads, s.d_state, s.head_dim)
    if layers is not None:
        cs, ss = (layers,) + cs, (layers,) + ss
    return {"conv": jnp.zeros(cs, dtype), "ssm": jnp.zeros(ss, jnp.float32)}


def mamba_apply(p, u, cfg: ArchConfig, *, state: Optional[Dict] = None,
                backend: Backend = XLA) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """u (B,T,d).  state=None: full-sequence scan (train/prefill).
    state given with T==1: single recurrent decode step."""
    s = cfg.ssm
    b, t, d = u.shape
    d_inner, nheads, conv_dim = _dims(cfg)
    g, n, hp = s.n_groups, s.d_state, s.head_dim
    proj = dense(p["in_proj"], u, backend)
    z, xc, bc, cc, dt = _split(cfg, proj)
    xbc = jnp.concatenate([xc, bc, cc], -1)                  # conv'd together

    new_state = None
    if state is None:
        pad = jnp.zeros((b, s.d_conv - 1, conv_dim), xbc.dtype)
        seq = jnp.concatenate([pad, xbc], 1)
    else:
        seq = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], 1)
        new_conv = seq[:, -(s.d_conv - 1):]
    # causal depthwise conv, width d_conv
    conv = sum(seq[:, i:i + t] * p["conv_w"][i].astype(xbc.dtype)
               for i in range(s.d_conv))
    conv = jax.nn.silu(conv + p["conv_b"].astype(xbc.dtype))
    xs, bs, cs_ = jnp.split(conv, [d_inner, d_inner + g * n], -1)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"])                     # (B,T,H)
    log_a = -jnp.exp(p["a_log"])[None, None, :] * dt         # (B,T,H) <= 0
    xh = xs.reshape(b, t, nheads, hp)
    xh = constrain(xh, "batch", None, "model", None)
    bg = bs.reshape(b, t, g, n)
    cg = cs_.reshape(b, t, g, n)
    rep = nheads // g

    if state is None or t > 1:
        # chunked SSD over the whole sequence (heads batched)
        xdt = (xh * dt[..., None])
        bh_rep = jnp.repeat(bg, rep, 2)
        # 4-D (B,H,T,*) keeps heads a shardable 'model' axis — flattening
        # (B*H) would force replication or per-layer resharding
        x4 = constrain(xdt.transpose(0, 2, 1, 3), "batch", "model", None, None)
        la4 = constrain(log_a.transpose(0, 2, 1), "batch", "model", None)
        b4 = constrain(bh_rep.transpose(0, 2, 1, 3), "batch", "model", None,
                       None)
        c4 = constrain(jnp.repeat(cg, rep, 2).transpose(0, 2, 1, 3),
                       "batch", "model", None, None)
        y = ops.ssd4(x4, la4.astype(jnp.float32), b4, c4,
                     use_pallas=(backend.mode == "pallas"),
                     interpret=backend.interpret, chunk=s.chunk)
        y = constrain(y, "batch", "model", None, None)
        y = y.transpose(0, 2, 1, 3)                            # (B,T,H,P)
        if state is not None:
            # prefill: closed-form final state (log_a <= 0 so the cumulative
            # weights exp(cum_T - cum_t) never overflow):
            #   S = a_total * S_in + sum_t exp(cum_T - cum_t) b_t (x*dt)_t
            cum = jnp.cumsum(log_a.astype(jnp.float32), axis=1)  # (B,T,H)
            wts = jnp.exp(cum[:, -1:] - cum)                     # (B,T,H)
            s_new = jnp.einsum("bthn,bthp->bhnp",
                               bh_rep.astype(jnp.float32) * wts[..., None],
                               xdt.astype(jnp.float32))
            s_new = s_new + jnp.exp(cum[:, -1])[..., None, None] * state["ssm"]
            new_state = {"conv": new_conv.astype(state["conv"].dtype),
                         "ssm": s_new}
    else:
        # one-step recurrence: S = a*S + dt*x (outer) B ; y = C @ S
        ssm_prev = state["ssm"]                              # (B,H,N,P) f32
        a1 = jnp.exp(log_a[:, 0])                            # (B,H)
        bx = jnp.einsum(
            "bhn,bhp->bhnp", jnp.repeat(bg[:, 0], rep, 1).astype(jnp.float32),
            (xh[:, 0] * dt[:, 0, :, None]).astype(jnp.float32))
        ssm_new = a1[..., None, None] * ssm_prev + bx
        ch = jnp.repeat(cg[:, 0], rep, 1).astype(jnp.float32)  # (B,H,N)
        y = jnp.einsum("bhn,bhnp->bhp", ch, ssm_new)[:, None]  # (B,1,H,P)
        new_state = {"conv": new_conv.astype(state["conv"].dtype),
                     "ssm": ssm_new}

    y = y.astype(u.dtype) + (p["d_skip"].astype(u.dtype)[None, None, :, None]
                             * xh)
    y = y.reshape(b, t, d_inner)
    y = apply_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = dense(p["out_proj"], y, backend)
    return out_constrain(out, cfg.policy), new_state
