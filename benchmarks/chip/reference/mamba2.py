"""Mamba2 stack in plain float32, one token at a time through the state.

Per layer (arXiv:2405.21060, mamba_ssm ``Mamba2``): x = RMSNorm(h);
in_proj -> z, x, B, C, dt; causal depthwise conv of width d_conv over
(x, B, C) with bias, then SiLU; dt = softplus(dt + dt_bias); A = -exp(A_log);
for each position t and head i (group ``i // (heads / groups)``):
    S_t = exp(dt_t A) S_{t-1} + dt_t * outer(B_t, x_t)
    y_t = C_t . S_t + D x_t
then y = RMSNorm(y * silu(z)) (gated, norm over d_inner) and h += out_proj(y).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chip.reference import F32, matmul, rmsnorm, upcast
from chip.shapes import Shape


def _layer(s: Shape, quant: bool, h, p):
    b, t, _ = h.shape
    di, n, hp, heads = s.d_inner, s.d_state, s.ssm_head_dim, s.ssm_heads
    gn = s.n_groups * n
    m = p["mamba"]
    x = rmsnorm(h, p["ln"]["scale"], s.norm_eps)
    proj = matmul(x, m["in_proj"]["w"], quant)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * gn], \
        proj[..., 2 * di + 2 * gn:]
    padded = jnp.pad(xbc, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * m["conv_w"][i] for i in range(s.d_conv))
    conv = jax.nn.silu(conv + m["conv_b"])
    xs = conv[..., :di].reshape(b, t, heads, hp)
    rep = heads // s.n_groups
    bs = jnp.repeat(conv[..., di:di + gn].reshape(b, t, s.n_groups, n), rep, 2)
    cs = jnp.repeat(conv[..., di + gn:].reshape(b, t, s.n_groups, n), rep, 2)
    dt = jax.nn.softplus(dt + m["dt_bias"])                   # (B, T, H)
    decay = jnp.exp(dt * -jnp.exp(m["a_log"]))                # (B, T, H)

    def step(state, inp):
        a, dx, bt, ct = inp          # (B,H) (B,H,P) (B,H,N) (B,H,N)
        state = a[..., None, None] * state + bt[..., :, None] * dx[..., None, :]
        return state, jnp.einsum("bhn,bhnp->bhp", ct, state)

    seq = (decay.swapaxes(0, 1), (xs * dt[..., None]).swapaxes(0, 1),
           bs.swapaxes(0, 1), cs.swapaxes(0, 1))
    state0 = jnp.zeros((b, heads, n, hp), F32)
    _, y = jax.lax.scan(step, state0, seq)
    y = y.swapaxes(0, 1) + m["d_skip"][:, None] * xs          # (B, T, H, P)
    y = rmsnorm(y.reshape(b, t, di) * jax.nn.silu(z), m["norm"]["scale"],
                s.norm_eps)
    return h + matmul(y, m["out_proj"]["w"], quant)


def hidden(params, tokens, s: Shape, quant: bool = False):
    """Final normed hidden states (B, T, d) of ``tokens`` (B, T)."""
    h = params["embed"]["table"][tokens].astype(F32)

    def body(h, p):
        return _layer(s, quant, h, upcast(p)), None

    h, _ = jax.lax.scan(body, h, params["stack"]["ssm_stack"])
    return rmsnorm(h, params["final_norm"]["scale"].astype(F32), s.norm_eps)
