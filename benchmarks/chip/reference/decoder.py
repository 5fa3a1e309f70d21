"""Qwen3-style decoder in plain float32: the published equations.

Per layer: x = RMSNorm(h); q, k, v projections; RMSNorm over head_dim on q
and k (qk-norm); RoPE with rotate-half pairing and frequencies
``theta ** (-2j / head_dim)``; causal softmax attention where query head
``i`` reads key/value head ``i // (heads / kv_heads)``; h += o_proj; then
h += down(silu(gate(x)) * up(x)) on x = RMSNorm(h).  A final RMSNorm ends
the stack; the LM head is the tied embedding table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chip.reference import F32, matmul, rmsnorm, upcast
from chip.shapes import Shape


def rope(x, positions, theta: float):
    """x (B, T, H, D) rotated by ``positions`` (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * inv               # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Shape, quant: bool, h, p):
    b, t, _ = h.shape
    hd, g = s.head_dim, s.heads // s.kv_heads
    a = p["attn"]
    x = rmsnorm(h, p["ln1"]["scale"], s.norm_eps)
    q = matmul(x, a["wq"]["w"], quant).reshape(b, t, s.heads, hd)
    k = matmul(x, a["wk"]["w"], quant).reshape(b, t, s.kv_heads, hd)
    v = matmul(x, a["wv"]["w"], quant).reshape(b, t, s.kv_heads, hd)
    if s.qk_norm:
        q = rmsnorm(q, a["qnorm"]["scale"], s.norm_eps)
        k = rmsnorm(k, a["knorm"]["scale"], s.norm_eps)
    pos = jnp.arange(t)
    q, k = rope(q, pos, s.rope_theta), rope(k, pos, s.rope_theta)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    h = h + matmul(o.reshape(b, t, s.heads * hd), a["wo"]["w"], quant)
    m = p["mlp"]
    x = rmsnorm(h, p["ln2"]["scale"], s.norm_eps)
    up = matmul(x, m["wi"]["w"], quant)
    gate = matmul(x, m["wg"]["w"], quant)
    return h + matmul(jax.nn.silu(gate) * up, m["wo"]["w"], quant)


def hidden(params, tokens, s: Shape, quant: bool = False):
    """Final normed hidden states (B, T, d) of ``tokens`` (B, T)."""
    h = params["embed"]["table"][tokens].astype(F32)

    def body(h, p):
        return _layer(s, quant, h, upcast(p)), None

    h, _ = jax.lax.scan(body, h, params["stack"]["dense_stack"])
    return rmsnorm(h, params["final_norm"]["scale"].astype(F32), s.norm_eps)
