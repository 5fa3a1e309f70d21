"""Shared neural layers, functional style: init(key,...) -> pytree,
apply(params, x, ...) -> y.

Kernels are chosen in the layer that runs an op, from what it can observe
(platform, shapes, config, mesh), never by a caller-set option: see
``attention.decode_kv_block``, which sends the decode read to the Pallas
kernel ``kernels.decode_attention`` on a TPU where the input allows it.  A
Pallas kernel serves an op only where its layer selects it so, and enters
the model through a change that shows it winning in a benchmark cell.  The
paper's AME GEMM (``kernels.ame_gemm``) is a kernel of the repo, not a
switch on the model: every matmul here is XLA's, accumulating in float32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.sharding.context import constrain


# -- dense -------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    """(..., K) @ (K, N) with float32 accumulation, in the input's dtype."""
    y = jnp.matmul(x, p["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# -- norms -------------------------------------------------------------------


def norm_init(d: int, dtype, kind: str = "rmsnorm"):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:            # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# -- rotary ------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (..., T, H, D) rotated by position.  positions (..., T)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (..., T, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# -- mlp ---------------------------------------------------------------------


def mlp_init(key, d: int, d_ff: int, act: str, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {"wi": dense_init(k1, d, d_ff, dtype),
                "wg": dense_init(k2, d, d_ff, dtype),
                "wo": dense_init(k3, d_ff, d, dtype)}
    return {"wi": dense_init(k1, d, d_ff, dtype),
            "wo": dense_init(k2, d_ff, d, dtype)}


def out_constrain(y, policy):
    """Block-output sharding per TP dataflow:

    * allgather (the paper's reduction-free dataflow) and ame_pim (the
      PIM-cluster flavor sharing its mesh posture): stay feature-sharded
      on 'model' — no partial-sum reduction exists on the model axis.
    * allreduce: replicate => the Megatron all-reduce.
    """
    from repro.configs.base import OUTPUT_SHARDED_TP_MODES
    if policy.tp_mode in OUTPUT_SHARDED_TP_MODES:
        return constrain(y, "batch", None, "model")
    return constrain(y, "batch", None, None)


def mlp(p, x, act: str, policy):
    """Gated/plain MLP.  Sharding posture depends on the TP dataflow of
    ``policy`` — see :func:`out_constrain`."""
    h = dense(p["wi"], x)
    if act == "swiglu":
        h = jax.nn.silu(dense(p["wg"], x)) * h
    elif act == "geglu":
        h = jax.nn.gelu(dense(p["wg"], x)) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", None, "model")
    y = dense(p["wo"], h)
    return out_constrain(y, policy)


# -- embedding ----------------------------------------------------------------


def embed_init(key, vocab: int, d: int, dtype):
    return {"table": jax.random.normal(key, (vocab, d), dtype) * d ** -0.5}


def embed(p, tokens, compute_dtype):
    return p["table"].astype(compute_dtype)[tokens]

