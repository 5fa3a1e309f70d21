"""Shared neural layers, functional style: init(key,...) -> pytree,
apply(params, x, ...) -> y.  All matmuls route through the backend so the
paper's AME GEMM path is a first-class, swappable substrate."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.sharding.context import constrain


@dataclasses.dataclass(frozen=True)
class Backend:
    """Routes dense compute: 'xla' (einsum; used for dry-run lowering) or
    'pallas' (the AME output-stationary kernels, compiled for the TPU).

    ``interpret=True`` runs the Pallas kernels through the interpreter, for
    CPU tests and rehearsals; it is never chosen from the device."""

    mode: str = "xla"
    interpret: bool = False

    def matmul(self, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        """(..., K) @ (K, N) with f32 accumulation."""
        if self.mode == "pallas":
            lead = x.shape[:-1]
            x2 = x.reshape(-1, x.shape[-1])
            return ops.gemm(x2, w, use_pallas=True, interpret=self.interpret,
                            out_dtype=x.dtype).reshape(*lead, w.shape[-1])
        return jnp.matmul(x, w, preferred_element_type=jnp.float32
                          ).astype(x.dtype)


XLA = Backend("xla")
PALLAS = Backend("pallas")


# -- dense -------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, backend: Backend = XLA):
    y = backend.matmul(x, p["w"].astype(x.dtype))
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
    * allreduce + SP: constrain straight to the seq-sharded residual layout
      so SPMD emits a reduce-scatter (S link bytes) instead of all-reduce
      (2S) followed by a slice.
    * allreduce: replicate => the Megatron all-reduce.
    """
    from repro.configs.base import OUTPUT_SHARDED_TP_MODES
    if policy.tp_mode in OUTPUT_SHARDED_TP_MODES:
        return constrain(y, "batch", None, "model")
    if policy.sp and policy.sp_rs and y.ndim == 3 and y.shape[1] > 1:
        return constrain(y, "batch", "model", None)
    return constrain(y, "batch", None, None)


def mlp(p, x, act: str, backend: Backend = XLA, tp_mode: str = "allreduce",
        policy=None):
    """Gated/plain MLP.  Sharding posture depends on the TP dataflow —
    see :func:`out_constrain`."""
    from repro.configs.base import Policy
    policy = policy or Policy(tp_mode=tp_mode)
    h = dense(p["wi"], x, backend)
    if act == "swiglu":
        h = jax.nn.silu(dense(p["wg"], x, backend)) * h
    elif act == "geglu":
        h = jax.nn.gelu(dense(p["wg"], x, backend)) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", None, "model")
    y = dense(p["wo"], h, backend)
    return out_constrain(y, policy)


# -- embedding ----------------------------------------------------------------


def embed_init(key, vocab: int, d: int, dtype):
    return {"table": jax.random.normal(key, (vocab, d), dtype) * d ** -0.5}


def embed(p, tokens, compute_dtype):
    return p["table"].astype(compute_dtype)[tokens]


def unembed(p, x, backend: Backend = XLA):
    """Logits against the (possibly tied) embedding table."""
    return backend.matmul(x, p["table"].astype(x.dtype).T)
