"""Plain float32 references, independent of the program under test.

``decoder`` (Qwen3-style: GQA, qk-norm, RoPE, SwiGLU, tied head) and
``mamba2`` (token-by-token selective-state recurrence, not the chunked scan)
read the parameter pytree that ``chip.weights`` makes, upcast each layer to
float32, and run under ``jax.default_matmul_precision("highest")`` (the
caller sets it).  Each returns the final normed hidden states; the LM head
is applied in blocks by ``chip.check``.

``quant=True`` is the benchmark's control: every matrix product takes its
inputs rounded to float8 e4m3 (per-tensor scale for weights, per-row for
activations), the precision below the served bfloat16.

``topk_near`` is for a family with routed layers: a router's top-k choice
and where it lies within a margin of changing (``chip.family``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8_MAX = 448.0                 # largest finite float8_e4m3fn


def fp8_round(x, axis=None):
    """``x`` rounded to float8 e4m3 under a max-abs scale, back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def matmul(x, w, quant: bool):
    """``x @ w`` in float32; with ``quant`` both inputs are float8-rounded."""
    if quant:
        x, w = fp8_round(x, axis=-1), fp8_round(w)
    return jnp.matmul(x, w)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def upcast(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def topk_near(keys, k: int, margin: float):
    """A top-``k`` choice over the last axis of ``keys`` and where it is
    within ``margin`` of changing: ``(chosen, near)``, booleans like
    ``keys``.  An entry is near when it is chosen less than ``margin``
    above the largest entry left out, or left out less than ``margin``
    below the smallest entry chosen.  ``-inf`` keys (masked) are never
    near.  For a grouped router, call it once on the group scores (the
    group boundary) and once on the keys of the groups kept (the top-k
    boundary)."""
    top, idx = jax.lax.top_k(keys, k + 1)
    chosen = jnp.any(jax.nn.one_hot(idx[..., :k], keys.shape[-1],
                                    dtype=bool), axis=-2)
    lo, hi = top[..., k - 1:k], top[..., k:k + 1]
    near = jnp.where(chosen, keys - hi < margin, lo - keys < margin)
    return chosen, near
