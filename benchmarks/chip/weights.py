"""Random weights from the seed, in the served type, made on the device.

The benchmark makes the weights itself, in the parameter layout the program
serves (``models/model.py``: layer-stacked leaves), so that the reference can
make the very same ones from the seed without importing the program.  The
harness checks the layout against the program's ``init`` before it serves.

Each leaf is drawn in float32 from ``fold_in(key, leaf index)`` and cast to
its served type in one jitted call.  Scales follow the program's own
initialisation; norm scales and the Mamba2 ``A``, ``dt`` and ``D`` are drawn
away from their trivial values so that the reference comparison exercises
them.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from chip.shapes import Shape

BF16, F32 = "bfloat16", "float32"

# (path, shape, served dtype, init kind, scale)
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, str, float]


def _decoder_leaves(s: Shape) -> List[Leaf]:
    L, d, hd = s.layers, s.d_model, s.head_dim
    q, kv = s.heads * hd, s.kv_heads * hd
    blk = ("stack", "dense_stack")
    leaves = [
        (blk + ("ln1", "scale"), (L, d), BF16, "norm", 0.0),
        (blk + ("ln2", "scale"), (L, d), BF16, "norm", 0.0),
        (blk + ("attn", "wq", "w"), (L, d, q), BF16, "normal", d ** -0.5),
        (blk + ("attn", "wk", "w"), (L, d, kv), BF16, "normal", d ** -0.5),
        (blk + ("attn", "wv", "w"), (L, d, kv), BF16, "normal", d ** -0.5),
        (blk + ("attn", "wo", "w"), (L, q, d), BF16, "normal", q ** -0.5),
        (blk + ("mlp", "wi", "w"), (L, d, s.d_ff), BF16, "normal", d ** -0.5),
        (blk + ("mlp", "wg", "w"), (L, d, s.d_ff), BF16, "normal", d ** -0.5),
        (blk + ("mlp", "wo", "w"), (L, s.d_ff, d), BF16, "normal",
         s.d_ff ** -0.5),
    ]
    if s.qk_norm:
        leaves += [(blk + ("attn", "qnorm", "scale"), (L, hd), BF16, "norm", 0.),
                   (blk + ("attn", "knorm", "scale"), (L, hd), BF16, "norm", 0.)]
    return leaves


def _mamba2_leaves(s: Shape) -> List[Leaf]:
    L, d, H = s.layers, s.d_model, s.ssm_heads
    blk = ("stack", "ssm_stack")
    m = blk + ("mamba",)
    return [
        (blk + ("ln", "scale"), (L, d), BF16, "norm", 0.0),
        (m + ("in_proj", "w"), (L, d, s.in_proj_dim), BF16, "normal",
         d ** -0.5),
        (m + ("conv_w",), (L, s.d_conv, s.conv_dim), BF16, "normal",
         s.d_conv ** -0.5),
        (m + ("conv_b",), (L, s.conv_dim), BF16, "normal", 0.1),
        (m + ("a_log",), (L, H), F32, "a_log", 0.0),
        (m + ("d_skip",), (L, H), F32, "norm", 0.0),
        (m + ("dt_bias",), (L, H), F32, "dt_bias", 0.0),
        (m + ("norm", "scale"), (L, s.d_inner), BF16, "norm", 0.0),
        (m + ("out_proj", "w"), (L, s.d_inner, d), BF16, "normal",
         s.d_inner ** -0.5),
    ]


def leaves(s: Shape) -> List[Leaf]:
    """Every parameter leaf of the served pytree, in a fixed order."""
    body = _decoder_leaves(s) if s.family == "decoder" else _mamba2_leaves(s)
    return body + [
        (("final_norm", "scale"), (s.d_model,), BF16, "norm", 0.0),
        (("embed", "table"), (s.vocab_rows, s.d_model), BF16, "normal",
         s.d_model ** -0.5),
    ]


def _draw(key, shape, kind: str, scale: float):
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * scale
    if kind == "norm":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind == "a_log":             # A = -exp(a_log), A ~ U[1, 16]
        return jnp.log(1.0 + 15.0 * u)
    if kind == "dt_bias":           # softplus(dt_bias) = dt, log dt ~ U
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {kind!r}")


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> Dict:
    out: Dict = {}
    for path, x in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x
    return out


def seed_key(seed: int):
    """A key for any non-negative seed, 64-bit ones included."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make(s: Shape, seed: int) -> Dict:
    """The served parameter pytree for ``seed``, made in one jitted call."""
    spec = leaves(s)

    def build(key):
        return _nest({
            path: _draw(jax.random.fold_in(key, i), shape, kind, scale
                        ).astype(dtype)
            for i, (path, shape, dtype, kind, scale) in enumerate(spec)})

    return jax.jit(build)(seed_key(seed))


def nbytes(s: Shape) -> int:
    """Bytes of the served weights."""
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
               for _, shape, dtype, _, _ in leaves(s))
