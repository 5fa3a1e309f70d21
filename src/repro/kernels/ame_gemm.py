"""ame_gemm — output-stationary outer-product GEMM (MAC-PEP on TPU).

TPU adaptation of the paper's reduction-free dataflow (DESIGN.md §3):

* The MAC-PEP keeps the accumulator column resident next to the MAC units
  (odd banks) for the whole K walk.  Here the accumulator tile is pinned in
  **VMEM scratch** for the whole K walk: grid = (M/bm, N/bn, K/bk) with K
  as the *minor* (sequential) dimension, so each (i, j) output tile sees
  its K-blocks back-to-back and partial sums never spill to HBM — unlike
  split-K GEMM, which writes partials and reduces (the host-side reduction
  the paper eliminates).
* The PIM unit's 16-lane FP16 rank-1 update becomes a (bm x bk)·(bk x bn)
  MXU rank-bk update; ROWNUM=128 survives as the default bm (MXU-native).
* Accumulation is f32 (MXU accumulator width), cast on the final K step —
  the single-rounding FMA semantics of the MAC datapath, block-wise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# MXU-native defaults; ROWNUM=128 from the paper's tile mapping
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref):
    """One grid step: rank-bk outer-product update into the resident acc."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def ame_gemm(a: jnp.ndarray, b: jnp.ndarray, *,
             block_m: int = DEFAULT_BM, block_n: int = DEFAULT_BN,
             block_k: int = DEFAULT_BK, out_dtype=None,
             interpret: bool = False) -> jnp.ndarray:
    """C = A(m,k) @ B(k,n), accumulation resident in VMEM (reduction-free).

    Shapes are padded up to block multiples (zero padding is exact for
    matmul).  ``interpret=True`` runs the kernel body on CPU for validation.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    gm, gn, gk = a.shape[0] // bm, b.shape[1] // bn, a.shape[1] // bk

    out = pl.pallas_call(
        _gemm_kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[1]), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]


def vmem_bytes(block_m: int = DEFAULT_BM, block_n: int = DEFAULT_BN,
               block_k: int = DEFAULT_BK, dtype_bytes: int = 2) -> int:
    """Working-set claim: A-block + B-block (double-buffered) + f32 acc."""
    stream = 2 * (block_m * block_k + block_k * block_n) * dtype_bytes
    return stream + block_m * block_n * 4
