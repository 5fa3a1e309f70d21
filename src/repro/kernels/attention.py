"""Flash-style chunked attention Pallas kernel (prefill hot-spot).

Online-softmax attention with the running (m, l, acc) statistics resident in
VMEM scratch across the KV walk — the same output-stationary posture as
ame_gemm: the output tile's accumulator never leaves VMEM while the
contraction (KV) dimension streams through.  Supports causal masking and
sliding windows (Mixtral SWA); queries are end-aligned against the KV
sequence so the same kernel serves prefill and chunked decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG = -1e30
_STAT_LANES = 128  # m/l scratch kept 2D and lane-aligned for the VPU


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int, tq: int, tk: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0].astype(jnp.float32)              # (bk, d)
    s = (q @ k.T) * scale                         # (bq, bk)

    bq, bk = s.shape
    qpos = (qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            + (tk - tq))                          # end-aligned query positions
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < tk                              # KV padding
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)

    m_old = m_ref[:, 0]                           # (bq,)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_old - m_new)
    l_ref[...] = (l_ref[...] * corr[:, None]
                  + jnp.broadcast_to(jnp.sum(p, -1)[:, None], l_ref.shape))
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + p @ v_ref[0].astype(jnp.float32))
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    interpret: bool = False) -> jnp.ndarray:
    """q (BH, Tq, D), k/v (BH, Tk, D) -> (BH, Tq, D); Tq end-aligned to Tk."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = d ** -0.5
    bq, bk = min(block_q, tq), min(block_k, tk)
    pq, pk = (-tq) % bq, (-tk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, tq=tq, tk=tk),
        grid=(bh, (tq + pq) // bq, (tk + pk) // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda i, qi, ki: (i, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda i, qi, ki: (i, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq + pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),             # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :tq]
