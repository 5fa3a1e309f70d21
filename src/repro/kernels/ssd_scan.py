"""ssd_scan — Mamba2 SSD chunked scan as an output-stationary Pallas kernel.

The SSD recurrence  S_t = a_t * S_{t-1} + b_t ⊗ x_t,  y_t = c_t @ S_t  is the
paper's reduction-free dataflow verbatim: a rank-1 (outer-product) update
into an accumulator that never leaves local memory.  The TPU mapping keeps
the (N x P) state resident in **VMEM scratch** across the whole time walk —
grid = (BH, T/L) with the chunk dimension minor — while each chunk is
processed with MXU matmuls (the state-space-duality block form):

  y_chunk = (C * exp(cum)) @ S_in  +  tril((C @ B^T) * decay) @ X
  S_out   = exp(sum) * S_in        +  (B * exp(sum - cum))^T @ X

so inter-chunk work is the resident-accumulator path and intra-chunk work
is a small attention-like matmul block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128
NEG = -1e30


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, y_ref, state_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    la = la_ref[0].astype(jnp.float32)        # (1, L) log decay (<= 0)
    b = b_ref[0].astype(jnp.float32)          # (L, N)
    c = c_ref[0].astype(jnp.float32)          # (L, N)
    l = x.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    causal = si <= ti

    # inclusive log-decay prefix, as a column and as a row.  Mosaic has no
    # cumsum and no 1-D vectors, so both come from masked 2-D reductions
    cum_col = jnp.sum(jnp.where(causal, jnp.broadcast_to(la, (l, l)), 0.0),
                      axis=1, keepdims=True)                    # (L, 1)
    cum_row = jnp.sum(jnp.where(si == ti, jnp.broadcast_to(cum_col, (l, l)),
                                0.0), axis=0, keepdims=True)    # (1, L)
    total = jnp.sum(la, axis=1, keepdims=True)                  # (1, 1)
    s_in = state_ref[...]                     # (N, P) resident state

    # inter-chunk: queries against the carried state
    y = jnp.dot(c * jnp.exp(cum_col), s_in)

    # intra-chunk: causal decay-masked score block (state-space duality)
    diff = jnp.where(causal, cum_col - cum_row, NEG)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))    # C @ B^T
    y += jnp.dot(cb * jnp.exp(diff), x)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: decayed carry + outer-product accumulation of the chunk
    wb = b * jnp.exp(total - cum_col)                           # (L, N)
    state_ref[...] = (jnp.exp(total) * s_in
                      + jax.lax.dot_general(wb, x, (((0,), (0,)), ((), ()))))


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunked_jnp(x: jnp.ndarray, log_a: jnp.ndarray, b: jnp.ndarray,
                    c: jnp.ndarray, *, chunk: int = DEFAULT_CHUNK):
    """Pure-jnp twin of the Pallas kernel: chunked SSD with the state
    carried once per chunk (not per step) — this is the XLA-lowered path
    the dry-run sees; HBM traffic scales with T/chunk, not T."""
    bh, t, p = x.shape
    n = b.shape[-1]
    lc = min(chunk, t)
    pad = (-t) % lc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // lc
    xs = x.reshape(bh, nc, lc, p).transpose(1, 0, 2, 3).astype(jnp.float32)
    las = log_a.reshape(bh, nc, lc).transpose(1, 0, 2).astype(jnp.float32)
    bs = b.reshape(bh, nc, lc, n).transpose(1, 0, 2, 3).astype(jnp.float32)
    cs = c.reshape(bh, nc, lc, n).transpose(1, 0, 2, 3).astype(jnp.float32)
    ti = jnp.arange(lc)[:, None]
    si = jnp.arange(lc)[None, :]
    causal = si <= ti

    def step(s, inp):
        xc, lac, bc, cc = inp                     # (BH, L, ...)
        cum = jnp.cumsum(lac, -1)                 # (BH, L)
        y = jnp.einsum("zln,znp->zlp", cc * jnp.exp(cum)[..., None], s)
        diff = jnp.where(causal[None], cum[:, :, None] - cum[:, None, :], NEG)
        g = jnp.einsum("zln,zmn->zlm", cc, bc) * jnp.exp(diff)
        y = y + jnp.einsum("zlm,zmp->zlp", g, xc)
        w = jnp.exp(cum[:, -1:] - cum)            # (BH, L)
        s = (jnp.exp(cum[:, -1])[:, None, None] * s
             + jnp.einsum("zln,zlp->znp", bc * w[..., None], xc))
        return s, y

    s0 = jnp.zeros((bh, n, p), jnp.float32)
    _, ys = jax.lax.scan(step, s0, (xs, las, bs, cs))
    y = ys.transpose(1, 0, 2, 3).reshape(bh, t + pad, p)[:, :t]
    return y.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunked_jnp4(x: jnp.ndarray, log_a: jnp.ndarray, b: jnp.ndarray,
                     c: jnp.ndarray, *, chunk: int = DEFAULT_CHUNK):
    """4-D chunked SSD: x (B,H,T,P), log_a (B,H,T), b/c (B,H,T,N).

    Keeping batch and heads as separate leading dims lets SPMD shard them
    on ('data', 'model') natively — the (B*H)-flattened form forces either
    replication or per-layer resharding all-to-alls."""
    bsz, h, t, p = x.shape
    n = b.shape[-1]
    lc = min(chunk, t)
    pad = (-t) % lc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // lc
    f32 = jnp.float32
    # stacked chunk arrays stay in the input dtype (bf16): the f32 cast is
    # per-chunk inside the scan (VMEM-local on TPU), halving HBM traffic
    xs = x.reshape(bsz, h, nc, lc, p).transpose(2, 0, 1, 3, 4)
    las = log_a.reshape(bsz, h, nc, lc).transpose(2, 0, 1, 3).astype(f32)
    bs = b.reshape(bsz, h, nc, lc, n).transpose(2, 0, 1, 3, 4)
    cs = c.reshape(bsz, h, nc, lc, n).transpose(2, 0, 1, 3, 4)
    causal = jnp.arange(lc)[:, None] >= jnp.arange(lc)[None, :]

    def step(s, inp):
        xc, lac, bc, cc = inp                     # (B,H,L,...)
        xc, bc, cc = (xc.astype(f32), bc.astype(f32), cc.astype(f32))
        cum = jnp.cumsum(lac, -1)                 # (B,H,L)
        y = jnp.einsum("bhln,bhnp->bhlp", cc * jnp.exp(cum)[..., None], s)
        diff = jnp.where(causal[None, None],
                         cum[..., :, None] - cum[..., None, :], NEG)
        g = jnp.einsum("bhln,bhmn->bhlm", cc, bc) * jnp.exp(diff)
        y = y + jnp.einsum("bhlm,bhmp->bhlp", g, xc)
        w = jnp.exp(cum[..., -1:] - cum)          # (B,H,L)
        s = (jnp.exp(cum[..., -1])[..., None, None] * s
             + jnp.einsum("bhln,bhlp->bhnp", bc * w[..., None], xc))
        return s, y

    s0 = jnp.zeros((bsz, h, n, p), f32)
    _, ys = jax.lax.scan(step, s0, (xs, las, bs, cs))
    y = ys.transpose(1, 2, 0, 3, 4).reshape(bsz, h, t + pad, p)[:, :, :t]
    return y.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, log_a: jnp.ndarray, b: jnp.ndarray,
             c: jnp.ndarray, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False) -> jnp.ndarray:
    """Batched SSD scan.  x (BH, T, P), log_a (BH, T), b/c (BH, T, N).

    T is padded to a chunk multiple with log_a = 0 / b = 0 (exactly neutral:
    state carries through, outputs for the pad are dropped).
    """
    bh, t, p = x.shape
    n = b.shape[-1]
    lc = min(chunk, t)
    pad = (-t) % lc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    tt = t + pad
    # log_a rides as (BH, 1, T): a (1, 1, L) block keeps its last two dims
    # tiling-legal (the size-1 dim spans the whole axis), where a (1, L)
    # block of the 2-D array would not
    log_a = log_a.reshape(bh, 1, tt)

    out = pl.pallas_call(
        _ssd_kernel,
        grid=(bh, tt // lc),
        in_specs=[
            pl.BlockSpec((1, lc, p), lambda i, tchunk: (i, tchunk, 0)),
            pl.BlockSpec((1, 1, lc), lambda i, tchunk: (i, 0, tchunk)),
            pl.BlockSpec((1, lc, n), lambda i, tchunk: (i, tchunk, 0)),
            pl.BlockSpec((1, lc, n), lambda i, tchunk: (i, tchunk, 0)),
        ],
        out_specs=pl.BlockSpec((1, lc, p), lambda i, tchunk: (i, tchunk, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tt, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, log_a, b, c)
    return out[:, :t]
