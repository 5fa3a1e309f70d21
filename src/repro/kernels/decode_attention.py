"""Decode attention that reads the layer-stacked KV cache in place (Pallas).

One new token per row attends over its row of the cache.  The kernel takes
the whole stacked cache, (L, B, S, Hkv, D) for K and V and (L, B, S) for the
positions, with the layer index by scalar prefetch, so no layer slice is
copied out.  Row ``b`` reads its blocks of ``block`` positions only up to the
one holding ``q_pos[b]``: with slot == position (no sliding window) the
slots past it hold ``pos == -1``, which the mask drops anyway.

One grid step runs the whole layer: a loop over rows and, inside it, over
each row's live blocks, double-buffering K, V and the positions by manual
DMA across block and row boundaries, so the read streams without a dead
grid step.  A block's positions come in the 8-row tile of the (L, B, S)
cache that holds the row (the HBM tiling refuses a one-row slice).  Each block is ``block * Hkv`` cache rows of D lanes (a
position's heads are one contiguous run); head ``h`` is every ``Hkv``-th
row, loaded strided (bf16 rows two heads to a 32-bit word).

Numerics are ``models.attention.decode_attention``'s as the TPU compiles
it: bf16 q and K into the score dot, float32 scores, online softmax and
normalisation, float32 probabilities rounded to the cache dtype for the
value dot with float32 accumulation.  The rescaling across blocks is the
only reordering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 256
NEG = -1e30
_NT = (((1,), (1,)), ((), ()))          # contract the minor dims: q @ k.T


def blocks_read(q_pos, block: int, seq: int):
    """Blocks of ``block`` positions the kernel reads of a row whose query
    sits at ``q_pos``: those up to the one holding it, at least one and at
    most ``seq // block``.  NumPy in, NumPy out (host-side counters);
    traced in, traced out."""
    xp = np if isinstance(q_pos, (int, np.integer, np.ndarray)) else jnp
    return xp.clip(q_pos // block + 1, 1, seq // block)


def _heads(buf, hkv: int, block: int):
    """The (block, D) rows of each KV head in one block buffer of
    ``block * hkv`` rows, position-major."""
    if hkv == 1:
        return [buf[...]]
    if buf.dtype.itemsize == 4:
        return [buf[pl.ds(h, block, stride=hkv), :] for h in range(hkv)]
    # 16-bit rows sit two to a 32-bit word: head 2i in the low half, 2i+1
    # in the high half; shifting either into the high half gives its value
    # exactly as float32
    words = buf.bitcast(jnp.uint32)
    out = []
    for i in range(hkv // 2):
        w = words[pl.ds(i, block, stride=hkv // 2), :]
        for bits in (w << 16, w & jnp.uint32(0xFFFF0000)):
            out.append(pltpu.bitcast(bits, jnp.float32).astype(buf.dtype))
    return out


def _kernel(layer_ref, nblk_ref, qpos_ref, q_ref, k_hbm, v_hbm, pos_hbm,
            o_ref, kbuf, vbuf, pbuf, sem, *, block: int, hkv: int,
            group: int, scale: float):
    rows = q_ref.shape[0]
    layer = layer_ref[0]
    span = block * hkv
    prows = pbuf.shape[1]           # the tile of rows that holds row b's

    def prow0(b):
        return pl.multiple_of(b // prows * prows, prows)

    def copies(b, j, slot):
        return (
            pltpu.make_async_copy(k_hbm.at[layer, b, pl.ds(j * span, span)],
                                  kbuf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[layer, b, pl.ds(j * span, span)],
                                  vbuf.at[slot], sem.at[1, slot]),
            pltpu.make_async_copy(
                pos_hbm.at[layer, pl.ds(prow0(b), prows),
                           pl.ds(j * block, block)],
                pbuf.at[slot], sem.at[2, slot]))

    for c in copies(0, 0, 0):
        c.start()

    def row(b, t):
        n, qp = nblk_ref[b], qpos_ref[b]
        q = q_ref[b]                                        # (H, D)

        def step(j, carry):
            t, m, l, acc = carry
            slot = t % 2
            last = j + 1 == n
            nb = jnp.where(last, b + 1, b)

            @pl.when(nb < rows)
            def _prefetch():
                for c in copies(nb, jnp.where(last, 0, j + 1), 1 - slot):
                    c.start()

            for c in copies(b, j, slot):
                c.wait()
            kpos = pbuf[slot, pl.ds(b - prow0(b), 1), :]    # (1, block)
            ok = (kpos >= 0) & (kpos <= qp)
            ks = _heads(kbuf.at[slot], hkv, block)
            vs = _heads(vbuf.at[slot], hkv, block)
            m2, l2, a2 = [], [], []
            for h in range(hkv):
                s = jax.lax.dot_general(
                    q[h * group:(h + 1) * group], ks[h], _NT,
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(ok, s, NEG)                   # (g, block)
                m_new = jnp.maximum(m[h], s.max(-1, keepdims=True))
                p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m[h] - m_new)
                m2.append(m_new)
                l2.append(l[h] * corr + p.sum(-1, keepdims=True))
                a2.append(acc[h] * corr + jnp.dot(
                    p.astype(vs[h].dtype), vs[h],
                    preferred_element_type=jnp.float32))
            return t + 1, tuple(m2), tuple(l2), tuple(a2)

        d = q.shape[-1]
        init = (t,
                (jnp.full((group, 1), NEG, jnp.float32),) * hkv,
                (jnp.zeros((group, 1), jnp.float32),) * hkv,
                (jnp.zeros((group, d), jnp.float32),) * hkv)
        t, _, l, acc = jax.lax.fori_loop(0, n, step, init)
        o_ref[b] = jnp.concatenate(
            [a / jnp.maximum(s, 1e-30) for a, s in zip(acc, l)], 0)
        return t

    jax.lax.fori_loop(0, rows, row, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def decode_attention(q, k, v, pos, layer, q_pos, *,
                     block: int = DEFAULT_BLOCK,
                     interpret: bool = False) -> jnp.ndarray:
    """q (B,H,D); k, v (L,B,S,Hkv,D); pos (L,B,S) int32 (-1 where
    unwritten); ``layer`` the layer to read; q_pos (B,) each row's
    position -> (B,H,D) float32.  ``S`` must be a multiple of ``block``;
    with 16-bit caches ``Hkv`` is 1 or even.  Row ``b`` reads positions
    up to the end of the block holding ``q_pos[b]``, masked as
    ``(pos >= 0) & (pos <= q_pos)``."""
    nl, b, s, hkv, d = k.shape
    h = q.shape[1]
    assert s % block == 0, (s, block)
    nblk = blocks_read(q_pos, block, s).astype(jnp.int32)
    span = block * hkv
    # positions come in the HBM tile of 8 rows that holds the row read
    prows = 8 if b % 8 == 0 else b
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec((b, h, d), lambda i, *_: (0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((b, h, d), lambda i, *_: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, span, d), k.dtype),
                        pltpu.VMEM((2, span, d), v.dtype),
                        pltpu.VMEM((2, prows, block), jnp.int32),
                        pltpu.SemaphoreType.DMA((3, 2))])
    return pl.pallas_call(
        functools.partial(_kernel, block=block, hkv=hkv, group=h // hkv,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        name="decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), nblk,
      q_pos.astype(jnp.int32), q.astype(k.dtype),
      k.reshape(nl, b, s * hkv, d), v.reshape(nl, b, s * hkv, d), pos)
