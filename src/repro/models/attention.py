"""Attention: GQA/MQA, qk-norm, RoPE, sliding windows, MLA, KV caches.

Memory-linear by construction: training/prefill attention is a chunked
online-softmax scan over KV blocks (the pure-jnp twin of the Pallas flash
kernel — same math, lowered by XLA for the dry-run), so 32k prefill never
materializes a T x T score matrix.  Decode (one new token per row) writes
that token into the layer-stacked cache in place.  On a TPU, where the
input allows it (``decode_kv_block``), the Pallas kernel
``kernels.decode_attention`` then reads the stacked cache in place, each
row only up to its query position; everywhere else ``decode_attention``
attends over the layer's slice as it is stored: no chunk-major or float32
copy of the cache.

Sharding posture (single/multi-pod mesh): q heads shard on 'model'; KV
tensors shard on heads when divisible, else on head_dim (partial scores are
then all-reduced over 'model' — a small (B,H,Tq,Tk)-free collective since
only the contraction dim is sharded).  See sharding/rules.py.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import decode_attention as decode_kernel
from repro.models.layers import (
    Backend, XLA, apply_norm, dense, dense_init, norm_init, out_constrain,
    rope,
)
from repro.sharding.context import constrain, current_mesh

NEG = -1e30


# ---------------------------------------------------------------------------
# chunked online-softmax attention (pure jnp; GQA-aware)
# ---------------------------------------------------------------------------


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      chunk: int = 1024, q_chunk: int = 512,
                      q_offset=0) -> jnp.ndarray:
    """q (B,Tq,H,D), k/v (B,Tk,Hkv,Dv?) -> (B,Tq,H,Dv).

    Memory-linear in BOTH directions: an outer scan over q blocks wraps the
    inner online-softmax scan over KV blocks, so the largest live score
    tensor is (B, q_chunk, H, chunk).

    ``q_offset``: absolute position of q[0] (scalar or (B,)); k/v sit at
    positions 0..Tk-1.
    """
    b, tq, h, d = q.shape
    if tq > q_chunk:
        pad = (-tq) % q_chunk
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nq = (tq + pad) // q_chunk
        qb = q.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
        offs = jnp.broadcast_to(jnp.asarray(q_offset), (b,))

        def qstep(_, inp):
            qi, off = inp
            out = chunked_attention(
                qi, k, v, causal=causal, window=window, chunk=chunk,
                q_chunk=q_chunk, q_offset=off)
            return None, out

        _, outs = jax.lax.scan(
            qstep, None,
            (qb, offs[None, :] + jnp.arange(nq)[:, None] * q_chunk))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(b, tq + pad, h, -1)
        return out[:, :tq]
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hkv
    scale = d ** -0.5
    chunk = min(chunk, tk)
    pad = (-tk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = (tk + pad) // chunk
    kv_positions = jnp.broadcast_to(jnp.arange(tk + pad)[None], (b, tk + pad))
    kv_valid = jnp.full((b,), tk, jnp.int32)
    qpos = (jnp.broadcast_to(jnp.asarray(q_offset), (b,))[:, None]
            + jnp.arange(tq)[None, :])                       # (B, Tq)

    qg = q.reshape(b, tq, hkv, g, d).astype(jnp.float32)
    kc = k.reshape(b, nb, chunk, hkv, d).astype(jnp.float32)
    vc = v.reshape(b, nb, chunk, hkv, dv).astype(jnp.float32)
    pc = kv_positions.reshape(b, nb, chunk)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, pb, slot0 = inp                              # (B,chunk,Hkv,D)...
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb) * scale  # (B,Tq,Hkv,g,chunk)
        kpos = pb[:, None, None, None, :]                    # (B,1,1,1,chunk)
        qp = qpos[:, :, None, None, None]
        slot = slot0 + jnp.arange(kb.shape[1])
        ok = slot[None, :, None] < kv_valid[:, None, None]   # (B,chunk,1)
        mask = jnp.transpose(ok, (0, 2, 1))[:, :, None, None, :]
        if causal:
            mask = mask & (kpos <= qp)
        if window > 0:
            mask = mask & (kpos > qp - window)
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((b, tq, hkv, g), NEG, jnp.float32)
    l0 = jnp.zeros((b, tq, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, tq, hkv, g, dv), jnp.float32)
    # checkpoint the chunk body: backward recomputes scores instead of the
    # scan saving per-chunk (B,Tq,H,chunk) residuals — this is what keeps
    # 32k attention memory-linear end to end
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step, prevent_cse=False), (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
         pc.transpose(1, 0, 2), jnp.arange(nb) * chunk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, tq, h, dv).astype(q.dtype)


def decode_attention(q, k, v, *, q_pos, kv_pos, window: int = 0):
    """One new token per row against a whole cache, read as it is stored.

    q (B,H,D), k (B,S,Hkv,D), v (B,S,Hkv,Dv) -> (B,H,Dv) float32.  ``q`` and
    ``k`` may also be tuples of parts split along D (MLA's latent and rope
    halves): their scores add up, so no concatenated copy of the cache is
    made.  ``q_pos`` (B,) is each row's position, ``kv_pos`` (B,S) each
    cache slot's (-1 where unwritten).  The cache dtype goes into both dots
    unconverted; scores, softmax and accumulation are float32.
    """
    qs, ks = (q, k) if isinstance(q, tuple) else ((q,), (k,))
    b, h = qs[0].shape[:2]
    hkv = v.shape[2]
    scale = sum(x.shape[-1] for x in qs) ** -0.5
    s = sum(jnp.einsum("bhgd,bkhd->bhgk",
                       qi.reshape(b, hkv, h // hkv, -1).astype(jnp.float32),
                       ki, preferred_element_type=jnp.float32)
            for qi, ki in zip(qs, ks)) * scale             # (B,Hkv,g,S)
    kpos = kv_pos[:, None, None, :]
    qp = q_pos[:, None, None, None]
    mask = (kpos >= 0) & (kpos <= qp)      # -1 marks unwritten cache slots
    if window > 0:
        mask = mask & (kpos > qp - window)
    s = jnp.where(mask, s, NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(p.sum(-1), 1e-30)[..., None]
    return out.reshape(b, h, -1)


def decode_kv_block(cfg: ArchConfig, cache_len: int, platform: str,
                    mesh=None) -> int:
    """Positions per block that ``kernels.decode_attention`` reads when it
    serves the decode read on ``platform``; 0 where ``decode_attention``
    (XLA) serves it.

    The kernel reads a row only up to its query position, which holds
    where slot == position: no sliding window and a plain (not MLA) cache.
    It also needs heads of whole 128-lane rows, 1 or a multiple of 8 KV
    heads (then the position-major view of the cache is a bitcast, not a
    copy), a cache of whole blocks, and no mesh axis over 1.
    """
    if (platform != "tpu" or cfg.attention_free or cfg.mla is not None
            or cfg.sliding_window or cfg.head_dim_ % 128
            or not (cfg.n_kv_heads == 1 or cfg.n_kv_heads % 8 == 0)):
        return 0
    if mesh is not None and any(n > 1 for n in mesh.shape.values()):
        return 0
    block = min(decode_kernel.DEFAULT_BLOCK, cache_len)
    return block if cache_len % block == 0 else 0


# ---------------------------------------------------------------------------
# standard GQA attention module
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ArchConfig, dtype):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype, bias=cfg.attn_bias),
        "wk": dense_init(ks[1], d, hkv * hd, dtype, bias=cfg.attn_bias),
        "wv": dense_init(ks[2], d, hkv * hd, dtype, bias=cfg.attn_bias),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["qnorm"] = norm_init(hd, dtype)
        p["knorm"] = norm_init(hd, dtype)
    return p


def make_cache(cfg: ArchConfig, batch: int, length: int, dtype,
               layers: Optional[int] = None):
    """Standard KV cache (rolling when sliding_window > 0)."""
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    if cfg.sliding_window:
        length = min(length, cfg.sliding_window)
    shape = (batch, length, hkv, hd)
    if layers is not None:
        shape = (layers,) + shape
    pshape = shape[:-2]
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.full(pshape, -1, jnp.int32),   # absolute position per slot
    }


def attention_apply(p, x, cfg: ArchConfig, *, positions, cache=None,
                    layer=None, backend: Backend = XLA, causal=True,
                    chunk: int = 1024) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x (B,T,d).  Training/prefill: cache is None or gets filled.
    Decode: T==1, ``cache`` is the layer-stacked cache (L,B,S,...) and
    ``layer`` this layer's index in it; the new entries are written in
    place (rolling for SWA) and the layer's slice is read."""
    b, t, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = dense(p["wq"], x, backend).reshape(b, t, h, hd)
    k = dense(p["wk"], x, backend).reshape(b, t, hkv, hd)
    v = dense(p["wv"], x, backend).reshape(b, t, hkv, hd)
    if cfg.qk_norm:
        q = apply_norm(p["qnorm"], q, cfg.norm_eps)
        k = apply_norm(p["knorm"], k, cfg.norm_eps)
    if cfg.pos_embed == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)

    new_cache = None
    if cache is None:
        out = chunked_attention(q, k, v, causal=causal,
                                window=cfg.sliding_window, chunk=chunk,
                                q_offset=positions[:, 0])
    elif t > 1:
        # prefill into the cache (rolling tail for SWA)
        clen = cache["k"].shape[1]
        kk, vv, pp = k, v, jnp.broadcast_to(positions, (b, t))
        if t >= clen:
            kk, vv, pp = k[:, -clen:], v[:, -clen:], pp[:, -clen:]
            slot = jnp.zeros((b,), jnp.int32)
        else:
            slot = jnp.zeros((b,), jnp.int32)
        with jax.named_scope("kv_write"):
            newk = jax.lax.dynamic_update_slice(
                cache["k"], kk.astype(cache["k"].dtype), (0, 0, 0, 0))
            newv = jax.lax.dynamic_update_slice(
                cache["v"], vv.astype(cache["v"].dtype), (0, 0, 0, 0))
            npos = jax.lax.dynamic_update_slice(
                cache["pos"], pp.astype(jnp.int32), (0, 0))
        new_cache = {"k": newk, "v": newv, "pos": npos}
        out = chunked_attention(q, k, v, causal=causal,
                                window=cfg.sliding_window, chunk=chunk,
                                q_offset=positions[:, 0])
    else:
        # decode: write the new kv at [layer, row, slot] of the stacked
        # cache, then read it: in place by the Pallas kernel on a TPU where
        # decode_kv_block allows, else over the layer's slice
        mesh = current_mesh()
        msize = mesh.shape.get("model", 1) if mesh else 1
        heads_shardable = hkv % max(msize, 1) == 0
        clen = cache["k"].shape[2]
        pos = positions[:, 0] if positions.ndim > 1 else positions  # (B,)
        slot = (pos % clen) if cfg.sliding_window else pos
        bi = jnp.arange(b)
        with jax.named_scope("kv_write"):
            new_cache = {
                "k": cache["k"].at[layer, bi, slot].set(
                    k[:, 0].astype(cache["k"].dtype)),
                "v": cache["v"].at[layer, bi, slot].set(
                    v[:, 0].astype(cache["v"].dtype)),
                "pos": cache["pos"].at[layer, bi, slot].set(
                    pos.astype(jnp.int32)),
            }

        def layer_slice():
            qq = q
            kk, vv = new_cache["k"][layer], new_cache["v"][layer]
            if heads_shardable:
                kk = constrain(kk, "batch", None, "model", None)
                vv = constrain(vv, "batch", None, "model", None)
            else:
                # KV heads don't divide the model axis: shard head_dim on
                # both q and kv so the score contraction is over the
                # sharded dim — a small all-reduce of (B,H,Tk) partials
                # instead of cache all-gathers
                qq = constrain(qq, "batch", None, None, "model")
                kk = constrain(kk, "batch", None, None, "model")
                vv = constrain(vv, "batch", None, None, "model")
            return decode_attention(qq[:, 0], kk, vv, q_pos=pos,
                                    kv_pos=new_cache["pos"][layer],
                                    window=cfg.sliding_window)

        block = decode_kv_block(cfg, clen, "tpu", mesh)
        if block:
            out = jax.lax.platform_dependent(
                tpu=lambda: decode_kernel.decode_attention(
                    q[:, 0], new_cache["k"], new_cache["v"],
                    new_cache["pos"], layer, pos, block=block),
                default=layer_slice)
        else:
            out = layer_slice()
        out = out[:, None].astype(q.dtype)
    out = constrain(out, "batch", None, "model", None)
    y = dense(p["wo"], out.reshape(b, t, h * hd), backend)
    return out_constrain(y, cfg.policy), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): low-rank q/kv with compressed latent cache
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ArchConfig, dtype):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 8)
    return {
        "wdq": dense_init(ks[0], d, m.q_lora_rank, dtype),
        "qnorm": norm_init(m.q_lora_rank, dtype),
        "wuq": dense_init(ks[1], m.q_lora_rank, h * qd, dtype),
        "wdkv": dense_init(ks[2], d, m.kv_lora_rank, dtype),
        "kvnorm": norm_init(m.kv_lora_rank, dtype),
        "wkr": dense_init(ks[3], d, m.qk_rope_dim, dtype),
        "wuk": dense_init(ks[4], m.kv_lora_rank, h * m.qk_nope_dim, dtype),
        "wuv": dense_init(ks[5], m.kv_lora_rank, h * m.v_head_dim, dtype),
        "wo": dense_init(ks[6], h * m.v_head_dim, d, dtype),
    }


def mla_make_cache(cfg: ArchConfig, batch: int, length: int, dtype,
                   layers: Optional[int] = None):
    m = cfg.mla
    shape_c = (batch, length, m.kv_lora_rank)
    shape_r = (batch, length, m.qk_rope_dim)
    if layers is not None:
        shape_c = (layers,) + shape_c
        shape_r = (layers,) + shape_r
    return {"ckv": jnp.zeros(shape_c, dtype),
            "kr": jnp.zeros(shape_r, dtype)}


def mla_apply(p, x, cfg: ArchConfig, *, positions, cache=None, layer=None,
              backend: Backend = XLA, chunk: int = 1024):
    """Like ``attention_apply``: decode (T==1) writes into the layer-stacked
    latent cache at ``layer`` and reads the layer's slice."""
    m = cfg.mla
    b, t, d = x.shape
    h = cfg.n_heads
    nd, rd, vd = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim

    q = dense(p["wuq"], apply_norm(p["qnorm"], dense(p["wdq"], x, backend),
                                   cfg.norm_eps), backend)
    q = q.reshape(b, t, h, nd + rd)
    qn, qr = q[..., :nd], q[..., nd:]
    qr = rope(qr, positions, cfg.rope_theta)
    ckv = apply_norm(p["kvnorm"], dense(p["wdkv"], x, backend), cfg.norm_eps)
    kr = rope(dense(p["wkr"], x, backend)[:, :, None, :], positions,
              cfg.rope_theta)[:, :, 0]                        # shared head

    # absorbed form: fold W_uk into q, attend directly against the latent —
    # the compressed cache is both k and v (reduction-free: no per-head KV
    # expansion is ever materialized for decode)
    wuk = p["wuk"]["w"].astype(q.dtype).reshape(m.kv_lora_rank, h, nd)
    q_lat = jnp.einsum("bthn,rhn->bthr", qn, wuk)             # (B,T,H,r)
    scale_fix = ((nd + rd) ** -0.5) / ((m.kv_lora_rank + rd) ** -0.5)

    new_cache = None
    if cache is not None and t == 1:
        pos = positions[:, 0] if positions.ndim > 1 else positions
        bi = jnp.arange(b)
        with jax.named_scope("kv_write"):
            new_cache = {
                "ckv": cache["ckv"].at[layer, bi, pos].set(
                    ckv[:, 0].astype(cache["ckv"].dtype)),
                "kr": cache["kr"].at[layer, bi, pos].set(
                    kr[:, 0].astype(cache["kr"].dtype)),
            }
        ckv_l = constrain(new_cache["ckv"][layer], "batch", None, None)
        kr_l = constrain(new_cache["kr"][layer], "batch", None, None)
        qs = tuple(constrain(qi[:, 0] * scale_fix, "batch", "model", None)
                   for qi in (q_lat, qr))
        slots = jnp.arange(ckv_l.shape[1])
        out = decode_attention(
            qs, (ckv_l[:, :, None], kr_l[:, :, None]), ckv_l[:, :, None],
            q_pos=pos, kv_pos=jnp.broadcast_to(slots, (b, slots.size)))
        out = out[:, None].astype(q.dtype)                    # (B,1,H,r)
    else:
        if cache is not None:  # prefill fills the cache
            with jax.named_scope("kv_write"):
                new_cache = {
                    "ckv": jax.lax.dynamic_update_slice(
                        cache["ckv"], ckv.astype(cache["ckv"].dtype),
                        (0, 0, 0)),
                    "kr": jax.lax.dynamic_update_slice(
                        cache["kr"], kr.astype(cache["kr"].dtype), (0, 0, 0)),
                }
        qq = jnp.concatenate([q_lat, qr], -1)                 # (B,T,H,r+rd)
        qq = constrain(qq, "batch", None, "model", None)
        kk = jnp.concatenate([ckv, kr], -1)[:, :, None, :]
        # gather the latent KV across the seq dim ONCE per layer (with SP
        # the inputs arrive seq-sharded; without this, every KV-chunk slice
        # in the attention scan triggers its own gather)
        kk = constrain(kk, "batch", None, None, None)
        ckv = constrain(ckv, "batch", None, None)
        out = chunked_attention(                              # (B,T,H,r)
            qq * scale_fix, kk, ckv[:, :, None, :], causal=True,
            chunk=chunk,
            q_offset=(positions[:, 0] if positions.ndim > 1 else positions))
    wuv = p["wuv"]["w"].astype(q.dtype).reshape(m.kv_lora_rank, h, vd)
    out = jnp.einsum("bthr,rhv->bthv", out, wuv)
    y = dense(p["wo"], out.reshape(b, t, h * vd), backend)
    return out_constrain(y, cfg.policy), new_cache
