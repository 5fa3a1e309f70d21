"""LModel — the public model facade: init / loss / prefill / decode.

Covers all assigned families behind one interface:
  dense | moe (+MLA/MTP) | vlm (patch-embed stub) | hybrid | ssm |
  audio (encoder-only, masked frame prediction).

Losses compute cross-entropy in token chunks so full (tokens x vocab)
logits are never materialized (vocab is 'model'-sharded; the padded vocab
tail is masked out of the logsumexp).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as tf
from repro.models.layers import (
    apply_norm, dense, dense_init, embed_init, norm_init,
)
from repro.sharding.context import constrain


def _family_fns(cfg: ArchConfig):
    if cfg.family == "ssm":
        return tf.ssm_stack_init, tf.ssm_stack_apply
    if cfg.family == "hybrid":
        return tf.hybrid_init, tf.hybrid_apply
    return tf.decoder_init, tf.decoder_apply


def init(cfg: ArchConfig, key) -> Dict[str, Any]:
    dtype = cfg.param_dtype_()
    ks = jax.random.split(key, 5)
    stack_init, _ = _family_fns(cfg)
    p: Dict[str, Any] = {
        "stack": stack_init(ks[0], cfg, dtype),
        "final_norm": norm_init(cfg.d_model, dtype, cfg.norm),
    }
    if cfg.modality == "audio_frames":
        p["mask_emb"] = jax.random.normal(ks[1], (cfg.d_model,), dtype) * 0.02
        p["head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_padded, dtype)
    else:
        p["embed"] = embed_init(ks[1], cfg.vocab_padded, cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            p["head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_padded, dtype)
    if cfg.mtp:
        p["mtp_proj"] = dense_init(ks[3], cfg.d_model, cfg.d_model, dtype)
        p["mtp_norm"] = norm_init(cfg.d_model, dtype, cfg.norm)
    return p


def _sinusoidal(t: int, d: int, dtype):
    pos = jnp.arange(t)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)[:, :d]
    return pe.astype(dtype)


def _head_weight(p, cfg: ArchConfig, dtype):
    if cfg.modality != "audio_frames" and cfg.tie_embeddings:
        return p["embed"]["table"].astype(dtype).T       # (d, Vp)
    return p["head"]["w"].astype(dtype)


def _embed_inputs(p, batch: Dict, cfg: ArchConfig):
    """Returns (h0 (B,T,d), positions (B,T), text_offset)."""
    with jax.named_scope("embed"):
        return _embed(p, batch, cfg)


def _embed(p, batch: Dict, cfg: ArchConfig):
    cd = cfg.compute_dtype_()
    if cfg.modality == "audio_frames":
        h = batch["frames"].astype(cd)
        if "mask" in batch:  # masked-prediction training
            h = jnp.where(batch["mask"][..., None],
                          p["mask_emb"].astype(cd)[None, None], h)
        b, t = h.shape[:2]
        off = 0
    else:
        emb = p["embed"]["table"].astype(cd)
        h = emb[batch["tokens"]]
        if cfg.modality == "vision_text":
            v = batch["vision_embeds"].astype(cd)
            h = jnp.concatenate([v, h], axis=1)
            off = v.shape[1]
        else:
            off = 0
        b, t = h.shape[:2]
    if cfg.pos_embed == "sinusoidal":
        h = h + _sinusoidal(t, cfg.d_model, cd)[None]
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    h = constrain(h, "batch", None, None)
    return h, positions, off


def _chunked_ce(h, head_w, targets, mask, cfg: ArchConfig,
                n_chunks: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-entropy without materializing (B,T,V) logits.

    h (B,T,d); targets/mask (B,T).  Returns (sum_loss, sum_mask)."""
    b, t, d = h.shape
    nc = n_chunks if t % n_chunks == 0 else 1
    tc = t // nc
    vp = head_w.shape[-1]
    vmask = (jnp.arange(vp) < cfg.vocab_size)

    hs = h.reshape(b, nc, tc, d).transpose(1, 0, 2, 3)
    tg = targets.reshape(b, nc, tc).transpose(1, 0, 2)
    mk = mask.reshape(b, nc, tc).transpose(1, 0, 2)

    def body(carry, xs):
        hc, tgc, mkc = xs
        logits = jnp.einsum("btd,dv->btv", hc, head_w.astype(hc.dtype)
                            ).astype(jnp.float32)
        logits = jnp.where(vmask[None, None], logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tgc[..., None], -1)[..., 0]
        loss = jnp.sum((lse - ll) * mkc)
        return (carry[0] + loss, carry[1] + jnp.sum(mkc)), None

    # checkpoint: backward recomputes per-chunk logits rather than the scan
    # saving them stacked (which would materialize the full (B,T,V) logits)
    (loss, denom), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (jnp.float32(0), jnp.float32(0)), (hs, tg, mk))
    return loss, denom


def loss_fn(params, batch: Dict, cfg: ArchConfig) -> Tuple[jnp.ndarray, Dict]:
    """Scalar training loss + metrics for any family/modality."""
    _, stack_apply = _family_fns(cfg)
    h, positions, off = _embed_inputs(params, batch, cfg)
    causal = not cfg.encoder_only
    h, _, aux = stack_apply(params["stack"], h, cfg, positions=positions,
                            caches=None, causal=causal)
    with jax.named_scope("head"):
        return _head_loss(params, h, aux, off, batch, cfg)


def _head_loss(params, h, aux, off, batch: Dict, cfg: ArchConfig):
    """Final norm, LM head and cross-entropy (plus MTP) of ``loss_fn``."""
    h = apply_norm(params["final_norm"], h, cfg.norm_eps)
    cd = cfg.compute_dtype_()
    head_w = _head_weight(params, cfg, cd)

    if cfg.modality == "audio_frames":
        targets = batch["targets"]
        mask = batch["mask"].astype(jnp.float32)
        ht = h
    else:
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        mask = jnp.ones_like(targets, jnp.float32) if mask is None \
            else mask.astype(jnp.float32)
        ht = h[:, off:] if off else h                       # text positions

    loss_sum, denom = _chunked_ce(ht, head_w, targets, mask, cfg)
    loss = loss_sum / jnp.maximum(denom, 1.0)
    metrics = {"ce": loss, "aux": aux, "tokens": denom}

    if cfg.mtp:
        # multi-token prediction: predict t+2 from a projected hidden state
        h2 = apply_norm(params["mtp_norm"],
                        dense(params["mtp_proj"], ht), cfg.norm_eps)
        t2 = jnp.roll(targets, -1, axis=1)
        m2 = mask * (jnp.arange(targets.shape[1]) <
                     targets.shape[1] - 1).astype(jnp.float32)[None]
        l2, d2 = _chunked_ce(h2, head_w, t2, m2, cfg)
        mtp = l2 / jnp.maximum(d2, 1.0)
        loss = loss + 0.3 * mtp
        metrics["mtp"] = mtp

    loss = loss + aux
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_caches(cfg: ArchConfig, batch: int, length: int):
    dtype = cfg.compute_dtype_()
    if cfg.family == "ssm":
        return tf.ssm_make_states(cfg, batch, dtype)
    if cfg.family == "hybrid":
        return tf.hybrid_make_caches(cfg, batch, length, dtype)
    return tf.decoder_make_caches(cfg, batch, length, dtype)


def prefill(params, batch: Dict, cfg: ArchConfig,
            cache_len: int) -> Tuple[jnp.ndarray, Any]:
    """Encode the prompt, fill caches, return last-position logits."""
    _, stack_apply = _family_fns(cfg)
    h, positions, _ = _embed_inputs(params, batch, cfg)
    b = h.shape[0]
    caches = make_caches(cfg, b, cache_len)
    causal = not cfg.encoder_only
    h, caches, _ = stack_apply(params["stack"], h, cfg, positions=positions,
                               caches=caches, causal=causal, remat=False)
    return _logits(params, h, cfg), caches


def _logits(params, h, cfg: ArchConfig):
    """Final norm, LM head and vocabulary mask of the last position:
    (B,T,d) -> (B,Vp) float32."""
    with jax.named_scope("head"):
        h = apply_norm(params["final_norm"], h, cfg.norm_eps)
        cd = cfg.compute_dtype_()
        logits = (h[:, -1] @ _head_weight(params, cfg, cd)).astype(
            jnp.float32)
        return jnp.where(jnp.arange(cfg.vocab_padded) < cfg.vocab_size,
                         logits, -1e30)


def decode_step(params, tokens, positions, caches,
                cfg: ArchConfig) -> Tuple[jnp.ndarray, Any]:
    """One token per sequence.  tokens (B,1) int32, positions (B,) int32."""
    _, stack_apply = _family_fns(cfg)
    cd = cfg.compute_dtype_()
    if cfg.pos_embed == "sinusoidal":
        raise NotImplementedError("encoder-only archs have no decode step")
    with jax.named_scope("embed"):
        h = params["embed"]["table"].astype(cd)[tokens]      # (B,1,d)
    pos2 = positions[:, None]
    h, caches, _ = stack_apply(params["stack"], h, cfg, positions=pos2,
                               caches=caches, causal=True, remat=False)
    return _logits(params, h, cfg), caches


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
