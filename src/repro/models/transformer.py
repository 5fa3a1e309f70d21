"""Blocks and stacks: decoder/encoder transformer, MoE, SSM, Zamba2 hybrid.

All stacks scan over layer-stacked parameters (compact HLO at 61-80 layers)
with optional per-layer remat.  Caches are layer-stacked pytrees: prefill
threads them through the scans' xs/ys; decode carries the attention caches
and updates them in place (``_scan_in_place``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    Backend, XLA, apply_norm, dense, dense_init, mlp, mlp_init, norm_init,
)
from repro.sharding.context import constrain


def constrain_sp(h):
    return constrain(h, "batch", "model", None)


def _remat_policy(cfg: ArchConfig):
    """'full': recompute everything in backward (min memory).  'dots': save
    matmul outputs — backward re-runs neither the forward GEMMs nor the
    forward collectives, trading memory for the dominant roofline terms.
    'save_collectives': save only the post-all-reduce block outputs (two
    d-sized tensors per layer) so the backward recompute never re-runs the
    forward collectives — the memory-term price of 'dots' without saving
    the f-sized hidden tensors."""
    if cfg.policy.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.policy.remat_policy == "save_collectives":
        return jax.checkpoint_policies.save_only_these_names("blk_out")
    return None


def _stack_init(key, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# attention + (mlp | moe) block
# ---------------------------------------------------------------------------


def block_init(key, cfg: ArchConfig, dtype, use_moe: bool):
    k1, k2 = jax.random.split(key)
    p = {"ln1": norm_init(cfg.d_model, dtype, cfg.norm),
         "ln2": norm_init(cfg.d_model, dtype, cfg.norm)}
    if cfg.mla is not None:
        p["attn"] = attn_mod.mla_init(k1, cfg, dtype)
    else:
        p["attn"] = attn_mod.attn_init(k1, cfg, dtype)
    if use_moe:
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def block_apply(p, h, cfg: ArchConfig, *, positions, cache=None, layer=None,
                backend: Backend = XLA, causal=True):
    # named scopes: each operation's op_name says which half of the block
    # it belongs to, in the compiled program and in a profiler trace
    with jax.named_scope("attn"):
        x = apply_norm(p["ln1"], h, cfg.norm_eps)
        if cfg.mla is not None:
            a, new_cache = attn_mod.mla_apply(p["attn"], x, cfg,
                                              positions=positions,
                                              cache=cache, layer=layer,
                                              backend=backend)
        else:
            a, new_cache = attn_mod.attention_apply(
                p["attn"], x, cfg, positions=positions, cache=cache,
                layer=layer, backend=backend, causal=causal)
        a = jax.ad_checkpoint.checkpoint_name(a, "blk_out")
        h = h + a
    with jax.named_scope("moe" if "moe" in p else "mlp"):
        x = apply_norm(p["ln2"], h, cfg.norm_eps)
        if "moe" in p:
            y, aux = moe_mod.moe_apply(p["moe"], x, cfg, backend)
        else:
            y, aux = mlp(p["mlp"], x, cfg.act, backend,
                         policy=cfg.policy), jnp.float32(0)
        h = h + jax.ad_checkpoint.checkpoint_name(y, "blk_out")
    if cfg.policy.sp and h.shape[1] > 1:
        # sequence-parallel residual stream: the per-layer saved residual
        # stack shards its seq dim over 'model' (Megatron-SP posture); XLA
        # inserts the all-gather at the next block's attention
        h = constrain_sp(h)
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# uniform stack (dense / vlm / audio / moe-with-leading-dense)
# ---------------------------------------------------------------------------


def _scan_in_place(fn, h, xs, caches):
    """Decode scan over stacked layers: ``fn(h, caches, x, l) -> (h, caches,
    y)`` updates the layer-stacked ``caches`` at layer ``l`` and they ride
    in the carry, so the loop aliases the (donated) buffers end to end.
    Threading them through xs/ys instead copies each layer's cache out and
    back and the whole stack once more."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]

    def body(carry, inp):
        x, l = inp
        hh, c, y = fn(*carry, x, l)
        return (hh, c), y

    (h, caches), ys = jax.lax.scan(body, (h, caches), (xs, jnp.arange(n)))
    return h, caches, ys


def _scan_blocks(params_stack, h, cfg, *, positions, caches, backend, causal,
                 remat: bool):
    fn = functools.partial(block_apply, cfg=cfg, positions=positions,
                           backend=backend, causal=causal)
    if caches is not None and h.shape[1] == 1:
        with jax.named_scope("stack"):
            h, new_caches, auxs = _scan_in_place(
                lambda hh, c, p, l: fn(p, hh, cache=c, layer=l),
                h, params_stack, caches)
        return h, new_caches, jnp.sum(auxs)

    def body(carry, xs):
        p, c = xs
        out, nc, aux = fn(p, carry, cache=c)
        return out, (nc, aux)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False,
                              policy=_remat_policy(cfg))
    with jax.named_scope("stack"):
        h, (new_caches, auxs) = jax.lax.scan(body, h, (params_stack, caches))
    return h, new_caches, jnp.sum(auxs)


def decoder_init(key, cfg: ArchConfig, dtype):
    """Transformer decoder (and encoder — causal flag at apply time)."""
    fd = cfg.moe.first_dense_layers if cfg.moe else cfg.n_layers
    fd = min(fd, cfg.n_layers)
    nm = cfg.n_layers - fd
    k1, k2 = jax.random.split(key)
    p = {}
    if fd:
        p["dense_stack"] = _stack_init(
            k1, fd, lambda k: block_init(k, cfg, dtype, use_moe=False))
    if nm:
        p["moe_stack"] = _stack_init(
            k2, nm, lambda k: block_init(k, cfg, dtype, use_moe=True))
    return p


def decoder_make_caches(cfg: ArchConfig, batch: int, length: int, dtype):
    fd = min(cfg.moe.first_dense_layers if cfg.moe else cfg.n_layers,
             cfg.n_layers)
    nm = cfg.n_layers - fd
    mk = (attn_mod.mla_make_cache if cfg.mla is not None
          else attn_mod.make_cache)
    c = {}
    if fd:
        c["dense_stack"] = mk(cfg, batch, length, dtype, layers=fd)
    if nm:
        c["moe_stack"] = mk(cfg, batch, length, dtype, layers=nm)
    return c


def decoder_apply(p, h, cfg: ArchConfig, *, positions, caches=None,
                  backend: Backend = XLA, causal=True, remat=None):
    remat = cfg.policy.remat if remat is None else remat
    new_caches, aux = {}, jnp.float32(0)
    for name in ("dense_stack", "moe_stack"):
        if name not in p:
            continue
        n = jax.tree_util.tree_leaves(p[name])[0].shape[0]
        cs = caches.get(name) if caches else _none_stack(n)
        h, nc, a = _scan_blocks(p[name], h, cfg, positions=positions,
                                caches=cs, backend=backend, causal=causal,
                                remat=remat and caches is None)
        if caches is not None:
            new_caches[name] = nc
        aux = aux + a
    return h, (new_caches if caches is not None else None), aux


def _none_stack(n: int):
    return None


# ---------------------------------------------------------------------------
# SSM stack (mamba2)
# ---------------------------------------------------------------------------


def ssm_stack_init(key, cfg: ArchConfig, dtype):
    def one(k):
        kk = jax.random.split(k, 2)
        return {"ln": norm_init(cfg.d_model, dtype, cfg.norm),
                "mamba": ssm_mod.mamba_init(kk[0], cfg, dtype)}
    return {"ssm_stack": _stack_init(key, cfg.n_layers, one)}


def ssm_make_states(cfg: ArchConfig, batch: int, dtype):
    return {"ssm_stack": ssm_mod.mamba_make_state(cfg, batch, dtype,
                                                  layers=cfg.n_layers)}


def ssm_stack_apply(p, h, cfg: ArchConfig, *, positions, caches=None,
                    backend: Backend = XLA, remat=None, **_):
    remat = cfg.policy.remat if remat is None else remat

    def body(carry, xs):
        lp, st = xs
        with jax.named_scope("ssm"):
            x = apply_norm(lp["ln"], carry, cfg.norm_eps)
            y, ns = ssm_mod.mamba_apply(lp["mamba"], x, cfg, state=st,
                                        backend=backend)
            return carry + y, ns

    if remat and caches is None:
        body = jax.checkpoint(body, prevent_cse=False)
    cs = caches["ssm_stack"] if caches else None
    with jax.named_scope("stack"):
        h, ns = jax.lax.scan(body, h, (p["ssm_stack"], cs))
    return h, ({"ssm_stack": ns} if caches is not None else None), jnp.float32(0)


# ---------------------------------------------------------------------------
# Zamba2 hybrid: mamba backbone + shared attention blocks every k layers
# ---------------------------------------------------------------------------


def hybrid_init(key, cfg: ArchConfig, dtype):
    hy = cfg.hybrid
    d = cfg.d_model
    every = hy.shared_every
    groups = cfg.n_layers // every
    tail = cfg.n_layers % every
    ks = jax.random.split(key, 6)

    def mamba_one(k):
        return {"ln": norm_init(d, dtype, cfg.norm),
                "mamba": ssm_mod.mamba_init(k, cfg, dtype)}

    def shared_one(k):
        kk = jax.random.split(k, 3)
        return {
            "in_proj": dense_init(kk[0], 2 * d, d, dtype),
            "block": block_init(kk[1], cfg, dtype, use_moe=False),
        }

    p = {
        "groups": _stack_init(ks[0], groups * every, mamba_one),
        "shared": _stack_init(ks[1], hy.n_shared_blocks, shared_one),
        # per-application LoRA on the shared input projection
        "lora_a": jax.random.normal(ks[2], (groups, 2 * d, hy.lora_rank),
                                    dtype) * (2 * d) ** -0.5,
        "lora_b": jnp.zeros((groups, hy.lora_rank, d), dtype),
    }
    if tail:
        p["tail"] = _stack_init(ks[3], tail, mamba_one)
    return p


def hybrid_make_caches(cfg: ArchConfig, batch: int, length: int, dtype):
    hy = cfg.hybrid
    groups = cfg.n_layers // hy.shared_every
    tail = cfg.n_layers % hy.shared_every
    c = {
        "groups": ssm_mod.mamba_make_state(cfg, batch, dtype,
                                           layers=groups * hy.shared_every),
        "shared_kv": attn_mod.make_cache(cfg, batch, length, dtype,
                                         layers=groups),
    }
    if tail:
        c["tail"] = ssm_mod.mamba_make_state(cfg, batch, dtype, layers=tail)
    return c


def hybrid_apply(p, h, cfg: ArchConfig, *, positions, caches=None,
                 backend: Backend = XLA, remat=None, **_):
    """Outer scan over groups; each group = ``shared_every`` mamba layers +
    one application of a shared attention block (round-robin over the
    distinct shared blocks, with per-application LoRA on its input proj)."""
    hy = cfg.hybrid
    remat = cfg.policy.remat if remat is None else remat
    every = hy.shared_every
    groups = cfg.n_layers // every
    e0 = h                                                   # original embeds

    gp = jax.tree.map(
        lambda x: x.reshape(groups, every, *x.shape[1:]), p["groups"])
    gc = (jax.tree.map(lambda x: x.reshape(groups, every, *x.shape[1:]),
                       caches["groups"]) if caches else None)
    kvc = caches["shared_kv"] if caches else None
    shared_ids = jnp.arange(groups) % hy.n_shared_blocks

    def mamba_body(carry, xs):
        lp, st = xs
        with jax.named_scope("ssm"):
            x = apply_norm(lp["ln"], carry, cfg.norm_eps)
            y, ns = ssm_mod.mamba_apply(lp["mamba"], x, cfg, state=st,
                                        backend=backend)
            return carry + y, ns

    def group(hcur, kv, xs, layer):
        glp, gst, la, lb, sid = xs
        hcur, gns = jax.lax.scan(mamba_body, hcur, (glp, gst))
        sp = jax.tree.map(lambda x: x[sid], p["shared"])
        with jax.named_scope("attn"):       # the shared block's input
            cat = jnp.concatenate([hcur, jnp.broadcast_to(e0, hcur.shape)],
                                  -1)
            w = sp["in_proj"]["w"].astype(cat.dtype) + (
                la.astype(cat.dtype) @ lb.astype(cat.dtype))
            xin = cat @ w
        y, nkv, _ = block_apply(sp["block"], xin, cfg, positions=positions,
                                cache=kv, layer=layer, backend=backend,
                                causal=True)
        return hcur + (y - xin), nkv, gns   # residual on the block's delta

    def group_body(carry, xs_kv):
        hcur, nkv, gns = group(carry, xs_kv[1], xs_kv[0], None)
        return hcur, (gns, nkv)

    if remat and caches is None:
        group_body = jax.checkpoint(group_body, prevent_cse=False)

    xs = (gp, gc, p["lora_a"], p["lora_b"], shared_ids)
    with jax.named_scope("stack"):
        if caches is not None and h.shape[1] == 1:
            h, nkv, gns = _scan_in_place(group, h, xs, kvc)
        else:
            h, (gns, nkv) = jax.lax.scan(group_body, h, (xs, kvc))
    new_caches = None
    if caches is not None:
        new_caches = {
            "groups": jax.tree.map(
                lambda x: x.reshape(groups * every, *x.shape[2:]), gns),
            "shared_kv": nkv,
        }
    if "tail" in p:
        tc = caches["tail"] if caches else None
        body = mamba_body
        if remat and caches is None:
            body = jax.checkpoint(mamba_body, prevent_cse=False)
        with jax.named_scope("stack"):
            h, tns = jax.lax.scan(body, h, (p["tail"], tc))
        if caches is not None:
            new_caches["tail"] = tns
    return h, new_caches, jnp.float32(0)
