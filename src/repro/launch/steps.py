"""Compiled step builders: train / prefill / decode, fully sharded.

Each builder returns (jit_fn, arg_shapes, arg_shardings) so callers can
either execute (real training) or ``.lower().compile()`` against
ShapeDtypeStructs (the multi-pod dry-run).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec, input_specs
from repro.models import model as lm
from repro.optim import adamw
from repro.sharding import rules
from repro.sharding.context import use_mesh


def abstract_params(cfg: ArchConfig):
    return jax.eval_shape(lambda k: lm.init(cfg, k), jax.random.PRNGKey(0))


def abstract_opt(cfg: ArchConfig, opt_cfg: adamw.AdamWConfig):
    ps = abstract_params(cfg)
    return jax.eval_shape(lambda p: adamw.init(p, opt_cfg), ps)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _split_microbatches(batch: Dict, mb: int):
    return jax.tree.map(
        lambda x: x.reshape(mb, x.shape[0] // mb, *x.shape[1:]), batch)


def make_train_step(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec,
                    opt_cfg: adamw.AdamWConfig | None = None,
                    donate: bool = True):
    opt_cfg = opt_cfg or adamw.from_policy(cfg.policy)
    mb = cfg.policy.microbatches
    accum_dtype = (jnp.bfloat16 if cfg.policy.param_dtype == "bfloat16"
                   else jnp.float32)

    def loss_fn(p, b):
        return lm.loss_fn(p, b, cfg)

    def train_step(params, opt_state, batch):
        with use_mesh(mesh):
            if mb == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            else:
                mbs = _split_microbatches(batch, mb)

                def body(acc, mbatch):
                    (l, mets), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, mbatch)
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(a.dtype), acc, g)
                    return acc, (l, mets)

                acc0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), params)
                grads, (ls, mets) = jax.lax.scan(body, acc0, mbs)
                grads = jax.tree.map(lambda g: g / mb, grads)
                loss = ls.mean()
                metrics = jax.tree.map(lambda x: x.mean(), mets)
            params2, opt2, om = adamw.apply(params, grads, opt_state, opt_cfg)
            metrics = dict(metrics, **om, loss_out=loss)
            return params2, opt2, metrics

    pshapes = abstract_params(cfg)
    oshapes = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), pshapes)
    bshapes = input_specs(cfg, shape)
    pspec = rules.param_pspecs(cfg, pshapes, mesh)
    ospec = rules.opt_pspecs(cfg, oshapes, mesh)
    bspec = rules.batch_pspecs(cfg, bshapes, mesh)
    mspec = P()

    fn = jax.jit(
        train_step,
        in_shardings=(rules.to_named(pspec, mesh), rules.to_named(ospec, mesh),
                      rules.to_named(bspec, mesh)),
        out_shardings=(rules.to_named(pspec, mesh),
                       rules.to_named(ospec, mesh), None),
        donate_argnums=(0, 1) if donate else (),
    )
    return fn, (pshapes, oshapes, bshapes), (pspec, ospec, bspec)


# ---------------------------------------------------------------------------
# serve: prefill + decode
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec):
    cache_len = shape.seq_len

    def prefill_step(params, batch):
        with use_mesh(mesh):
            return lm.prefill(params, batch, cfg, cache_len=cache_len)

    pshapes = abstract_params(cfg)
    bshapes = input_specs(cfg, shape)
    pspec = rules.param_pspecs(cfg, pshapes, mesh)
    bspec = rules.batch_pspecs(cfg, bshapes, mesh)
    cshapes = jax.eval_shape(
        lambda: lm.make_caches(cfg, shape.global_batch, cache_len))
    cspec = rules.cache_pspecs(cfg, cshapes, mesh)
    baxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    lspec = P(baxes if shape.global_batch % (
        _prod(mesh, baxes)) == 0 else None, "model")

    fn = jax.jit(
        prefill_step,
        in_shardings=(rules.to_named(pspec, mesh), rules.to_named(bspec, mesh)),
        out_shardings=(NamedSharding(mesh, lspec), rules.to_named(cspec, mesh)),
    )
    return fn, (pshapes, bshapes), (pspec, bspec, cspec)


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def make_decode_step(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec,
                     donate: bool = True):
    cache_len = (min(shape.seq_len, cfg.sliding_window)
                 if cfg.sliding_window else shape.seq_len)

    def decode(params, tokens, positions, caches):
        with use_mesh(mesh):
            return lm.decode_step(params, tokens, positions, caches, cfg)

    b = shape.global_batch
    pshapes = abstract_params(cfg)
    pspec = rules.param_pspecs(cfg, pshapes, mesh)
    tshape = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    posshape = jax.ShapeDtypeStruct((b,), jnp.int32)
    cshapes = jax.eval_shape(lambda: lm.make_caches(cfg, b, cache_len))
    cspec = rules.cache_pspecs(cfg, cshapes, mesh)
    baxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    bax = baxes if b % _prod(mesh, baxes) == 0 else None
    tspec, posspec = P(bax, None), P(bax)
    lspec = P(bax, "model")

    fn = jax.jit(
        decode,
        in_shardings=(rules.to_named(pspec, mesh),
                      NamedSharding(mesh, tspec), NamedSharding(mesh, posspec),
                      rules.to_named(cspec, mesh)),
        out_shardings=(NamedSharding(mesh, lspec), rules.to_named(cspec, mesh)),
        donate_argnums=(3,) if donate else (),
    )
    shapes = (pshapes, tshape, posshape, cshapes)
    return fn, shapes, (pspec, tspec, posspec, cspec)


def make_step_for(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec):
    """The step a given (arch x shape) cell lowers (train vs serve)."""
    if shape.kind == "train":
        return ("train_step",) + make_train_step(cfg, mesh, shape)
    if shape.kind == "prefill":
        return ("prefill_step",) + make_prefill_step(cfg, mesh, shape)
    return ("decode_step",) + make_decode_step(cfg, mesh, shape)
