"""Distributed-semantics example: the SAME train step the 256-chip dry-run
lowers, executed for real on a tiny 4-device debug mesh (CPU host devices),
with sharded params/optimizer/batch, microbatching, and both TP dataflows.

Run in a fresh process (device count must be set before jax init):

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python examples/distributed_train.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.configs.base import ShapeSpec
from repro.data.pipeline import SyntheticLM
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.optim import adamw
from repro.sharding import rules


def main():
    assert len(jax.devices()) >= 4, "set XLA_FLAGS device count first"
    enable_compile_cache()
    mesh = make_debug_mesh((2, 2), ("data", "model"))
    shape = ShapeSpec("tiny", seq_len=64, global_batch=8, kind="train")

    for tp_mode in ("allreduce", "allgather"):
        cfg = get("qwen3-1.7b").reduced().replace(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=512).with_policy(microbatches=2, tp_mode=tp_mode)
        oc = adamw.AdamWConfig(peak_lr=5e-3, warmup_steps=5, total_steps=50)
        fn, shapes, specs = steps_mod.make_train_step(cfg, mesh, shape,
                                                      opt_cfg=oc)
        pshapes, oshapes, _ = shapes
        pspec, ospec, bspec = specs

        from repro.models import model as lm
        params = lm.init(cfg, jax.random.PRNGKey(0))
        params = jax.device_put(params, rules.to_named(pspec, mesh))
        opt = jax.device_put(adamw.init(params, oc),
                             rules.to_named(ospec, mesh))
        pipe = SyntheticLM(cfg, shape, seed=0)

        losses = []
        for step in range(20):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
            batch = jax.device_put(batch, rules.to_named(bspec, mesh))
            params, opt, mets = fn(params, opt, batch)
            losses.append(float(mets["loss_out"]))
        print(f"tp_mode={tp_mode}: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
              f"on mesh {dict(mesh.shape)}")
        assert losses[-1] < losses[0]
    print("distributed_train OK")


if __name__ == "__main__":
    main()
