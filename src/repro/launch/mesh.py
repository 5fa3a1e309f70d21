"""Production mesh construction.

Single pod:  (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:   (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is pure
data parallelism over DCN.

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """A mesh whose axes are all Auto: the model code places tensors with
    ``with_sharding_constraint`` (sharding/context.py), which jax refuses on
    the Explicit axes ``jax.make_mesh`` makes by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under launch/dryrun.py (sets "
            "--xla_force_host_platform_device_count)")
    return _auto_mesh(shape, axes, devices[:n])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh for CPU tests (requires host-device override in conftest
    subprocess or few devices)."""
    n = 1
    for s in shape:
        n *= s
    return _auto_mesh(shape, axes, jax.devices()[:n])
