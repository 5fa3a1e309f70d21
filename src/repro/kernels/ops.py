"""Dispatch between the Pallas kernels and their XLA twins.

The model stack's :class:`~repro.models.layers.Backend` consults these:

  * ``use_pallas=False`` — plain jnp ops (also what the dry-run lowers)
  * ``use_pallas=True``  — ``pallas_call``, compiled to Mosaic for the TPU

``interpret=True`` runs a Pallas kernel body through the interpreter
instead of compiling it.  Nothing here picks it from the device: callers
that rehearse on the CPU (tests, tiny smoke runs) ask for it explicitly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ame_gemm import ame_gemm
from repro.kernels.ssd_scan import ssd_chunked_jnp4, ssd_scan


def gemm(a: jnp.ndarray, b: jnp.ndarray, *, use_pallas: bool = False,
         interpret: bool = False, out_dtype=None, **blocks) -> jnp.ndarray:
    """C = A @ B via the reduction-free output-stationary kernel or XLA."""
    if use_pallas:
        return ame_gemm(a, b, out_dtype=out_dtype, interpret=interpret,
                        **blocks)
    return ref.gemm(a, b, out_dtype=out_dtype)


def ssd4(x, log_a, b, c, *, use_pallas: bool = False,
         interpret: bool = False, chunk: int = 128):
    """4-D SSD: x (B,H,T,P) — heads stay a shardable axis ('model')."""
    if use_pallas:
        bsz, h, t, p = x.shape
        y = ssd_scan(x.reshape(bsz * h, t, p),
                     log_a.reshape(bsz * h, t),
                     b.reshape(bsz * h, t, -1), c.reshape(bsz * h, t, -1),
                     chunk=chunk, interpret=interpret)
        return y.reshape(bsz, h, t, p)
    return ssd_chunked_jnp4(x, log_a, b, c, chunk=chunk)
