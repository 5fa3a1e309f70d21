"""Compile the Pallas kernels for a described TPU v5e at real widths.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, compiles each kernel for one chip of a described ``v5e:2x2``
topology and must emit it as a Mosaic ``tpu_custom_call``.  This catches
what interpret mode cannot (block shapes that break the (8, 128) tiling
rule, VMEM overuse) at no chip time.  Widths are qwen3-1.7b's and
mamba2-370m's.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every pytest worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ame_gemm import ame_gemm
from repro.kernels.attention import flash_attention
from repro.kernels.elementwise import ame_elementwise
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, shardng, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=shardng) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("m,k,n,dtype", [
    (4, 2048, 6144, BF16),       # qwen3-1.7b decode MLP up/gate (4 slots)
    (512, 2048, 6144, BF16),     # qwen3-1.7b prefill MLP up/gate
    (4, 6144, 2048, BF16),       # qwen3-1.7b decode MLP down
    (4, 2048, 151936, BF16),     # qwen3-1.7b lm_head
    (20, 2048, 2048, F32),       # f32 operands, ragged rows
    (4, 2048, 1024, BF16),       # mamba2-370m out_proj
])
def test_ame_gemm_compiles_for_v5e(one_chip, m, k, n, dtype):
    fn = functools.partial(ame_gemm, out_dtype=dtype)
    hlo = _compile_text(fn, one_chip, ((m, k), dtype), ((k, n), dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bh,tq,tk", [
    (16, 1024, 1024),            # qwen3-1.7b prefill, 16 heads x hd 128
    (16, 1, 2048),               # qwen3-1.7b decode against a 2k cache
])
def test_flash_attention_compiles_for_v5e(one_chip, bh, tq, tk):
    hlo = _compile_text(flash_attention, one_chip,
                        ((bh, tq, 128), BF16), ((bh, tk, 128), BF16),
                        ((bh, tk, 128), BF16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kind", ["add", "mul"])
def test_ame_elementwise_compiles_for_v5e(one_chip, kind):
    fn = functools.partial(ame_elementwise, kind=kind, relu=kind == "add")
    hlo = _compile_text(fn, one_chip, ((1024, 4096), BF16),
                        ((1024, 4096), BF16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("t", [1024, 200])
def test_ssd_scan_compiles_for_v5e(one_chip, t):
    """mamba2-370m: 32 SSD heads of P=64, d_state N=128, chunk 128; the
    ragged T=200 exercises the padded tail chunk."""
    bh, p, n = 32, 64, 128
    hlo = _compile_text(ssd_scan, one_chip, ((bh, t, p), BF16),
                        ((bh, t), F32), ((bh, t, n), BF16),
                        ((bh, t, n), BF16))
    assert "tpu_custom_call" in hlo
