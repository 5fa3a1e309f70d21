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
import math
import re

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


# --- the full-width serving decode step: its KV cache stays in place -------

_HLO_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")


def _materialized_ops(hlo: str):
    """(name, dims, op) of each instruction outside fused computations, a
    fusion named by what its root does (through bitcasts)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _HLO_COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), {"ins": {}, "root": None})
            continue
        m = _HLO_INSTR.match(line) if cur is not None else None
        if m:
            name, dims, op, operands = m.groups()
            calls = re.search(r"calls=%([\w.-]+)", line)
            cur["ins"][name] = (dims, op, operands.split(", ")[0].lstrip("%"),
                                calls.group(1) if calls else None)
            if line.lstrip().startswith("ROOT"):
                cur["root"] = name
    fused = set(re.findall(r" fusion\(.*calls=%([\w.-]+)", hlo))
    for cname, comp in comps.items():
        if cname in fused:
            continue
        for name, (dims, op, _, calls) in comp["ins"].items():
            while op == "fusion" and calls in comps:
                inner = comps[calls]
                r = inner["root"]
                while r in inner["ins"] and inner["ins"][r][1] == "bitcast":
                    r = inner["ins"][r][2]
                if r not in inner["ins"]:
                    break
                op, calls = inner["ins"][r][1], inner["ins"][r][3]
            yield name, tuple(int(d) for d in dims.split(",") if d), op


def test_server_decode_updates_kv_cache_in_place_for_v5e(one_chip):
    """Full-width qwen3-1.7b decode as ``Server`` jits it (bfloat16 weights,
    24 slots x 2048, caches donated).  The step writes each layer's new
    entries into the stacked cache and the Pallas decode kernel reads the
    stacked cache in place, once per layer-scan body: no temporary as
    large as one layer's K slice, and no copy, transpose, dynamic-slice or
    dynamic-update-slice of the whole cache or of a layer's worth of it."""
    import dataclasses
    from repro.configs.base import get
    from repro.models import model as lm
    cfg = get("qwen3-1.7b")
    cfg = cfg.replace(policy=dataclasses.replace(cfg.policy,
                                                 param_dtype="bfloat16"))
    slots, cache_len = 24, 2048

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0))))
    caches = on_chip(jax.eval_shape(
        lambda: lm.make_caches(cfg, slots, cache_len)))
    toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)

    def server_decode(p, t, ps, c):
        return lm.decode_step(p, t, ps, c, cfg)

    compiled = jax.jit(server_decode, donate_argnums=(3,)).lower(
        params, toks, pos, caches).compile()
    k_layer = caches["dense_stack"]["k"]
    layer_elems = k_layer.size // k_layer.shape[0]          # 24x2048x8x128
    layer_bytes = layer_elems * k_layer.dtype.itemsize      # 100663296
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    hlo = compiled.as_text()
    copies = [(name, dims, op) for name, dims, op in _materialized_ops(hlo)
              if op in ("copy", "transpose", "dynamic-slice",
                        "dynamic-update-slice")
              and math.prod(dims) in (layer_elems, k_layer.size)]
    assert not copies, copies
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1, kernels
    assert "decode_attention" in kernels[0]
    assert "/stack/while/body/" in kernels[0]


@pytest.mark.parametrize("layers,rows,hkv,d,g", [
    (28, 24, 8, 128, 2),         # qwen3-1.7b, 24 slots x 2048
    (18, 8, 1, 256, 8),          # gemma-2b: MQA, head_dim 256
])
def test_decode_attention_kernel_compiles_for_v5e(one_chip, layers, rows,
                                                  hkv, d, g):
    from repro.kernels.decode_attention import decode_attention
    slots = 2048
    hlo = _compile_text(
        decode_attention, one_chip, ((rows, hkv * g, d), BF16),
        ((layers, rows, slots, hkv, d), BF16),
        ((layers, rows, slots, hkv, d), BF16),
        ((layers, rows, slots), jnp.int32), ((), jnp.int32),
        ((rows,), jnp.int32))
    assert "tpu_custom_call" in hlo
