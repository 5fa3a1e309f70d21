"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.ame_gemm import ame_gemm, vmem_bytes
from repro.kernels.attention import flash_attention
from repro.kernels.elementwise import ame_elementwise
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=0.06, rtol=0.06),
       jnp.float16: dict(atol=0.02, rtol=0.02)}


def allclose(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# ame_gemm — shape x dtype x block sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [
    (32, 32, 32), (128, 64, 128), (100, 130, 70), (1, 256, 64),
    (257, 33, 129), (8, 8, 8),
])
def test_ame_gemm_vs_oracle(m, k, n, dtype):
    a, b = randn(m, k, dtype=dtype, scale=0.3), randn(k, n, dtype=dtype, scale=0.3)
    got = ame_gemm(a, b, block_m=32, block_n=32, block_k=32, interpret=True)
    allclose(got, ref.gemm(a, b), dtype)


@pytest.mark.parametrize("bm,bn,bk", [(16, 16, 16), (64, 32, 128), (128, 128, 64)])
def test_ame_gemm_block_sweep(bm, bn, bk):
    a, b = randn(96, 160, scale=0.3), randn(160, 96, scale=0.3)
    got = ame_gemm(a, b, block_m=bm, block_n=bn, block_k=bk, interpret=True)
    allclose(got, ref.gemm(a, b), jnp.float32)


def test_ame_gemm_vmem_claim_fits():
    # default blocks must fit a v5e VMEM (~16 MiB per core) with headroom
    assert vmem_bytes() < 8 * 1024 * 1024


def test_ame_gemm_out_dtype():
    a, b = randn(64, 64, dtype=jnp.bfloat16), randn(64, 64, dtype=jnp.bfloat16)
    got = ame_gemm(a, b, block_m=32, block_n=32, block_k=32,
                   out_dtype=jnp.float32, interpret=True)
    assert got.dtype == jnp.float32


# ---------------------------------------------------------------------------
# elementwise — the fused PEP analogue
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["add", "sub", "mul"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("m,c", [(128, 2048), (57, 129), (1, 8)])
def test_elementwise_vs_oracle(kind, dtype, m, c):
    a, b = randn(m, c, dtype=dtype), randn(m, c, dtype=dtype)
    got = ame_elementwise(a, b, kind=kind, block_m=64, block_c=128,
                          interpret=True)
    allclose(got, ref.elementwise(kind, a, b), dtype)


def test_elementwise_fused_relu():
    a, b = randn(64, 64), randn(64, 64)
    got = ame_elementwise(a, b, kind="add", relu=True, block_m=32,
                          block_c=32, interpret=True)
    allclose(got, ref.elementwise("add", a, b, relu=True), jnp.float32)


# ---------------------------------------------------------------------------
# ssd_scan — chunked vs sequential recurrence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,t,p,n,chunk", [
    (2, 64, 16, 8, 16), (1, 100, 32, 16, 32), (3, 33, 8, 4, 16),
    (1, 16, 8, 8, 16),
])
def test_ssd_scan_vs_recurrence(bh, t, p, n, chunk, dtype):
    x = randn(bh, t, p, dtype=dtype, scale=0.5)
    log_a = -jnp.abs(randn(bh, t, dtype=jnp.float32, scale=0.2))
    b = randn(bh, t, n, dtype=dtype, scale=0.5)
    c = randn(bh, t, n, dtype=dtype, scale=0.5)
    got = ssd_scan(x, log_a, b, c, chunk=chunk, interpret=True)
    want = jax.vmap(ref.ssd_scan)(x, log_a, b, c)
    tol = dict(atol=1e-4, rtol=1e-3) if dtype == jnp.float32 else \
        dict(atol=0.08, rtol=0.08)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_ssd_state_carries_across_chunks():
    """A long-decay sequence: late outputs must see early inputs."""
    bh, t, p, n = 1, 64, 4, 4
    x = jnp.zeros((bh, t, p)).at[0, 0].set(1.0)      # impulse at t=0
    log_a = jnp.full((bh, t), -0.01)                  # slow decay
    b = jnp.ones((bh, t, n))
    c = jnp.ones((bh, t, n))
    got = ssd_scan(x, log_a, b, c, chunk=16, interpret=True)
    assert float(jnp.abs(got[0, -1]).max()) > 0.1     # impulse visible at end


# ---------------------------------------------------------------------------
# flash attention — causal, windowed, decode-aligned
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,tq,tk,d,causal,window", [
    (2, 64, 64, 32, True, 0),
    (1, 128, 128, 64, True, 0),
    (1, 100, 100, 32, True, 0),       # ragged seq vs block
    (2, 64, 64, 32, False, 0),
    (1, 128, 128, 32, True, 48),      # sliding window
    (1, 16, 128, 32, True, 0),        # chunked decode: q tail-aligned
])
def test_flash_attention_vs_oracle(bh, tq, tk, d, causal, window, dtype):
    q = randn(bh, tq, d, dtype=dtype, scale=0.5)
    k = randn(bh, tk, d, dtype=dtype, scale=0.5)
    v = randn(bh, tk, d, dtype=dtype, scale=0.5)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=32, interpret=True)
    want = jax.vmap(lambda q_, k_, v_: ref.attention(
        q_, k_, v_, causal=causal, window=window))(q, k, v)
    allclose(got, want, dtype)


def test_flash_attention_block_sweep():
    q = randn(1, 96, 32, scale=0.5)
    k = randn(1, 96, 32, scale=0.5)
    v = randn(1, 96, 32, scale=0.5)
    want = jax.vmap(ref.attention)(q, k, v)
    for bq, bk in [(16, 16), (32, 96), (96, 32)]:
        got = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        allclose(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# chunked-jnp SSD (the XLA-lowered production path) vs sequential oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bh,t,p,n,chunk", [
    (2, 64, 16, 8, 16), (1, 100, 32, 16, 32), (3, 33, 8, 4, 16),
])
def test_ssd_chunked_jnp_vs_recurrence(bh, t, p, n, chunk):
    from repro.kernels.ssd_scan import ssd_chunked_jnp
    x = randn(bh, t, p, scale=0.5)
    log_a = -jnp.abs(randn(bh, t, dtype=jnp.float32, scale=0.2))
    b = randn(bh, t, n, scale=0.5)
    c = randn(bh, t, n, scale=0.5)
    got = ssd_chunked_jnp(x, log_a, b, c, chunk=chunk)
    want = jax.vmap(ref.ssd_scan)(x, log_a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-3)


def test_ssd4_vs_recurrence():
    from repro.kernels import ops
    b_, h_, t_, p_, n_ = 2, 3, 64, 8, 4
    x = randn(b_, h_, t_, p_, scale=0.5)
    log_a = -jnp.abs(randn(b_, h_, t_, dtype=jnp.float32, scale=0.2))
    bb = randn(b_, h_, t_, n_, scale=0.5)
    cc = randn(b_, h_, t_, n_, scale=0.5)
    got = ops.ssd4(x, log_a, bb, cc, chunk=16)
    want = jax.vmap(jax.vmap(ref.ssd_scan))(x, log_a, bb, cc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# decode attention over the layer-stacked cache, read in place
# ---------------------------------------------------------------------------


def _stacked_cache(layers, rows, slots, hkv, d, q_pos, rng):
    """bf16 K/V (L,B,S,Hkv,D) and positions (L,B,S), slot == position up to
    each row's q_pos, -1 past it; a hole (-1) in every longer row.  Returns
    the clean cache and one with garbage wherever the mask drops a slot:
    pos -1, or a valid-looking position past q_pos."""
    shape = (layers, rows, slots, hkv, d)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    slot = np.arange(slots)
    pos = np.where(slot[None, :] <= q_pos[:, None], slot[None, :], -1)
    pos[q_pos > 4, 3] = -1                              # unwritten hole
    past = rng.integers(1, 5, rows)                     # past q_pos, valid-
    for b, n in enumerate(past):                        # looking positions
        end = min(q_pos[b] + 1 + n, slots)
        pos[b, q_pos[b] + 1:end] = slot[q_pos[b] + 1:end]
    pos = np.broadcast_to(pos, (layers, rows, slots)).astype(np.int32)
    drop = jnp.asarray((pos < 0) | (pos > q_pos[None, :, None]))[..., None,
                                                                  None]
    junk = jnp.asarray(rng.standard_normal(shape) * 1e4, jnp.bfloat16)
    return (k, v, jnp.where(drop, junk, k), jnp.where(drop, -junk, v),
            jnp.asarray(pos))


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("hkv,d,g", [
    (8, 128, 1),        # MHA, head_dim 128
    (8, 128, 2),        # qwen3-1.7b: GQA 16 q / 8 kv heads
    (1, 256, 8),        # gemma-2b: MQA, head_dim 256
])
def test_decode_attention_kernel_vs_oracle(hkv, d, g, layer):
    """The Pallas decode read of one layer of the stacked cache against
    ``models.attention.decode_attention`` on that layer's slice.  One batch
    mixes q_pos 0, block - 1, block, block + 1 and S - 1 with random
    lengths; garbage where the mask drops a slot must not move the output."""
    from repro.kernels.decode_attention import decode_attention
    from repro.models.attention import decode_attention as oracle
    layers, slots, block = 2, 512, 128
    rng = np.random.default_rng(hkv * 1000 + d + g)
    q_pos = np.array([0, block - 1, block, block + 1, slots - 1,
                      *rng.integers(0, slots, 3)], np.int32)
    k, v, k_junk, v_junk, pos = _stacked_cache(layers, len(q_pos), slots,
                                               hkv, d, q_pos, rng)
    q = randn(len(q_pos), hkv * g, d, dtype=jnp.bfloat16)
    li = 0 if layer == "first" else layers - 1
    qp = jnp.asarray(q_pos)
    got = decode_attention(q, k_junk, v_junk, pos, li, qp, block=block,
                           interpret=True)
    want = oracle(q, k[li], v[li], q_pos=qp, kv_pos=pos[li])
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2, rtol=1e-2)


def _decode_cfg(**kw):
    from repro.configs import get
    return get("qwen3-1.7b").reduced().replace(
        n_heads=16, n_kv_heads=8, head_dim=128, n_layers=1, **kw)


@pytest.mark.parametrize("case,block", [
    ("eligible", 256),
    ("cpu", 0),
    ("window", 0),
    ("mla", 0),
    ("head_dim_80", 0),
    ("kv_heads_2", 0),
    ("model_axis_2", 0),
    ("data_axis_2", 0),
    ("ragged_cache", 0),
])
def test_decode_kv_block_rule(case, block):
    """The one rule for the Pallas decode read: TPU only, and only where
    the input allows it; everything else keeps the XLA read."""
    from repro.configs import get
    from repro.models.attention import decode_kv_block
    cfg, platform, mesh, cache_len = _decode_cfg(), "tpu", None, 2048
    if case == "cpu":
        platform = "cpu"
    elif case == "window":
        cfg = cfg.replace(sliding_window=1024)
    elif case == "mla":
        cfg = get("deepseek-v3-671b")
    elif case == "head_dim_80":
        cfg = cfg.replace(head_dim=80)
    elif case == "kv_heads_2":
        cfg = cfg.replace(n_kv_heads=2)
    elif case == "model_axis_2":
        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    elif case == "data_axis_2":
        mesh = jax.sharding.AbstractMesh((2, 1), ("data", "model"))
    elif case == "ragged_cache":
        cache_len = 1000
    assert decode_kv_block(cfg, cache_len, platform, mesh) == block
    one = jax.sharding.AbstractMesh((1, 1), ("data", "model"))
    assert decode_kv_block(_decode_cfg(), 2048, "tpu", one) == 256
    assert decode_kv_block(_decode_cfg(), 128, "tpu") == 128


@pytest.mark.parametrize("arch", ["gemma-2b", "phi4-mini-3.8b",
                                  "command-r-35b", "internvl2-76b",
                                  "qwen3-1.7b"])
def test_decode_kv_block_engages_for_full_attention_configs(arch):
    from repro.configs import get
    from repro.models.attention import decode_kv_block
    assert decode_kv_block(get(arch), 2048, "tpu") == 256


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "deepseek-v3-671b",
                                  "zamba2-2.7b", "mamba2-370m"])
def test_decode_kv_block_keeps_xla_for_other_configs(arch):
    from repro.configs import get
    from repro.models.attention import decode_kv_block
    assert decode_kv_block(get(arch), 2048, "tpu") == 0


def test_server_counts_what_the_decode_read_scans():
    """``Server._kv_positions``: with the rule engaged, each slot's pos + 1
    rounded up to the block, live or not; otherwise every position."""
    from repro.serve.loop import Server
    from repro.models import model as lm
    cfg = _decode_cfg()
    srv = Server(cfg, lm.init(cfg, jax.random.key(0)), slots=3,
                 cache_len=512)
    srv.pos[:] = [0, 255, 300]
    live = [1, 2]
    assert srv._kv_positions(live, "tpu") == (256 + 301, 256 + 256 + 512)
    assert srv._kv_positions(live, "cpu") == (256 + 301, 3 * 512)
    narrow = cfg.replace(head_dim=64)
    wide = Server(narrow, lm.init(narrow, jax.random.key(0)), slots=3,
                  cache_len=512)
    wide.pos[:] = srv.pos
    assert wide._kv_positions(live, "tpu") == (256 + 301, 3 * 512)


def test_free_slots_decode_at_position_zero():
    """A free slot decodes at position 0, so the kernel reads one block of
    it however far its last request ran."""
    from repro.serve.loop import Server
    from repro.models import model as lm
    cfg = _decode_cfg()
    srv = Server(cfg, lm.init(cfg, jax.random.key(0)), slots=3,
                 cache_len=512)
    srv.pos[:] = [400, 255, 300]
    live = [1, 2]
    assert list(srv._decode_positions(live)) == [0, 255, 300]
    assert list(srv.pos) == [400, 255, 300]
    assert srv._kv_positions(live, "tpu") == (256 + 301, 256 + 256 + 512)


@pytest.mark.parametrize("q_pos,want", [(-1, 1), (0, 1), (127, 1), (128, 2),
                                        (129, 2), (511, 4), (900, 4)])
def test_blocks_read_host_and_traced_agree(q_pos, want):
    from repro.kernels.decode_attention import blocks_read
    host = blocks_read(np.array([q_pos]), 128, 512)
    traced = jax.jit(lambda p: blocks_read(p, 128, 512))(jnp.array([q_pos]))
    assert isinstance(host, np.ndarray) and int(host[0]) == want
    assert int(traced[0]) == want
