"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
shape + finiteness assertions, and prefill/decode parity checks."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, all_names, applicable, get, input_specs
from repro.models import model as lm
from repro.models.layers import XLA

RNG = np.random.default_rng(7)


def make_batch(cfg, shape, reduced=True):
    """Concrete arrays matching input_specs."""
    specs = input_specs(cfg, shape, reduced=reduced)
    out = {}
    for name, s in specs.items():
        if s.dtype == jnp.int32:
            hi = cfg.vocab_size if name in ("tokens", "targets") else s.shape[-1]
            out[name] = jnp.asarray(RNG.integers(0, hi, s.shape), jnp.int32)
        elif s.dtype == jnp.bool_:
            out[name] = jnp.asarray(RNG.random(s.shape) < 0.3)
        elif name == "loss_mask":
            out[name] = jnp.ones(s.shape, s.dtype)
        else:
            out[name] = jnp.asarray(RNG.standard_normal(s.shape) * 0.1, s.dtype)
    return out


@pytest.mark.parametrize("name", all_names())
def test_train_step_smoke(name):
    cfg = get(name).reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, SHAPES["train_4k"])
    loss, metrics = jax.jit(
        lambda p, b: lm.loss_fn(p, b, cfg))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{name}: loss={loss}"
    assert float(metrics["ce"]) > 0
    # one grad step must be finite too
    g = jax.jit(jax.grad(lambda p, b: lm.loss_fn(p, b, cfg)[0]))(params, batch)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in flat), name


@pytest.mark.parametrize("name", [n for n in all_names()
                                  if not get(n).encoder_only])
def test_prefill_decode_smoke(name):
    cfg = get(name).reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    b, t = 2, 32
    batch = make_batch(cfg, SHAPES["prefill_32k"])
    batch = {k: v[:, :t] if v.ndim > 1 else v for k, v in batch.items()}
    if "tokens" in batch:
        batch["tokens"] = batch["tokens"][:, :t]
    logits, caches = jax.jit(
        lambda p, bb: lm.prefill(p, bb, cfg, cache_len=t + 8))(params, batch)
    assert logits.shape[0] == 2 and np.isfinite(np.asarray(logits)).all()
    # a few decode steps
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos_base = t if cfg.modality == "text" else logits.shape[0]
    pos = jnp.full((2,), t, jnp.int32)
    step = jax.jit(lambda p, tk, ps, c: lm.decode_step(p, tk, ps, c, cfg))
    for i in range(3):
        logits, caches = step(params, tok, pos + i, caches)
        assert np.isfinite(np.asarray(logits)).all(), f"{name} step {i}"
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


def _decode_chain_logits(cfg, params, tokens, cache_len):
    """Prefill 1 token, then decode the rest one-by-one."""
    b, t = tokens.shape
    logits, caches = lm.prefill(params, {"tokens": tokens[:, :1]}, cfg,
                                cache_len=cache_len)
    outs = [logits]
    step = jax.jit(lambda p, tk, ps, c: lm.decode_step(p, tk, ps, c, cfg))
    for i in range(1, t):
        lg, caches = step(params, tokens[:, i:i + 1],
                          jnp.full((b,), i, jnp.int32), caches)
        outs.append(lg)
    return jnp.stack(outs, 1)  # (B, T, Vp)


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mamba2-370m", "gemma-2b",
                                  "mixtral-8x22b", "deepseek-v3-671b",
                                  "zamba2-2.7b"])
def test_decode_matches_full_forward(name):
    """Sequential decode must reproduce the full-sequence forward logits.

    MoE capacity is raised so no token is dropped — capacity drops are a
    *semantic* difference between a 16-token forward and 1-token decodes,
    not a parity bug (covered by test_moe_capacity_drops)."""
    import dataclasses
    cfg = get(name).reduced()
    if cfg.moe:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init(cfg, jax.random.PRNGKey(1))
    b, t = 2, 16
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, t)), jnp.int32)

    # full forward logits via prefill over the whole sequence
    full_logits, _ = lm.prefill(params, {"tokens": tokens}, cfg, cache_len=t)
    # prefill(1) + decode chain
    chain = _decode_chain_logits(cfg, params, tokens, cache_len=t)
    np.testing.assert_allclose(
        np.asarray(chain[:, -1]), np.asarray(full_logits),
        atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mixtral-8x22b",
                                  "deepseek-v3-671b"])
def test_ragged_slots_decode_matches_full_forward(name):
    """Rows at different positions in one decode batch, as ``Server`` runs
    them: each row is prefilled alone, spliced into its slot of the batch
    cache, then decoded together with the others.  Row 1 runs past the
    sliding window's wrap (mixtral: window 16).  Each row's last logits
    must match its own full forward — a write at the wrong [layer, row,
    slot] of the stacked cache shows here, not with equal positions."""
    import dataclasses
    from repro.serve.loop import _splice
    cfg = get(name).reduced()
    if cfg.moe:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init(cfg, jax.random.PRNGKey(4))
    prompt_lens, steps, cache_len = (1, 12, 6), 12, 24
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size,
                                      (len(prompt_lens), cache_len)),
                         jnp.int32)
    caches = lm.make_caches(cfg, len(prompt_lens), cache_len)
    for i, n in enumerate(prompt_lens):
        _, one = lm.prefill(params, {"tokens": tokens[i:i + 1, :n]}, cfg,
                            cache_len=cache_len)
        caches = jax.tree.map(lambda full, o: _splice(full, o, i, cfg),
                              caches, one)
    step = jax.jit(lambda p, tk, ps, c: lm.decode_step(p, tk, ps, c, cfg))
    pos = np.asarray(prompt_lens, np.int32)
    for _ in range(steps):
        logits, caches = step(params, tokens[np.arange(len(pos)), pos][:, None],
                              jnp.asarray(pos), caches)
        pos = pos + 1
    for i, n in enumerate(pos):
        full, _ = lm.prefill(params, {"tokens": tokens[i:i + 1, :n]}, cfg,
                             cache_len=int(n))
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(full[0]), atol=2e-2, rtol=2e-2)


def test_moe_capacity_drops():
    """With a tiny capacity factor, some tokens are dropped (output becomes
    the shared/residual path only) — outputs change but stay finite."""
    import dataclasses
    cfg = get("mixtral-8x22b").reduced()
    lo = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    hi = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init(hi, jax.random.PRNGKey(3))
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    l_lo, _ = lm.prefill(params, {"tokens": tokens}, lo, cache_len=16)
    l_hi, _ = lm.prefill(params, {"tokens": tokens}, hi, cache_len=16)
    assert np.isfinite(np.asarray(l_lo)).all()
    assert float(jnp.abs(l_lo - l_hi).max()) > 1e-4   # drops visibly differ


@pytest.mark.parametrize("name", ["mixtral-8x22b"])
def test_swa_rolling_cache_decode(name):
    """Rolling cache with window smaller than the sequence stays finite and
    matches the full forward (window masks identically)."""
    import dataclasses
    cfg = get(name).reduced()        # window 16
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init(cfg, jax.random.PRNGKey(2))
    b, t = 1, 24                      # longer than the 16-slot rolling cache
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    full_logits, _ = lm.prefill(params, {"tokens": tokens}, cfg, cache_len=t)
    chain = _decode_chain_logits(cfg, params, tokens, cache_len=t)
    np.testing.assert_allclose(np.asarray(chain[:, -1]),
                               np.asarray(full_logits), atol=2e-2, rtol=2e-2)


def test_hubert_masked_prediction_loss_only_on_mask():
    cfg = get("hubert-xlarge").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    b, t = 2, 32
    frames = jnp.asarray(RNG.standard_normal((b, t, cfg.d_model)) * 0.1,
                         jnp.float32)
    targets = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    m0 = jnp.zeros((b, t), bool).at[:, :4].set(True)
    l0, _ = lm.loss_fn(params, {"frames": frames, "mask": m0,
                                "targets": targets}, cfg)
    # flipping targets OUTSIDE the mask must not change the loss
    targets2 = targets.at[:, 10:].set((targets[:, 10:] + 1) % cfg.vocab_size)
    l1, _ = lm.loss_fn(params, {"frames": frames, "mask": m0,
                                "targets": targets2}, cfg)
    assert abs(float(l0) - float(l1)) < 1e-6


def test_param_counts_full_configs():
    """Full-config parameter counts estimated analytically (no allocation)
    land in the right ballpark for the headline sizes."""
    import repro.launch.params as pc
    approx = {
        "qwen3-1.7b": (1.4e9, 2.4e9),
        "command-r-35b": (30e9, 42e9),
        "gemma-2b": (2.0e9, 3.3e9),
        "phi4-mini-3.8b": (3.0e9, 4.8e9),
        "deepseek-v3-671b": (600e9, 720e9),
        "mixtral-8x22b": (120e9, 150e9),
        "internvl2-76b": (62e9, 80e9),
        "zamba2-2.7b": (2.2e9, 3.4e9),
        "mamba2-370m": (0.30e9, 0.50e9),
        "hubert-xlarge": (0.8e9, 1.3e9),
    }
    for name, (lo, hi) in approx.items():
        n = pc.count_params(get(name))
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]"
