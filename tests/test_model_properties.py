"""Model-level property tests: causality, bidirectional encoders, loss
scaling."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get
from repro.models import model as lm

RNG = np.random.default_rng(11)


def tiny(name, **kw):
    import dataclasses
    cfg = get(name).reduced().replace(n_layers=2, d_model=64, n_heads=4,
                                      n_kv_heads=2, d_ff=96, vocab_size=128,
                                      head_dim=None, **kw)
    if cfg.ssm:
        cfg = cfg.replace(ssm=dataclasses.replace(cfg.ssm, d_state=8,
                                                  head_dim=8, chunk=16))
    if cfg.moe:
        # capacity drops are deliberately non-causal at train time (see
        # models/moe.py); ample capacity isolates the network's causality
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=16.0))
    return cfg


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mamba2-370m",
                                  "deepseek-v3-671b"])
@given(flip=st.integers(8, 15), seed=st.integers(0, 99))
@settings(max_examples=5, deadline=None)
def test_causality(name, flip, seed):
    """Changing token j must not change any logit at positions < j."""
    cfg = tiny(name)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)), jnp.int32)
    toks2 = toks.at[0, flip].set((toks[0, flip] + 1) % cfg.vocab_size)

    def logits(tk):
        h, _, _ = lm._family_fns(cfg)[1](
            params["stack"],
            params["embed"]["table"].astype(jnp.float32)[tk],
            cfg, positions=jnp.arange(16)[None], caches=None)
        return h

    l1, l2 = logits(toks), logits(toks2)
    np.testing.assert_allclose(np.asarray(l1[:, :flip]),
                               np.asarray(l2[:, :flip]), atol=1e-5)
    # and the flipped position itself must differ (no dead inputs)
    assert float(jnp.abs(l1[:, flip:] - l2[:, flip:]).max()) > 1e-6


def test_encoder_is_not_causal():
    cfg = tiny("hubert-xlarge")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    frames = jnp.asarray(RNG.standard_normal((1, 16, 64)) * 0.1, jnp.float32)
    f2 = frames.at[0, 12].add(1.0)
    from repro.models.transformer import decoder_apply
    h1, _, _ = decoder_apply(params["stack"], frames, cfg,
                             positions=jnp.arange(16)[None], causal=False)
    h2, _, _ = decoder_apply(params["stack"], f2, cfg,
                             positions=jnp.arange(16)[None], causal=False)
    # bidirectional: early positions DO see the late change
    assert float(jnp.abs(h1[:, :12] - h2[:, :12]).max()) > 1e-6


def test_loss_scales_with_random_vs_learnable_targets():
    """CE on targets == inputs-shifted (learnable) must be below CE on
    unrelated random targets after a few gradient steps."""
    from repro.optim import adamw
    cfg = tiny("qwen3-1.7b")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    oc = adamw.AdamWConfig(peak_lr=5e-3, warmup_steps=2, total_steps=40,
                           weight_decay=0.0)
    opt = adamw.init(params, oc)
    toks = jnp.asarray(RNG.integers(0, 64, (4, 17)), jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
             "loss_mask": jnp.ones((4, 16), jnp.float32)}

    @jax.jit
    def step(p, s):
        (l, _), g = jax.value_and_grad(
            lambda pp: lm.loss_fn(pp, batch, cfg), has_aux=True)(p)
        p2, s2, _ = adamw.apply(p, g, s, oc)
        return p2, s2, l

    losses = []
    for _ in range(30):
        params, opt, l = step(params, opt)
        losses.append(float(l))
    assert losses[-1] < losses[0] - 0.5   # memorizes the fixed batch
