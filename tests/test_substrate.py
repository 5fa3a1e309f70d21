"""Substrate tests: optimizer (incl. int8/factored moments), checkpointing
(atomic/async/elastic), data determinism, fault-tolerant train loop
(resume + preemption), serving loop."""
import json
import os
import signal
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs import SHAPES, get
from repro.data.pipeline import PipelineState, SyntheticLM
from repro.models import model as lm
from repro.optim import adamw
from repro.serve.loop import Request, Server
from repro.train.loop import LoopConfig, TrainLoop

RNG = np.random.default_rng(3)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def quad_params():
    return {"w": jnp.asarray(RNG.standard_normal((8, 16)), jnp.float32),
            "stack": {"k": jnp.asarray(RNG.standard_normal((4, 8, 16)),
                                       jnp.float32)}}


def quad_loss(p):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(p))


@pytest.mark.parametrize("moment_dtype,factored", [
    ("float32", False), ("bfloat16", False), ("int8", False),
    ("float32", True), ("int8", True),
])
def test_adamw_decreases_quadratic(moment_dtype, factored):
    c = adamw.AdamWConfig(peak_lr=0.05, warmup_steps=0, total_steps=100,
                          weight_decay=0.0, moment_dtype=moment_dtype,
                          factored_v=factored)
    p = quad_params()
    s = adamw.init(p, c)
    l0 = float(quad_loss(p))
    step = jax.jit(lambda p_, s_: adamw.apply(
        p_, jax.grad(quad_loss)(p_), s_, c))
    for _ in range(60):
        p, s, m = step(p, s)
    assert float(quad_loss(p)) < 0.5 * l0
    assert int(s["step"]) == 60


def test_adamw_int8_moments_close_to_fp32():
    cf = adamw.AdamWConfig(peak_lr=0.02, warmup_steps=0, weight_decay=0.0,
                           moment_dtype="float32")
    cq = adamw.AdamWConfig(peak_lr=0.02, warmup_steps=0, weight_decay=0.0,
                           moment_dtype="int8")
    p0 = quad_params()
    pf, sf = p0, adamw.init(p0, cf)
    pq, sq = p0, adamw.init(p0, cq)
    for _ in range(20):
        pf, sf, _ = adamw.apply(pf, jax.grad(quad_loss)(pf), sf, cf)
        pq, sq, _ = adamw.apply(pq, jax.grad(quad_loss)(pq), sq, cq)
    rel = abs(float(quad_loss(pq)) - float(quad_loss(pf))) / float(quad_loss(pf))
    assert rel < 0.15, rel


def test_adamw_grad_clipping_and_schedule():
    c = adamw.AdamWConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                          clip_norm=1.0)
    assert float(adamw.schedule(c, jnp.asarray(0))) == 0.0
    assert float(adamw.schedule(c, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(adamw.schedule(c, jnp.asarray(100))) == pytest.approx(
        c.peak_lr * c.end_lr_frac, rel=1e-3)
    p = {"w": jnp.ones((4,))}
    s = adamw.init(p, c)
    g = {"w": jnp.full((4,), 100.0)}      # huge grad, must be clipped
    p2, s2, m = adamw.apply(p, g, s, c)
    assert float(m["grad_norm"]) == pytest.approx(200.0)
    assert np.all(np.isfinite(np.asarray(p2["w"])))
    assert float(jnp.abs(p["w"] - p2["w"]).max()) < 1.0   # clip bounded step


def test_adamw_weight_decay_mask():
    c = adamw.AdamWConfig(peak_lr=0.0, warmup_steps=0, weight_decay=0.5)
    p = {"w": jnp.ones((4,)), "ln": {"scale": jnp.ones((4,))}}
    s = adamw.init(p, c)
    g = jax.tree.map(jnp.zeros_like, p)
    p2, _, _ = adamw.apply(p, g, s, c)
    # lr==0 => no update at all regardless of decay; now lr>0:
    c2 = adamw.AdamWConfig(peak_lr=0.1, warmup_steps=0, weight_decay=0.5)
    p3, _, _ = adamw.apply(p, g, adamw.init(p, c2), c2)
    assert float(jnp.abs(p3["w"] - 1).max()) > 0        # decayed
    assert float(jnp.abs(p3["ln"]["scale"] - 1).max()) == 0  # masked


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    state = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones((4,))}}
    for step in (1, 2, 3):
        mgr.save(step, state, meta={"pipeline": {"step": step}},
                 blocking=True)
    assert mgr.latest_step() == 3
    assert sorted(mgr.steps()) == [2, 3]       # gc kept last 2
    restored, meta = mgr.restore(state)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(state["a"]))
    assert meta["pipeline"]["step"] == 3


def test_checkpoint_atomicity_tmp_never_visible(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(7, {"x": jnp.ones((2,))}, blocking=True)
    assert not list(Path(tmp_path).glob("*.tmp"))
    assert mgr.latest_step() == 7


# ---------------------------------------------------------------------------
# data pipeline determinism
# ---------------------------------------------------------------------------


def test_pipeline_deterministic_and_sharded():
    cfg = get("qwen3-1.7b").reduced()
    pipe = SyntheticLM(cfg, SHAPES["train_4k"], seed=5, batch_override=8,
                       seq_override=32)
    b1 = pipe.batch(3)
    b2 = pipe.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = pipe.batch(4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # different shards differ; same chain statistics
    p0 = SyntheticLM(cfg, SHAPES["train_4k"], seed=5, shard=0, num_shards=2,
                     batch_override=8, seq_override=32)
    p1 = SyntheticLM(cfg, SHAPES["train_4k"], seed=5, shard=1, num_shards=2,
                     batch_override=8, seq_override=32)
    assert not np.array_equal(p0.batch(0)["tokens"], p1.batch(0)["tokens"])


# ---------------------------------------------------------------------------
# train loop: resume + preemption
# ---------------------------------------------------------------------------


def _tiny_setup(tmp_path, total_steps=6, ckpt_every=2):
    cfg = get("qwen3-1.7b").reduced().replace(n_layers=2, d_model=64,
                                              d_ff=128, vocab_size=128)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    oc = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=100)
    opt = adamw.init(params, oc)
    pipe = SyntheticLM(cfg, SHAPES["train_4k"], seed=1, batch_override=4,
                       seq_override=16)

    @jax.jit
    def step_fn(p, s, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        (loss, mets), g = jax.value_and_grad(
            lambda pp: lm.loss_fn(pp, batch, cfg), has_aux=True)(p)
        p2, s2, om = adamw.apply(p, g, s, oc)
        return p2, s2, dict(mets, **om)

    lc = LoopConfig(total_steps=total_steps, ckpt_every=ckpt_every,
                    log_every=1, out_dir=str(tmp_path / "run"))
    return cfg, params, opt, pipe, step_fn, lc


def test_train_loop_runs_and_checkpoints(tmp_path):
    cfg, params, opt, pipe, step_fn, lc = _tiny_setup(tmp_path)
    loop = TrainLoop(lc, step_fn, params, opt, pipe)
    out = loop.run()
    assert out["status"] == "done" and out["step"] == 6
    assert np.isfinite(out["loss"])
    assert loop.ckpt.latest_step() == 6
    lines = [json.loads(l) for l in
             (Path(lc.out_dir) / "metrics.jsonl").read_text().splitlines()]
    assert lines[-1]["step"] == 6


def test_train_loop_resume_matches_uninterrupted(tmp_path):
    # run A: 6 steps straight through
    cfg, params, opt, pipe, step_fn, lc = _tiny_setup(tmp_path / "a",
                                                      total_steps=6,
                                                      ckpt_every=3)
    outA = TrainLoop(lc, step_fn, params, opt, pipe).run()
    # run B: 3 steps, "crash", new loop resumes from ckpt to 6
    cfg, params, opt, pipe, step_fn, lcB = _tiny_setup(tmp_path / "b",
                                                       total_steps=3,
                                                       ckpt_every=3)
    TrainLoop(lcB, step_fn, params, opt, pipe).run()
    lcB2 = LoopConfig(total_steps=6, ckpt_every=3, log_every=1,
                      out_dir=lcB.out_dir)
    outB = TrainLoop(lcB2, step_fn, params, opt, pipe).run()
    assert outB["step"] == 6
    assert outA["loss"] == pytest.approx(outB["loss"], rel=1e-4)


def test_train_loop_preemption_checkpoints(tmp_path):
    cfg, params, opt, pipe, step_fn, lc = _tiny_setup(tmp_path,
                                                      total_steps=50,
                                                      ckpt_every=50)
    loop = TrainLoop(lc, step_fn, params, opt, pipe)

    orig = loop.step_fn
    calls = {"n": 0}

    def counting(p, s, b):
        calls["n"] += 1
        if calls["n"] == 3:
            os.kill(os.getpid(), signal.SIGTERM)   # preempt mid-run
        return orig(p, s, b)

    loop.step_fn = counting
    out = loop.run()
    assert out["status"] == "preempted"
    assert loop.ckpt.latest_step() == out["step"] >= 3


# ---------------------------------------------------------------------------
# serving loop
# ---------------------------------------------------------------------------


def test_server_batched_decode_drains_queue():
    cfg = get("qwen3-1.7b").reduced().replace(n_layers=2, d_model=64,
                                              d_ff=128, vocab_size=128)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    srv = Server(cfg, params, slots=2, cache_len=48)
    for uid in range(5):
        srv.submit(Request(uid=uid,
                           prompt=RNG.integers(0, 127, 8).astype(np.int32),
                           max_new=6))
    done = srv.run_until_drained()
    assert len(done) == 5
    for r in done:
        assert 1 <= len(r.out_tokens) <= 6
        assert all(0 <= t < cfg.vocab_padded for t in r.out_tokens)


def test_server_decode_matches_offline_decode():
    """A request served through slot batching must produce the same greedy
    tokens as a standalone prefill+decode chain."""
    cfg = get("qwen3-1.7b").reduced().replace(n_layers=2, d_model=64,
                                              d_ff=128, vocab_size=128)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    prompt = RNG.integers(0, 127, 8).astype(np.int32)
    # offline
    logits, caches = lm.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                cfg, cache_len=48)
    toks = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for i in range(4):
        lg, caches = lm.decode_step(params, jnp.asarray([[toks[-1]]]),
                                    jnp.asarray([pos + i]), caches, cfg)
        toks.append(int(jnp.argmax(lg[0])))
    # served
    srv = Server(cfg, params, slots=3, cache_len=48)
    srv.submit(Request(uid=0, prompt=prompt, max_new=5))
    done = srv.run_until_drained()
    assert done[0].out_tokens == toks
