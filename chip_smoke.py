#!/usr/bin/env python3
"""Smoke run of the model stack on TPU v5e at qwen3-1.7b's full width.

  python3 chip_smoke.py              # one chip: serve, check, Pallas decode
  python3 chip_smoke.py --chips 4    # four chips: the sharded train step only

One chip, in one process:

  (a) the first device must be a TPU; nothing falls back to the CPU;
  (b) ``serve.loop.Server`` serves seeded requests at the published width
      (28 layers, d=2048, vocab 151936; random weights from ``--seed``);
  (c) each request's first token is the argmax of a separate ``lm.prefill``
      of its prompt, one decoded token scores within ``DECODE_TOL`` of the
      best token of a prefill reference, and the bf16 prefill logits agree
      with a float32 run under highest matmul precision (``COS_MIN``);
  (d) one ``decode_step`` through ``Backend("pallas")`` on the Server's
      caches matches the XLA backend, and its compiled text holds the
      ``ame_gemm`` kernel as a ``tpu_custom_call``;
  (e) the Server's own decode program holds the Pallas decode-attention
      kernel as a ``tpu_custom_call``, and the kernel's read of one layer
      of the Server's caches matches ``attention.decode_attention`` on
      that layer's slice.

``--chips 4`` runs ``launch.steps.make_train_step`` on a (data=2, model=2)
mesh of four chips with FSDP, compares step 0's loss with ``lm.loss_fn`` on
one chip and checks that the loss falls.

The lines before the last are smoke output, not benchmark metrics.  The last
line is ``{"ok": true, "device": {...}}``; a failed phase raises, so the
script exits non-zero and never prints that line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get  # noqa: E402
from repro.configs.base import ShapeSpec  # noqa: E402
from repro.kernels import decode_attention as decode_kernel  # noqa: E402
from repro.models import attention  # noqa: E402
from repro.models import model as lm  # noqa: E402
from repro.models.layers import PALLAS, Backend  # noqa: E402
from repro.serve.loop import Request, Server  # noqa: E402

ARCH = "qwen3-1.7b"
PROMPT_LENS = (128, 256, 512)   # _prefill_one compiles once per length
COS_MIN = 0.99          # cosine similarity of two logit vectors
DECODE_TOL = 0.1        # decoded token's logit >= best - DECODE_TOL * std
LOSS_RTOL = 2e-3        # sharded step-0 loss vs the one-chip loss


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, as JAX reports
    them through ``jax.monitoring`` (a cache hit still counts its load)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        s0, h0, m0 = self.seconds, self.hits, self.misses
        t0 = time.perf_counter()
        yield
        print(f"[smoke] phase {name}: wall {time.perf_counter() - t0:.2f}s, "
              f"compile {self.seconds - s0:.2f}s, persistent-cache hits "
              f"{self.hits - h0} misses {self.misses - m0}", flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> dict:
    """Phase (a): the script measures nothing anywhere but on the chip."""
    dev = device_info()
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (first device is "
                         f"{dev['platform']}: {dev['kind']}); refusing to "
                         f"run on the CPU")
    if dev["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX found {dev['count']}")
    return dev


def init_params(cfg, seed: int):
    return jax.jit(lambda k: lm.init(cfg, k))(jax.random.PRNGKey(seed))


def cosine(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


def valid_logits(cfg, logits):
    """Drop the padded vocab tail (its logits are -1e30 by construction)."""
    return np.asarray(logits, np.float32)[..., :cfg.vocab_size]


# -- (b) serve ----------------------------------------------------------------


def serve(cfg, params, *, n_requests: int = 8, slots: int = 4,
          cache_len: int = 2048, max_new: int = 32,
          prompt_lens=PROMPT_LENS, seed: int = 0):
    """Serve seeded requests through ``Server``; return it and its results."""
    rng = np.random.default_rng(seed)
    lens = rng.permutation([prompt_lens[i % len(prompt_lens)]
                            for i in range(n_requests)])
    srv = Server(cfg, params, slots=slots, cache_len=cache_len, wall=True)
    for uid, plen in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab_size, int(plen)).astype(np.int32)
        srv.submit(Request(uid=uid, prompt=prompt, max_new=max_new))
    t0 = time.perf_counter()
    done = srv.run_until_drained()
    wall = time.perf_counter() - t0
    check(len(done) == n_requests,
          f"served {len(done)} of {n_requests} requests")
    for req in done:
        check(len(req.out_tokens) == max_new,
              f"request {req.uid} got {len(req.out_tokens)} of {max_new} "
              f"tokens")
        check(all(0 <= t < cfg.vocab_size for t in req.out_tokens),
              f"request {req.uid} got a token outside the vocabulary")
    return srv, done, wall


# -- (c) correctness on the same device -----------------------------------------


def prefill_fn(cfg, cache_len: int):
    """The prefill program ``Server`` compiles, as a separate jit."""
    return jax.jit(lambda p, toks: lm.prefill(p, {"tokens": toks}, cfg,
                                              cache_len=cache_len))


def check_first_tokens(cfg, params, done, cache_len: int) -> int:
    """Each request's first token is the argmax of its prompt's prefill."""
    prefill = prefill_fn(cfg, cache_len)
    for req in done:
        logits, _ = prefill(params, jnp.asarray(req.prompt[None, :]))
        want = int(jnp.argmax(logits[0]))
        check(req.out_tokens[0] == want,
              f"request {req.uid}: first token {req.out_tokens[0]} but "
              f"prefill argmax is {want}")
    return len(done)


def check_decoded_token(cfg, params, req, cache_len: int) -> float:
    """The Server's second token (its first decode step, from the spliced
    slot cache) must score within ``DECODE_TOL`` standard deviations of the
    best logit of a prefill of prompt + first token.  Returns the gap."""
    toks = np.concatenate([req.prompt, req.out_tokens[:1]]).astype(np.int32)
    logits, _ = prefill_fn(cfg, cache_len)(params, jnp.asarray(toks[None]))
    ref = valid_logits(cfg, logits[0])
    gap = float((ref.max() - ref[req.out_tokens[1]]) / ref.std())
    check(gap <= DECODE_TOL,
          f"request {req.uid}: decoded token {req.out_tokens[1]} scores "
          f"{gap:.3f} std below the prefill reference's best "
          f"(tolerance {DECODE_TOL})")
    return gap


def check_precision(cfg, params, prompt, cache_len: int) -> dict:
    """bf16-compute prefill logits vs float32 compute at highest matmul
    precision, same params and prompt: cosine similarity >= ``COS_MIN``."""
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    low, _ = prefill_fn(cfg, cache_len)(params, toks)
    cfg32 = cfg.with_policy(compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        high, _ = prefill_fn(cfg32, cache_len)(params, toks)
    low, high = valid_logits(cfg, low), valid_logits(cfg, high)
    rep = {"cosine": cosine(low, high),
           "rel_l2": float(np.linalg.norm(low - high) / np.linalg.norm(high)),
           "max_abs": float(np.abs(low - high).max()),
           "argmax_equal": bool(low.argmax() == high.argmax())}
    check(rep["cosine"] >= COS_MIN,
          f"bf16 vs float32 prefill logits: cosine {rep['cosine']:.6f} < "
          f"{COS_MIN}")
    return rep


# -- (d) the Pallas backend on the Server's caches -----------------------------


def check_pallas_decode(cfg, params, caches, positions, *,
                        backend: Backend = PALLAS, seed: int = 0) -> dict:
    """One decode step through ``backend`` against the XLA backend on the
    same caches.  Unless the backend interprets its kernels, the compiled
    program must hold ``ame_gemm`` as a Mosaic ``tpu_custom_call``."""
    slots = positions.shape[0]
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, 1)), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)

    def step(be):
        return jax.jit(lambda p, t, ps, c: lm.decode_step(
            p, t, ps, c, cfg, backend=be)[0])

    compiled = step(backend).lower(params, toks, pos, caches).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if not backend.interpret:
        check(has_kernel, "the Pallas decode step holds no tpu_custom_call: "
                          "ame_gemm did not compile to a Mosaic kernel")
    got = valid_logits(cfg, compiled(params, toks, pos, caches))
    want = valid_logits(cfg, step(Backend("xla"))(params, toks, pos, caches))
    rep = {"cosine_min": min(cosine(g, w) for g, w in zip(got, want)),
           "max_abs": float(np.abs(got - want).max()),
           "argmax_equal": int((got.argmax(-1) == want.argmax(-1)).sum()),
           "slots": slots, "tpu_custom_call": has_kernel}
    check(rep["cosine_min"] >= COS_MIN,
          f"Pallas vs XLA decode logits: cosine {rep['cosine_min']:.6f} < "
          f"{COS_MIN}")
    return rep


def check_decode_kernel(cfg, srv, *, interpret: bool = False,
                        seed: int = 0) -> dict:
    """(e) Unless ``interpret``, the Server's decode program must hold the
    Pallas decode-attention kernel as a ``tpu_custom_call``.  The kernel's
    read of the last layer of the Server's caches, each row at its last
    written position, must match ``attention.decode_attention`` on that
    layer's slice (cosine ``COS_MIN`` per row).  Interpreting, on a
    configuration the kernel does not serve, it reads whole-cache blocks."""
    block = attention.decode_kv_block(cfg, srv.cache_len, "tpu")
    has_kernel = None
    if not interpret:
        check(block > 0, f"{cfg.name}: the decode-attention kernel does not "
                         f"serve this configuration on a TPU")
        toks = jnp.zeros((srv.slots, 1), jnp.int32)
        text = srv._decode.lower(srv.params, toks, jnp.asarray(srv.pos),
                                 srv.caches).compile().as_text()
        has_kernel = any('custom_call_target="tpu_custom_call"' in line
                         and "decode_attention" in line
                         for line in text.splitlines())
        check(has_kernel, "the Server's decode program holds no "
                          "decode_attention tpu_custom_call")
    cache = srv.caches["dense_stack"]
    layer = cache["k"].shape[0] - 1
    q_pos = jnp.asarray(np.maximum(srv.pos - 1, 0), jnp.int32)
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal(
        (srv.slots, cfg.n_heads, cfg.head_dim_)), jnp.bfloat16)
    got = decode_kernel.decode_attention(
        q, cache["k"], cache["v"], cache["pos"], layer, q_pos,
        block=block or min(decode_kernel.DEFAULT_BLOCK, srv.cache_len),
        interpret=interpret)
    want = jax.jit(lambda q, k, v, pos, qp: attention.decode_attention(
        q, k[layer], v[layer], q_pos=qp, kv_pos=pos[layer]))(
        q, cache["k"], cache["v"], cache["pos"], q_pos)
    got, want = np.asarray(got), np.asarray(want)
    rep = {"cosine_min": min(cosine(g, w) for g, w in zip(got, want)),
           "max_abs": float(np.abs(got - want).max()), "layer": layer,
           "block": block, "tpu_custom_call": has_kernel}
    check(rep["cosine_min"] >= COS_MIN,
          f"decode-attention kernel vs decode_attention: cosine "
          f"{rep['cosine_min']:.6f} < {COS_MIN}")
    return rep


def peak_bytes(dev=None):
    stats = (dev or jax.devices()[0]).memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def run_one_chip(seed: int, log: CompileLog) -> None:
    cfg = get(ARCH)
    cache_len = 2048
    with log.phase("init"):
        params = init_params(cfg, seed)
        jax.block_until_ready(params)
    print(f"[smoke] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {lm.param_count(params) / 1e9:.3f}B "
          f"params ({cfg.policy.param_dtype} params, "
          f"{cfg.policy.compute_dtype} compute)", flush=True)

    with log.phase("serve"):
        srv, done, wall = serve(cfg, params, cache_len=cache_len, seed=seed)
    ntok = sum(len(r.out_tokens) for r in done)
    print(f"[smoke] served {len(done)} requests, {ntok} tokens in "
          f"{wall:.2f}s wall (compiles included), prompt lengths "
          f"{sorted({len(r.prompt) for r in done})}", flush=True)

    with log.phase("check_first_tokens"):
        n = check_first_tokens(cfg, params, done, cache_len)
    print(f"[smoke] first tokens: {n}/{n} equal the prefill argmax",
          flush=True)
    with log.phase("check_decoded_token"):
        gap = check_decoded_token(cfg, params, done[0], cache_len)
    print(f"[smoke] decoded token vs prefill reference: {gap:.4f} std below "
          f"its best (tolerance {DECODE_TOL})", flush=True)
    prompt = next(r.prompt for r in done if len(r.prompt) == PROMPT_LENS[0])
    with log.phase("check_precision"):
        prec = check_precision(cfg, params, prompt, cache_len)
    print(f"[smoke] bf16 vs float32 prefill logits ({len(prompt)}-token "
          f"prompt): cosine {prec['cosine']:.6f} (min {COS_MIN}), rel L2 "
          f"{prec['rel_l2']:.4e}, max abs {prec['max_abs']:.4e}, argmax "
          f"equal {prec['argmax_equal']}", flush=True)

    with log.phase("pallas_decode"):
        pal = check_pallas_decode(cfg, params, srv.caches, srv.pos,
                                  seed=seed)
    print(f"[smoke] Pallas vs XLA decode logits ({pal['slots']} slots): "
          f"min cosine {pal['cosine_min']:.6f} (min {COS_MIN}), max abs "
          f"{pal['max_abs']:.4e}, argmax equal {pal['argmax_equal']}/"
          f"{pal['slots']}, tpu_custom_call {pal['tpu_custom_call']}",
          flush=True)
    with log.phase("decode_kernel"):
        dk = check_decode_kernel(cfg, srv, seed=seed)
    print(f"[smoke] decode-attention kernel vs decode_attention (layer "
          f"{dk['layer']}, block {dk['block']}): min cosine "
          f"{dk['cosine_min']:.6f} (min {COS_MIN}), max abs "
          f"{dk['max_abs']:.4e}, in the Server's decode program "
          f"{dk['tpu_custom_call']}", flush=True)
    print(f"[smoke] peak_bytes_in_use {peak_bytes()}", flush=True)


# -- --chips 4: the sharded train step -----------------------------------------


def train_steps(cfg, *, steps: int = 4, batch: int = 8, seq: int = 512,
                seed: int = 0) -> dict:
    """``make_train_step`` on a (data, model) mesh of the first devices,
    with FSDP so parameters and AdamW state are split over all of them.
    Step 0's loss is compared with ``lm.loss_fn`` on one device for the same
    parameters and batch; the loss must fall over the steps (one batch,
    seen every step)."""
    from repro.data.pipeline import SyntheticLM
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_debug_mesh
    from repro.optim import adamw
    from repro.sharding import rules

    cfg = cfg.with_policy(fsdp=True)
    mesh = make_debug_mesh((2, 2), ("data", "model"))
    shape = ShapeSpec("smoke", seq_len=seq, global_batch=batch, kind="train")
    oc = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=100,
                           weight_decay=0.0)
    fn, _, (pspec, ospec, bspec) = steps_mod.make_train_step(
        cfg, mesh, shape, opt_cfg=oc)
    data = {k: jnp.asarray(v)
            for k, v in SyntheticLM(cfg, shape, seed=seed).batch(0).items()}

    one = jax.devices()[0]
    params = init_params(cfg, seed)
    ref = float(jax.jit(lambda p, b: lm.loss_fn(p, b, cfg)[0])(params, data))
    peak_one = peak_bytes(one)

    params = jax.device_put(params, rules.to_named(pspec, mesh))
    opt = jax.jit(lambda p: adamw.init(p, oc),
                  out_shardings=rules.to_named(ospec, mesh))(params)
    data = jax.device_put(data, rules.to_named(bspec, mesh))
    losses = []
    for _ in range(steps):
        params, opt, mets = fn(params, opt, data)
        losses.append(float(mets["loss_out"]))
    rep = {"reference": ref, "losses": losses,
           "rel_err": abs(losses[0] - ref) / abs(ref),
           "mesh": dict(mesh.shape), "peak_bytes_reference": peak_one,
           "peak_bytes": [peak_bytes(d) for d in mesh.devices.flat]}
    check(rep["rel_err"] <= LOSS_RTOL,
          f"sharded step-0 loss {losses[0]:.6f} vs one-device "
          f"{ref:.6f}: relative error {rep['rel_err']:.2e} > {LOSS_RTOL}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return rep


def run_four_chips(seed: int, log: CompileLog) -> None:
    with log.phase("train_4chip"):
        rep = train_steps(get(ARCH), seed=seed)
    print(f"[smoke] {ARCH} train step on mesh {rep['mesh']} (FSDP): step-0 "
          f"loss {rep['losses'][0]:.6f} vs one-device {rep['reference']:.6f} "
          f"(rel err {rep['rel_err']:.2e}, max {LOSS_RTOL}); losses "
          f"{[round(x, 6) for x in rep['losses']]}", flush=True)
    print(f"[smoke] peak_bytes_in_use per chip {rep['peak_bytes']} (device "
          f"0 held {rep['peak_bytes_reference']} after the one-device "
          f"reference)", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded train step on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"[smoke] device {dev['kind']} x{dev['count']} "
          f"({dev['platform']}); compile cache {cache_dir}", flush=True)
    log = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed, log)
    else:
        run_one_chip(args.seed, log)
    entries = sum(1 for _ in Path(cache_dir).iterdir()) \
        if Path(cache_dir).is_dir() else 0
    print(f"[smoke] total wall {time.perf_counter() - t0:.2f}s, compile "
          f"{log.seconds:.2f}s, persistent-cache hits {log.hits} misses "
          f"{log.misses}, {entries} entries in {cache_dir}", flush=True)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
