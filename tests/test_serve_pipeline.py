"""The serving loop runs one decode ahead of the host.

``Server.step`` samples the next tokens inside the decode program, keeps them
on the device as the next decode's input, and dispatches decode n before it
reads decode n-1's tokens back.  These tests hold the served tokens to the
synchronous order written out below (prefill, splice, decode, read the
logits, argmax on the host, retire), and check what the order changes: a
request's first token reaches the host in the step that admitted it, a row
that ends by EOS or a fault has its token in flight dropped, and the two
counters ``serve.decode_ahead_steps`` and ``serve.discarded_tokens``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import model as lm
from repro.obs import MetricsRegistry
from repro.serve.loop import Request, Server, _splice

SLOTS, CACHE_LEN = 3, 48
# more requests than slots; staggered prompts and answers, so admissions
# land while a decode is in flight.  (40, 20) runs into the cache's last
# position, (3, 1) asks for a single token.
SHAPES = ((5, 6), (9, 2), (3, 9), (12, 1), (7, 4), (40, 20), (4, 7), (10, 3))


def tiny():
    return get("qwen3-1.7b").reduced().replace(n_layers=2, d_model=64,
                                               d_ff=128, vocab_size=128)


CONFIGS = {"dense": tiny, "ssm": lambda: get("mamba2-370m").reduced(),
           "moe": lambda: get("mixtral-8x22b").reduced()}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg = CONFIGS[request.param]()
    return cfg, lm.init(cfg, jax.random.key(1))


@pytest.fixture(scope="module")
def dense():
    cfg = tiny()
    return cfg, lm.init(cfg, jax.random.key(1))


def prompts(cfg):
    rng = np.random.default_rng(3)
    return [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), new)
            for n, new in SHAPES]


def serve_in_order(cfg, params, reqs, eos_id=None):
    """The synchronous order: each step admits into free slots (prefill,
    splice, the prefill's argmax), decodes every slot (token 0 at position 0
    in a free one), waits for the logits, takes their argmax on the host and
    retires at ``max_new``, EOS or the cache's last position."""
    prefill = jax.jit(lambda p, t: lm.prefill(p, {"tokens": t}, cfg,
                                              cache_len=CACHE_LEN))
    decode = jax.jit(lambda p, t, ps, c: lm.decode_step(p, t, ps, c, cfg))
    caches = lm.make_caches(cfg, SLOTS, CACHE_LEN)
    queue = list(enumerate(reqs))
    active, pos, out = [None] * SLOTS, np.zeros(SLOTS, np.int32), {}
    while queue or any(a is not None for a in active):
        for i in range(SLOTS):
            if active[i] is None and queue:
                uid, (prompt, max_new) = queue.pop(0)
                logits, fresh = prefill(params, jnp.asarray(prompt[None]))
                caches = jax.tree.map(lambda f, o: _splice(f, o, i, cfg),
                                      caches, fresh)
                out[uid] = [int(np.argmax(np.asarray(logits[0])))]
                active[i], pos[i] = (uid, max_new), len(prompt)
        live = [i for i in range(SLOTS) if active[i] is not None]
        toks = np.zeros((SLOTS, 1), np.int32)
        ps = np.zeros(SLOTS, np.int32)
        for i in live:
            toks[i, 0], ps[i] = out[active[i][0]][-1], pos[i]
        logits, caches = decode(params, jnp.asarray(toks), jnp.asarray(ps),
                                caches)
        nxt = np.argmax(np.asarray(logits), -1)
        for i in live:
            uid, max_new = active[i]
            out[uid].append(int(nxt[i]))
            pos[i] += 1
            if (len(out[uid]) >= max_new or int(nxt[i]) == eos_id
                    or pos[i] >= CACHE_LEN - 1):
                active[i] = None
    return out


def serve(cfg, params, reqs, **kw):
    """Tokens by uid, and the counters, of ``reqs`` served by ``Server``."""
    reg = MetricsRegistry()
    srv = Server(cfg, params, slots=SLOTS, cache_len=CACHE_LEN,
                 metrics=reg, **kw)
    for uid, (prompt, max_new) in enumerate(reqs):
        srv.submit(Request(uid=uid, prompt=prompt, max_new=max_new))
    srv.run_until_drained()
    counters = {k: v["value"] for k, v in reg.snapshot().items()
                if v["type"] == "counter"}
    return srv, {r.uid: r.out_tokens for r in srv.completed}, counters


def test_tokens_equal_the_synchronous_order(model):
    cfg, params = model
    reqs = prompts(cfg)
    srv, got, counters = serve(cfg, params, reqs)
    assert got == serve_in_order(cfg, params, reqs)
    assert len(got[5]) < 20          # stopped at the cache's last position
    assert len(got[3]) == 2          # max_new 1: the prefill's and a decode's
    # every decode but the first was dispatched with one in flight
    assert counters["serve.decode_ahead_steps"] \
        == counters["serve.decode_steps"] - 1 > 0
    assert counters.get("serve.discarded_tokens", 0) == 0
    assert counters["serve.tokens"] == sum(map(len, got.values()))
    assert srv._in_flight is None and srv.undrained == 0


def test_first_token_reaches_the_host_in_its_admission_step(dense):
    """The step that prefills a request returns with its first token, and
    each later step with at most one more, so a caller that watches
    ``out_tokens`` sees a request's prefill in the step that ran it."""
    cfg, params = dense
    srv = Server(cfg, params, slots=SLOTS, cache_len=CACHE_LEN)
    reqs = [Request(uid=u, prompt=p, max_new=n)
            for u, (p, n) in enumerate(prompts(cfg))]
    for r in reqs:
        srv.submit(r)
    seen = {r.uid: 0 for r in reqs}
    while not all(r.done for r in reqs):
        queued = {r.uid for r in srv.queue}
        srv.step()
        for r in reqs:
            grew = len(r.out_tokens) - seen[r.uid]
            if r.uid in queued and r.uid not in {q.uid for q in srv.queue}:
                assert grew == 1, r.uid          # admitted in this step
            else:
                assert grew in (0, 1), r.uid
            seen[r.uid] = len(r.out_tokens)


def test_eos_ends_a_request_and_drops_its_token_in_flight(dense):
    cfg, params = dense
    reqs = prompts(cfg)
    _, plain, _ = serve(cfg, params, reqs)
    # a token one request decodes mid-answer and no other request decodes
    # (the first token is not held to EOS)
    uid, k = next((u, k) for u, toks in sorted(plain.items())
                  for k in range(1, len(toks) - 1)
                  if toks[k] not in toks[1:k] and all(
                      toks[k] not in t[1:] for v, t in plain.items()
                      if v != u))
    eos = plain[uid][k]
    _, got, counters = serve(cfg, params, reqs, eos_id=eos)
    assert got[uid] == plain[uid][:k + 1]
    assert {u: t for u, t in got.items() if u != uid} == \
        {u: t for u, t in plain.items() if u != uid}
    assert counters["serve.discarded_tokens"] == 1
    assert got == serve_in_order(cfg, params, reqs, eos_id=eos)


def test_fault_with_a_decode_in_flight_restarts_the_request(dense):
    """Slot 0 is knocked out at iteration 2, while the decode that step 1
    dispatched for it is in flight: that token is dropped, and the request
    restarts from its prompt to the tokens of a fault-free run."""
    cfg, params = dense
    reqs = prompts(cfg)
    _, plain, _ = serve(cfg, params, reqs)
    srv, got, counters = serve(cfg, params, reqs,
                               faults="fail slot 0 @ iter 2")
    assert srv.retries_total == 1
    (knocked,) = [r for r in srv.completed if r.retries == 1]
    assert knocked.uid == 0
    assert got == plain
    assert counters["serve.discarded_tokens"] == 1
