"""Traffic generator: one general generator, one data file per mix.

A mix (``traffic/<mix>.json``) gives the arrival process, the length
distributions, the prompt grid and the server's slots.  Lengths are
lognormal, clipped, and prompt lengths rounded up to a grid, because
``Server`` compiles one prefill per prompt length: every grid length is
warmed in set-up, and no other length can occur.  Gaps are Gamma (CV 1 is
Poisson), after ``serve/traffic.bursty_trace``; ``backlog`` is a closed loop
whose queue never empties.

A backlog with ``fill_round`` starts with a fill that puts every slot where a
slot stands in steady state, so that the window measures the loop's steady
state and not the start-up of one lock-stepped batch.  In steady state the
request a slot holds is length-biased (a request stays as many steps as it
has tokens) and has served a uniform share of its answer.  Such a request is
sent as its prompt plus the tokens it has served, rounded to a multiple of
``fill_round`` (never below its prompt, and leaving a token to come), with
the rest of its answer to come: the context and the remaining work of a
slot in steady state.  The fill's lengths are
on the prompt grid too, and its sizes are the same for every seed.

Every seed gets the same sizes and arrivals in another order: requests come
in blocks, and each block holds the distribution's stratified quantiles
(the ``(i + 0.5) / block`` points), shuffled by the seed.  So the seed moves
which request is long and when a burst comes, not how much work a run holds.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    uid: int
    due_s: Optional[float]      # seconds after the schedule's origin; None: backlog
    prompt_len: int
    max_new: int


def load(chip_dir: Path, mix: str) -> dict:
    with open(chip_dir / "traffic" / f"{mix}.json") as f:
        return json.load(f)


def prompt_grid(mix: dict) -> List[int]:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    p = mix["prompt"]
    r = p["round_up"]
    lo = -(-p["min"] // r) * r
    grid = set(range(lo, p["max"] + 1, r))
    if mix["arrivals"].get("fill_round"):
        grid |= {sent for sent, _ in steady_fill(mix)}
    return sorted(grid)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_block(dist: dict, n: int) -> List[int]:
    """``n`` stratified lognormal lengths, clipped and rounded up."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    x = np.clip(np.ceil(x), dist["min"], dist["max"])
    r = dist.get("round_up", 1)
    return [int(-(-v // r) * r) for v in x]


def length_biased_block(dist: dict, n: int) -> List[int]:
    """``n`` stratified answer lengths as slots hold them in steady state:
    the clipped lognormal of ``dist`` with the odds of length ``l`` weighted
    by ``l``."""
    ls = np.arange(dist["min"], dist["max"] + 1)
    nd = NormalDist(math.log(dist["median"]), dist["sigma"])
    cdf = np.array([nd.cdf(math.log(v)) for v in ls])   # P(ceil(x) <= l)
    p = np.diff(cdf, prepend=0.0)
    p[-1] += 1.0 - cdf[-1]                              # the clip at the top
    c = np.cumsum(ls * p)
    c /= c[-1]
    return [int(ls[np.searchsorted(c, q)]) for q in _quantiles(n)]


def steady_fill(mix: dict) -> List[Tuple[int, int]]:
    """(prompt length, answer still to come) of every slot in steady state,
    the same for every seed (module docstring)."""
    n, r = mix["slots"], mix["arrivals"]["fill_round"]
    pair = np.random.default_rng(0xF111)                # not the run's seed
    prompts = pair.permutation(lognormal_block(mix["prompt"], n))
    answers = pair.permutation(length_biased_block(mix["output"], n))
    out = []
    for p, l, u in zip(prompts, answers, _quantiles(n)):
        p, l = int(p), int(l)
        ctx = p + int(u * l)                            # context it has
        sent = (ctx + r // 2) // r * r
        if sent >= p + l:                               # none left to come
            sent -= r
        sent = max(p, sent)
        out.append((sent, p + l - sent))
    return out


def gamma_block(rate_rps: float, cv: float, n: int) -> np.ndarray:
    """``n`` stratified Gamma gaps of coefficient of variation ``cv``, scaled
    so that the block's mean rate is exactly ``rate_rps``."""
    from scipy.stats import gamma
    g = gamma.ppf(_quantiles(n), a=1.0 / (cv * cv))
    return g * (n / rate_rps) / g.sum()


def _blocks(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        return mix["blocks"]
    horizon = arr.get("preroll_s", 0.0) + seconds
    # one block of slack past the end: the window never runs out of arrivals
    return math.ceil(arr["rate_rps"] * horizon / mix["block"]) + 1


def plan(mix: dict, seed: int, seconds: float) -> List[Planned]:
    """The run's requests in arrival order, all from ``seed``."""
    rng = np.random.default_rng([seed, 0x7A1F])
    n, kind = mix["block"], mix["arrivals"]["kind"]
    prompts = lognormal_block(mix["prompt"], n)
    outputs = lognormal_block(mix["output"], n)
    gaps = None
    if kind == "gamma":
        gaps = gamma_block(mix["arrivals"]["rate_rps"], mix["arrivals"]["cv"],
                           n)
    elif kind != "backlog":
        raise ValueError(f"unknown arrival kind {kind!r}")
    out: List[Planned] = []
    if kind == "backlog" and mix["arrivals"].get("fill_round"):
        for p, m in rng.permutation(steady_fill(mix)):
            out.append(Planned(uid=len(out), due_s=None, prompt_len=int(p),
                               max_new=int(m)))
    t = 0.0
    for _ in range(_blocks(mix, seconds)):
        p, o = rng.permutation(prompts), rng.permutation(outputs)
        g = rng.permutation(gaps) if gaps is not None else None
        for i in range(n):
            due = None
            if g is not None:
                t += float(g[i])
                due = t
            out.append(Planned(uid=len(out), due_s=due, prompt_len=int(p[i]),
                               max_new=int(o[i])))
    return out


def prompt_tokens(seed: int, uid: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of request ``uid``: uniform token ids from the seed."""
    rng = np.random.default_rng([seed, 0x9E37, uid])
    return rng.integers(0, vocab, size=length, dtype=np.int32)
