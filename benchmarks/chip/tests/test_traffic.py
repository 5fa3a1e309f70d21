"""The traffic generator: seeded, same sizes for every seed, on the grid."""
from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

from chip import cells, traffic
from chip.tests import tiny

MIXES = ["reasoning-backlog", "tiny-backlog", "tiny-burst"]


def mix(name):
    if name == "tiny-burst":
        return tiny.BURST
    if name == "tiny-backlog":
        return tiny.BACKLOG
    return traffic.load(cells.REPO_ROOT / "benchmarks" / "chip", name)


def filled(m) -> int:
    """Requests of the steady fill at the head of a plan."""
    return m["slots"] if m["arrivals"].get("fill_round") else 0


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    m = mix(name)
    a, b = traffic.plan(m, 2**31 + 7, 30), traffic.plan(m, 2**31 + 7, 30)
    assert a == b
    assert np.array_equal(traffic.prompt_tokens(5, 3, 64, 1000),
                          traffic.prompt_tokens(5, 3, 64, 1000))
    assert a != traffic.plan(m, 8, 30)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    m = mix(name)
    runs = [traffic.plan(m, seed, 30) for seed in (1, 2, 3_000_000_000)]
    for field in ("prompt_len", "max_new"):
        sets = [Counter(getattr(p, field) for p in r) for r in runs]
        assert sets[0] == sets[1] == sets[2]
    if m["arrivals"]["kind"] == "gamma":
        ends = [r[-1].due_s for r in runs]
        assert ends[0] == pytest.approx(ends[1]) == pytest.approx(ends[2])


@pytest.mark.parametrize("name", MIXES)
def test_prompts_stay_on_the_warmed_grid(name):
    m = mix(name)
    grid = set(traffic.prompt_grid(m))
    plans = traffic.plan(m, 4, 30)
    k = filled(m)
    assert {p.prompt_len for p in plans} <= grid
    assert all(m["output"]["min"] <= p.max_new <= m["output"]["max"]
               for p in plans[k:])
    assert all(p.max_new >= 1 for p in plans[:k])
    longest = m["prompt"]["max"] + m["output"]["max"]
    assert max(p.prompt_len + p.max_new for p in plans) <= longest
    assert longest < m["cache_len"]


def test_grid_is_every_multiple_in_range_and_the_fill():
    assert traffic.prompt_grid(tiny.BURST) == [8, 16, 24, 32]
    assert traffic.prompt_grid(mix("reasoning-backlog")) == list(
        range(64, 513, 64)) + [768, 1024, 1280, 1792]


def test_length_biased_lengths_weight_long_answers():
    d = mix("reasoning-backlog")["output"]
    plain = traffic.lognormal_block(d, 240)
    biased = traffic.length_biased_block(d, 240)
    assert min(biased) >= d["min"] and max(biased) == d["max"]
    # the mean of a length-biased draw is E[L^2] / E[L]
    want = np.mean(np.square(plain)) / np.mean(plain)
    assert np.mean(biased) == pytest.approx(want, rel=0.02)


def test_steady_fill_stands_where_steady_state_does():
    m = mix("reasoning-backlog")
    fill = traffic.steady_fill(m)
    assert len(fill) == m["slots"]
    prompts = traffic.lognormal_block(m["prompt"], 2400)
    answers = traffic.lognormal_block(m["output"], 2400)
    # a slot's context in steady state: its prompt plus the age of its
    # request, whose mean is E[L^2] / (2 E[L])
    age = np.mean(np.square(answers)) / (2 * np.mean(answers))
    ctx = np.mean([s for s, _ in fill])
    assert ctx == pytest.approx(np.mean(prompts) + age, rel=0.1)
    # requests finish all through the first answer's length, not at once
    left = sorted(r for _, r in fill)
    assert left[0] < 0.1 * np.mean(answers)
    assert left[len(left) // 2] < np.mean(answers)
    # the same fill, in another order, for every seed
    heads = [traffic.plan(m, s, 30)[:m["slots"]] for s in (1, 2**31 + 3)]
    assert [(p.prompt_len, p.max_new) for p in heads[0]] != \
        [(p.prompt_len, p.max_new) for p in heads[1]]
    assert sorted((p.prompt_len, p.max_new) for p in heads[0]) == sorted(fill)


def test_gamma_gaps_keep_the_rate_and_burst():
    gaps = traffic.gamma_block(16.0, 3.0, 32)
    assert gaps.sum() == pytest.approx(32 / 16.0)
    cv = gaps.std() / gaps.mean()
    assert cv > 2.0                         # bursty, not Poisson
    poisson = traffic.gamma_block(16.0, 1.0, 32)
    assert poisson.std() / poisson.mean() == pytest.approx(1.0, abs=0.15)


def test_open_loop_covers_the_window():
    m = mix("tiny-burst")
    plans = traffic.plan(m, 1, 30)
    assert plans[-1].due_s > m["arrivals"]["preroll_s"] + 30
    assert all(b.due_s >= a.due_s for a, b in zip(plans, plans[1:]))


def test_lognormal_block_clips_and_rounds():
    d = {"median": 192, "sigma": 0.6, "min": 64, "max": 512, "round_up": 64}
    xs = traffic.lognormal_block(d, 24)
    assert min(xs) == 64 and max(xs) == 512
    assert all(x % 64 == 0 for x in xs)
    assert sorted(xs)[11] == 192            # just below the median: 186 -> 192
