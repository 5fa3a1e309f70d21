"""The check of a routed model: routing near-ties are marked and left out,
every other position keeps the tight limit, and the marked share has a
limit of its own.

The tiny routed family (``tiny.ROUTED_FAMILY``) is written into a test's
checkout.  Its served tokens are the greedy picks of a bfloat16 run of its
own layers, so real routing flips occur against the float32 reference.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import cells, check, family, harness, traffic, weights
from chip.reference import topk_near
from chip.tests import tiny

SEED = 2**31 + 5
PROMPTS = (8, 19, 26, 32)       # prompt lengths of the served requests
NEW = 40                        # tokens served to each


def serve(cell, params, seed):
    """Greedy answers of the family's bfloat16 run, a full forward a token."""
    s = cell.shape
    fam = family.of(s)
    fwd = jax.jit(lambda p, t: fam.served_hidden(p, t, s).astype(jnp.bfloat16))
    table = params["embed"]["table"][:s.vocab]
    prompts = [traffic.prompt_tokens(seed, i, n, s.vocab)
               for i, n in enumerate(PROMPTS)]
    tokens = np.zeros((len(prompts), check.reference_length(cell.mix)),
                      np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    rows = np.arange(len(prompts))
    for n in range(NEW):
        ends = np.array(PROMPTS) + n
        h = fwd(params, jnp.asarray(tokens))[rows, ends - 1]
        tokens[rows, ends] = np.asarray(jnp.argmax(h @ table.T, -1))
    return [check.Served(p, tokens[i, len(p):len(p) + NEW].copy())
            for i, p in enumerate(prompts)]


def with_limits(cell, **limits):
    """``cell`` with its configuration's ``check`` replaced."""
    return dataclasses.replace(cell, config=dict(cell.config, check=limits))


def rule_off(cell):
    gap = cell.config["check"]["max_logit_gap"]
    return with_limits(cell, max_logit_gap=gap)


@pytest.fixture(scope="module")
def routed(tmp_path_factory):
    """The routed cell, its weights and a served sample."""
    root = tiny.write_root(tmp_path_factory.mktemp("routed"), tiny.ROUTED,
                           tiny.BACKLOG)
    (root / "bench" / "families" / "tiny-routed.py").write_text(
        tiny.ROUTED_FAMILY)
    cell = cells.resolve("tiny.cell", root)
    params = weights.make(cell.shape, SEED)
    return cell, params, serve(cell, params, SEED)


def judged(cell, served, control=False):
    g = harness.judge(cell, SEED, served, control=control)
    gap = g.control_gap if control else g.max_gap
    return harness.compare(cell, gap, g.tie_share)


def test_topk_near_marks_both_sides_of_the_boundary():
    keys = jnp.array([[0.9, 0.5, 0.52, 0.1, -jnp.inf],
                      [0.3, 0.31, 0.8, 0.7, 0.2]])
    chosen, near = topk_near(keys, 2, 0.05)
    np.testing.assert_array_equal(chosen, [[1, 0, 1, 0, 0], [0, 0, 1, 1, 0]])
    np.testing.assert_array_equal(near, [[0, 1, 1, 0, 0], [0, 0, 0, 0, 0]])


def test_sound_routed_run_needs_its_marks(routed):
    """(a) Every position at the tight limit refuses a sound bfloat16 run;
    with the near-ties left out it is correct, and the share is reported."""
    cell, _, served = routed
    correct, numbers = judged(rule_off(cell), served)
    assert correct is False
    assert list(numbers) == ["max_logit_gap"]
    gap = numbers["max_logit_gap"]
    assert gap["value"] > gap["limit"]

    correct, numbers = judged(cell, served)
    assert correct is True
    assert list(numbers) == ["max_logit_gap", "route_tie_share"]
    gap = numbers["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    share = numbers["route_tie_share"]
    assert 0 < share["value"] <= share["limit"]
    assert share["limit"] == cell.config["check"]["max_route_tie_share"]


def test_token_changed_at_an_unmarked_position_is_not_correct(routed):
    """(b) One served token, at a position the reference does not mark,
    replaced by the reference's least likely token."""
    cell, params, served = routed
    s = cell.shape
    fam = family.of(s)
    tokens, _, mask = check.pack(served, check.reference_length(cell.mix))
    with jax.default_matmul_precision("highest"):
        h, ties = fam.hidden_ties(params, jnp.asarray(tokens), s,
                                  cell.config["check"]["route_margin"])
    b, t = np.argwhere(mask & ~np.asarray(ties))[len(served)]
    logits = h[b, t] @ fam.head(params, s).T
    r = served[b]
    changed = r.tokens.copy()
    changed[t + 1 - len(r.prompt)] = int(jnp.argmin(logits))
    bad = list(served)
    bad[b] = check.Served(r.prompt, changed)
    correct, numbers = judged(cell, bad)
    assert correct is False
    gap = numbers["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("how", ["margin_too_wide", "share_limit_below"])
def test_tie_share_above_its_limit_is_not_correct(routed, how):
    """(c) A margin that marks every position, or a share limit below what
    the sample marks: the gap passes, the share does not."""
    cell, _, served = routed
    limits = dict(cell.config["check"])
    if how == "margin_too_wide":
        limits["route_margin"] = 1.0        # sigmoid keys lie within 1
    else:
        limits["max_route_tie_share"] = 0.05
    correct, numbers = judged(with_limits(cell, **limits), served)
    assert correct is False
    gap = numbers["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    share = numbers["route_tie_share"]
    assert share["value"] > share["limit"]
    if how == "margin_too_wide":
        assert share["value"] == 1.0


def test_float8_control_is_not_correct_under_the_marks(routed):
    """(d) The float8 control, judged over the same unmarked positions and
    the same share, fails the gap's limit."""
    cell, _, served = routed
    correct, numbers = judged(cell, served, control=True)
    assert correct is False
    gap = numbers["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    share = numbers["route_tie_share"]
    assert share["value"] <= share["limit"]


@pytest.mark.parametrize("config, drop, match", [
    (tiny.DECODER, (), "marks no routing ties"),
    (tiny.ROUTED, ("max_route_tie_share",), "need"),
    (tiny.ROUTED, ("route_margin_why", "max_route_tie_share_why"), "need"),
])
def test_half_a_route_rule_is_refused_before_the_run(tmp_path, config, drop,
                                                     match):
    limits = dict(tiny.ROUTED["check"], max_logit_gap=0.08)
    for k in drop:
        del limits[k]
    root = tiny.write_root(tmp_path, dict(config, check=limits), tiny.BACKLOG)
    (root / "bench" / "families" / "tiny-routed.py").write_text(
        tiny.ROUTED_FAMILY)
    with pytest.raises(ValueError, match=match):
        harness.prepare("tiny.cell", root)
