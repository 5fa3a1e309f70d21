"""Whole runs of tiny cells on the CPU, with the look for a chip skipped.

A cell defined from new files only, its model family included, resolves and
runs; a sound run is correct; a run with the timed path broken underneath
is not, once for each fault a serving cell can have, nor one whose family
reads the wrong LM head.  The float8 control reads wider gaps than the
program.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from chip import cells, family, harness, weights
from chip.tests import tiny

CASES = {"decoder-backlog": (tiny.DECODER, tiny.BACKLOG),
         "mamba2-burst": (tiny.MAMBA2, tiny.BURST),
         "untied-backlog": (tiny.UNTIED, tiny.BACKLOG)}


@pytest.fixture
def on_cpu(monkeypatch):
    """Skip the harness's look for a TPU, and keep the compile cache off."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(harness, "require_chips", lambda n: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "(off)")


def run_tiny(tmp_path, case, seconds=1.0, seed=2**31 + 5):
    config, mix = CASES[case]
    root = tiny.write_root(tmp_path, config, mix)
    return harness.run("tiny.cell", seed, seconds, False, time.perf_counter(),
                       root)


def test_new_files_resolve(tmp_path):
    root = tiny.write_root(tmp_path, tiny.MAMBA2, tiny.BURST)
    cell = cells.resolve("tiny.cell", root)
    assert cell.config["name"] == "tiny-mamba2"
    assert cell.mix["arrivals"]["kind"] == "gamma"
    assert cell.grid == [8, 16, 24, 32]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "ttft_p95_ms"]
    assert list(cell.readers) == ["admitted_per_step"]
    cfg = cells.program_config(cell)
    assert (cfg.n_layers, cfg.d_model, cfg.ssm.d_state) == (2, 64, 16)


def test_new_family_resolves_from_new_files(tmp_path):
    """A family defined only in files of the checkout: its leaves are the
    program's parameter layout, the untied head among them."""
    from repro.models import model as lm
    root = tiny.write_root(tmp_path, tiny.UNTIED, tiny.BACKLOG)
    cell = cells.resolve("tiny.cell", root)
    assert cell.shape.family == "tiny-untied"
    assert family.of(cell.shape) is family.find("tiny-untied", root / "bench")
    cfg = cells.program_config(cell)
    assert cfg.tie_embeddings is False
    expected = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    got = jax.eval_shape(lambda: weights.make(cell.shape, 3))
    assert jax.tree.structure(got) == jax.tree.structure(expected)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(b.shape, b.dtype) for b in jax.tree.leaves(expected)]
    assert got["head"]["w"].shape == (64, 512)


def test_unknown_family_names_the_families_found(tmp_path):
    root = tiny.write_root(tmp_path, dict(tiny.DECODER, family="no-such"),
                           tiny.BACKLOG)
    with pytest.raises(ValueError, match=r"no model family 'no-such'.*"
                       r"\['decoder', 'mamba2', 'tiny-untied'\]"):
        cells.resolve("tiny.cell", root)


def test_benchmark_cells_resolve():
    import json
    bench = json.loads((cells.REPO_ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        cfg = cells.program_config(cell)
        assert cfg.policy.param_dtype == "bfloat16"
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}


def test_published_sizes_are_enforced(tmp_path):
    bad = dict(tiny.DECODER, program_overrides=dict(
        tiny.DECODER["program_overrides"], d_ff=96))
    root = tiny.write_root(tmp_path, bad, tiny.BACKLOG)
    with pytest.raises(ValueError, match="differs from the published"):
        cells.program_config(cells.resolve("tiny.cell", root))


@pytest.mark.parametrize("case", sorted(CASES))
def test_sound_run_is_correct(tmp_path, on_cpu, case):
    r = run_tiny(tmp_path, case)
    assert r["correct"] is True
    assert list(r)[-1] == "check"
    gap = r["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    # no routing-tie rule in the configuration: the gap alone is compared
    assert r["check"] == {"max_logit_gap": {
        "value": gap["value"],
        "limit": CASES[case][0]["check"]["max_logit_gap"]}}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert r["device"]["count"] == 1


def _alter_finished(monkeypatch, longest: bool):
    """As a request finishes, the last token it was served is replaced
    where it is produced: in each request that finishes longer than every
    request finished before it in the window (``longest``), or in each of
    the others.  The check always samples the window's longest finished
    request, the first of its size, and draws the rest of its sample from
    the others: the first fault reaches the sample on every run, the second
    only through the drawn part."""
    from repro.serve.loop import Server
    step, seen = Server.step, {}      # requests already looked at, by id

    def faulty(self):
        out = step(self)
        top = -1
        for r in self.completed:
            size = len(r.prompt) + len(r.out_tokens)
            if id(r) not in seen:
                seen[id(r)] = r       # held, so that no id is reused
                if (size > top) == longest:
                    r.out_tokens[-1] = \
                        (r.out_tokens[-1] + 1) % self.cfg.vocab_size
            top = max(top, size)
        return out
    monkeypatch.setattr(Server, "step", faulty)


def _alter_tokens(monkeypatch):
    _alter_finished(monkeypatch, longest=True)


def _alter_tokens_beside_longest(monkeypatch):
    _alter_finished(monkeypatch, longest=False)


def _stale_state(monkeypatch):
    """The decode step returns the cache or state it was given."""
    from repro.serve.loop import Server
    init = Server.__init__

    def faulty_init(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode

        def stale(p, t, ps, c):
            keep = jax.tree.map(jnp.copy, c)
            logits, _ = decode(p, t, ps, c)
            return logits, keep
        self._decode = stale
    monkeypatch.setattr(Server, "__init__", faulty_init)


def _no_splice(monkeypatch):
    """Admission leaves the slot's old cache or state in place."""
    import repro.serve.loop as loop
    monkeypatch.setattr(loop, "_splice", lambda full, one, slot, cfg: full)


FAULTS = {"token_altered": _alter_tokens,
          "token_altered_beside_longest": _alter_tokens_beside_longest,
          "state_unchanged": _stale_state, "splice_skipped": _no_splice}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(tmp_path, on_cpu, monkeypatch,
                                          case, fault):
    FAULTS[fault](monkeypatch)
    r = run_tiny(tmp_path, case)
    assert r["correct"] is False
    gap = r["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_untied_head_read_from_the_table_is_not_correct(tmp_path, on_cpu,
                                                       monkeypatch):
    """The family's ``head`` returns the embedding table, not the head the
    program served through."""
    root = tiny.write_root(tmp_path, tiny.UNTIED, tiny.BACKLOG)
    fam = family.find("tiny-untied", root / "bench")
    monkeypatch.setattr(fam, "head", fam.dec.head)
    r = harness.run("tiny.cell", 2**31 + 5, 1.0, False, time.perf_counter(),
                    root)
    assert r["correct"] is False
    gap = r["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_float8_control_reads_wider_than_the_program(tmp_path, on_cpu, case):
    config, mix = CASES[case]
    root = tiny.write_root(tmp_path, config, mix)
    cell, dev, peaks = harness.prepare("tiny.cell", root)
    seed = 11
    _, served = harness.measure(cell, dev, peaks, seed, 1.0, False,
                                time.perf_counter())
    g = harness.judge(cell, seed, served, control=True)
    limit = config["check"]["max_logit_gap"]
    assert g.max_gap <= limit < g.control_gap
    assert harness.compare(cell, g.max_gap)[0] is True
    assert harness.compare(cell, g.control_gap)[0] is False
    assert g.ties == 0
    assert harness.compare(cell, g.max_gap, g.tie_share) == \
        harness.compare(cell, g.max_gap)


def test_sweep_finds_a_growing_queue(tmp_path, on_cpu):
    """The knee sweep of an open-loop cell: at a rate far past what the
    server sustains the queue grows, far below it the queue stays empty."""
    from chip import sweep
    root = tiny.write_root(tmp_path, tiny.MAMBA2, tiny.BURST)
    low, high = list(sweep.sweep("tiny.cell", [2.0, 2000.0], 1.0, 3, root))
    assert low["rate_rps"] == 2.0 and high["rate_rps"] == 2000.0
    assert low["queued_at_close"] <= 1 < high["queued_at_close"]
    assert low["first_tokens"] > 0 and high["due"] > low["due"]
