"""Distribution-layer integration tests (subprocess: device count must be
set before jax initializes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
ENV = dict(os.environ, PYTHONPATH=str(ROOT / "src"))


def run(args, env=None, timeout=520):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=ROOT,
                          env=env or ENV)


@pytest.mark.slow
def test_distributed_train_example_4dev():
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = run([str(ROOT / "examples" / "distributed_train.py")], env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "distributed_train OK" in r.stdout
    assert "tp_mode=allreduce" in r.stdout and "tp_mode=allgather" in r.stdout


@pytest.mark.slow
def test_dryrun_cell_multi_pod(tmp_path):
    """One full-config cell lowers+compiles on the 512-chip multi-pod mesh
    (the dry-run path end to end, including the roofline extraction)."""
    out = tmp_path / "qwen3-1.7b.decode_32k.multi.json"
    r = run(["-m", "repro.launch.dryrun", "--arch", "qwen3-1.7b",
             "--shape", "decode_32k", "--mesh", "multi", "--force",
             "--out", str(out)])
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    rec = json.loads(out.read_text())
    assert rec["ok"] and rec["flops"] > 0
    assert rec["memory"]["peak_bytes_per_device"] < 16 * 2 ** 30


def test_sharding_rules_cover_all_archs():
    """Every parameter of every full config gets a valid spec on a mock
    16x16 mesh (divisibility-checked), and FSDP/TP axes land where the
    rules say."""
    import jax
    from repro.configs import all_names, get
    from repro.launch.params import param_shapes
    from repro.sharding import rules

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    for name in all_names():
        cfg = get(name)
        shapes = param_shapes(cfg)
        specs = rules.param_pspecs(cfg, shapes, FakeMesh())
        for (path, leaf), spec in zip(
                jax.tree_util.tree_flatten_with_path(shapes)[0],
                jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: hasattr(x, "index"))):
            assert len(spec) <= leaf.ndim, (name, path)
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                size = 1
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    size *= FakeMesh.shape[a]
                assert leaf.shape[dim] % size == 0, (name, path, spec)


def test_embedding_and_ffn_sharded_on_model_axis():
    from repro.configs import get
    from repro.launch.params import param_shapes
    from repro.sharding import rules

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    cfg = get("command-r-35b")
    specs = rules.param_pspecs(cfg, param_shapes(cfg), FakeMesh())
    emb = specs["embed"]["table"]
    assert emb[0] == "model"                      # vocab on model
    wi = specs["stack"]["dense_stack"]["mlp"]["wi"]["w"]
    assert wi[-1] == "model" and wi[-2] == "data"  # TP + FSDP
    wo = specs["stack"]["dense_stack"]["mlp"]["wo"]["w"]
    assert wo[-2] == "model"                       # row-sharded (allreduce TP)
