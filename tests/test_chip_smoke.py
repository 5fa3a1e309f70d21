"""Rehearse chip_smoke.py on the CPU: its phases at a reduced width with the
Pallas kernels explicitly in interpret mode, its refusal to run without a
TPU, its four-device train step on virtual CPU devices, and where the
compile cache goes."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import get  # noqa: E402
from repro.models.layers import Backend  # noqa: E402

CPU_ENV = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")


def run(args, env=CPU_ENV, cwd=ROOT, timeout=300):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=cwd, env=env)


def test_smoke_phases_on_reduced_config():
    """Serve, the first-token, decoded-token and precision checks, the
    Pallas-vs-XLA decode and the decode-attention kernel's read on the
    Server's caches, at bf16 compute."""
    cfg = get("qwen3-1.7b").reduced().with_policy(compute_dtype="bfloat16")
    params = chip_smoke.init_params(cfg, seed=0)
    srv, done, wall = chip_smoke.serve(
        cfg, params, n_requests=5, slots=2, cache_len=64, max_new=4,
        prompt_lens=(8, 16, 24), seed=0)
    assert sorted(r.uid for r in done) == list(range(5)) and wall > 0
    assert {len(r.prompt) for r in done} == {8, 16, 24}
    assert chip_smoke.check_first_tokens(cfg, params, done, 64) == 5
    gap = chip_smoke.check_decoded_token(cfg, params, done[0], 64)
    assert 0.0 <= gap <= chip_smoke.DECODE_TOL
    prec = chip_smoke.check_precision(cfg, params, done[0].prompt, 64)
    assert chip_smoke.COS_MIN <= prec["cosine"] <= 1.0 + 1e-9
    assert prec["rel_l2"] > 0          # bf16 and f32 really differ
    pal = chip_smoke.check_pallas_decode(
        cfg, params, srv.caches, srv.pos,
        backend=Backend("pallas", interpret=True))
    assert pal["slots"] == 2 and pal["cosine_min"] >= chip_smoke.COS_MIN
    assert not pal["tpu_custom_call"]  # interpreted, not compiled
    dk = chip_smoke.check_decode_kernel(cfg, srv, interpret=True)
    assert dk["cosine_min"] >= chip_smoke.COS_MIN and dk["max_abs"] < 0.1
    assert dk["tpu_custom_call"] is None  # interpreted, not compiled


def test_checks_fail_on_a_wrong_result():
    cfg = get("qwen3-1.7b").reduced()
    params = chip_smoke.init_params(cfg, seed=0)
    _, done, _ = chip_smoke.serve(cfg, params, n_requests=1, slots=1,
                                  cache_len=32, max_new=2, prompt_lens=(8,))
    done[0].out_tokens[0] = (done[0].out_tokens[0] + 1) % cfg.vocab_size
    with pytest.raises(chip_smoke.SmokeFailure, match="first token"):
        chip_smoke.check_first_tokens(cfg, params, done, 32)


def test_require_tpu_refuses_the_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.require_tpu(1)


def test_script_fails_without_a_tpu_or_without_the_repo(tmp_path):
    r = run([str(ROOT / "chip_smoke.py")])
    assert r.returncode != 0 and '"ok"' not in r.stdout, r.stdout
    assert "no TPU" in r.stderr
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", alone)
    env = {k: v for k, v in CPU_ENV.items() if k != "PYTHONPATH"}
    r = run([str(alone / "chip_smoke.py")], env=env, cwd=alone)
    assert r.returncode != 0 and '"ok"' not in r.stdout, r.stdout


def test_train_steps_on_four_virtual_devices():
    """The --chips 4 phase's control flow and checks on a (2, 2) mesh of
    virtual CPU devices, at a reduced width with bf16 compute."""
    code = (
        "import json, chip_smoke\n"
        "from repro.configs import get\n"
        "cfg = get('qwen3-1.7b').reduced().with_policy(\n"
        "    compute_dtype='bfloat16', microbatches=2)\n"
        "print(json.dumps(chip_smoke.train_steps(cfg, steps=3, batch=8,\n"
        "                                        seq=32)))\n")
    env = dict(CPU_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    r = run(["-c", code], env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["mesh"] == {"data": 2, "model": 2}
    assert rep["rel_err"] <= chip_smoke.LOSS_RTOL
    assert rep["losses"][-1] < rep["losses"][0]


_CACHE_PROBE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "path = enable_compile_cache()\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n"
    "print(path)\n")


def test_compile_cache_dir_set_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR names the cache directory, and the cache
    files land there."""
    cache = tmp_path / "cache"
    r = run(["-c", _CACHE_PROBE],
            env=dict(CPU_ENV, JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_checkout():
    from repro.launch.compile_cache import REPO_CACHE_DIR
    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n")
    env = {k: v for k, v in CPU_ENV.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = run(["-c", code], env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(REPO_CACHE_DIR) == str(ROOT / ".jax_cache")
