"""Tiny cells for CPU tests, defined from new files only.

``write_root`` lays out a checkout of its own under a temporary directory:
``BENCHMARK.json``, a configuration file, traffic files and metric readers,
none of which exists in the repository.  The harness resolves them by name.
"""
from __future__ import annotations

import json
from pathlib import Path

DECODER = {
    "name": "tiny-decoder", "source": "test", "family": "decoder",
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 500,
               "tie_word_embeddings": True, "hidden_act": "silu",
               "rms_norm_eps": 1e-6, "rope_theta": 1e6},
    "architecture": {"qk_norm": True}, "assumed": {}, "reduced": [],
    "registry": "qwen3-1.7b",
    "program_overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                          "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                          "vocab_size": 500, "policy.param_dtype": "bfloat16"},
    "check": {"max_logit_gap": 0.08},
}

MAMBA2 = {
    "name": "tiny-mamba2", "source": "test", "family": "mamba2",
    "config": {"d_model": 64, "n_layer": 2, "vocab_size": 500,
               "tie_embeddings": True, "attn_layer_idx": []},
    "assumed": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                "ngroups": 1, "norm_epsilon": 1e-5},
    "reduced": [], "registry": "mamba2-370m",
    "program_overrides": {"n_layers": 2, "d_model": 64, "vocab_size": 500,
                          "norm_eps": 1e-5, "ssm.d_state": 16,
                          "ssm.head_dim": 16, "ssm.chunk": 32,
                          "policy.param_dtype": "bfloat16"},
    "check": {"max_logit_gap": 0.08},
}

BACKLOG = {
    "arrivals": {"kind": "backlog", "fill_round": 16}, "slots": 3,
    "cache_len": 96,
    "block": 3, "blocks": 40,
    "prompt": {"median": 16, "sigma": 0.6, "min": 8, "max": 32, "round_up": 8},
    "output": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
    "check_sample": 6,
}

BURST = {
    "arrivals": {"kind": "gamma", "cv": 3.0, "rate_rps": 30.0,
                 "preroll_s": 0.2},
    "slots": 4, "cache_len": 96, "block": 8,
    "prompt": {"median": 16, "sigma": 0.8, "min": 8, "max": 32, "round_up": 8},
    "output": {"median": 8, "sigma": 0.8, "min": 4, "max": 24},
    "check_sample": 6,
}

READER = '"""Requests admitted per traced step."""\n' \
    'def read(w):\n' \
    '    return sum(len(s.admitted) for s in w.steps) / max(len(w.steps), 1)\n'


def write_root(tmp: Path, config=DECODER, mix=BACKLOG, mix_name="tiny-mix",
               cell="tiny.cell") -> Path:
    """A checkout that holds one tiny cell; returns its root."""
    chip = tmp / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (chip / sub).mkdir(parents=True, exist_ok=True)
    (chip / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (chip / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
    (chip / "metrics" / "admitted_per_step.py").write_text(READER)
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": config["name"], "source": "test",
                     "file": f"bench/configs/{config['name']}.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": cell, "config": config["name"],
                       "traffic": mix_name, "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
            {"name": "output_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": [cell] if mix is BACKLOG else []},
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": [cell] if mix is BURST else []}],
        "per_layer": [{"name": "admitted_per_step", "unit": "requests",
                       "better": "higher", "source": "program_counter",
                       "layer": "serving loop",
                       "moves": "output_tokens_per_s", "workloads": [cell]}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
