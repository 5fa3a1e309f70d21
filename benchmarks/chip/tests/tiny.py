"""Tiny cells for CPU tests, defined from new files only.

``write_root`` lays out a checkout of its own under a temporary directory:
``BENCHMARK.json``, a configuration file, traffic files, metric readers and
a model family (``tiny-untied``), none of which exists in the repository,
beside a copy of the benchmark's own families.  The harness resolves them
by name.  The routed test family (``ROUTED_FAMILY``) is written beside them
by the tests that use it.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chip import family

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

UNTIED = dict(
    DECODER, name="tiny-untied", family="tiny-untied",
    config=dict(DECODER["config"], tie_word_embeddings=False),
    program_overrides=dict(DECODER["program_overrides"],
                           tie_embeddings=False))

# A family of its own file: the decoder family's pieces with an LM head that
# is a leaf of its own (``head/w``, d x vocab_rows), as the program serves
# ``tie_embeddings: false``.
UNTIED_FAMILY = '''"""Decoder with an untied LM head (test family)."""
from __future__ import annotations

import dataclasses
from pathlib import Path

from chip import family, weights
from chip.reference import F32

dec = family.load(Path(__file__).with_name("decoder.py"))


@dataclasses.dataclass(frozen=True)
class Shape(dec.Shape):
    pass


def shape(config):
    if config["config"].get("tie_word_embeddings", True):
        raise ValueError("this family reads an untied LM head")
    return Shape(family=config["family"], **dec.sizes(config))


def program_sizes(s):
    return dict(dec.program_sizes(s), tie_embeddings=False)


def leaves(s):
    return dec.leaves(s) + [(("head", "w"), (s.d_model, s.vocab_rows),
                             weights.BF16, "normal", s.d_model ** -0.5)]


hidden = dec.hidden


def head(params, s):
    return params["head"]["w"][:, :s.vocab].T.astype(F32)


non_embedding_params = dec.non_embedding_params
head_params = dec.head_params
decode_token_flops = dec.decode_token_flops
prefill_flops = dec.prefill_flops


def weight_read_bytes(s):
    # neither the table nor the padded head; the head over the real vocabulary
    rows = 2 * s.vocab_rows * s.d_model * 2
    return weights.nbytes(s) - rows + head_params(s) * 2


def decode_step_bytes(s, step):
    return weight_read_bytes(s) + sum(step.ctxs) * dec.kv_bytes_per_token(s)
'''

# One layer: a flip then moves only its own position's logits.  With two, a
# flip at a prompt position reached later positions through the second
# layer's attention (one seed read an unmarked gap of 0.28), which the
# marks, of the reference's own near-ties, do not cover.
ROUTED = dict(
    DECODER, name="tiny-routed", family="tiny-routed",
    config=dict(DECODER["config"], num_hidden_layers=1, n_routed_experts=8,
                num_experts_per_tok=2, n_group=4, topk_group=2,
                moe_intermediate_size=32),
    held_experts=4,
    check={"max_logit_gap": 0.1,
           "why": "widest gap at unmarked positions: the bfloat16 run reads "
                  "0-0.047 over 15 seeds (CPU), the float8 control 0.29-2.9",
           "route_margin": 0.025,
           "route_margin_why": "the bfloat16 run's selection keys lie within "
                               "0.0102 of the float32 reference's over 15 "
                               "seeds (CPU); a choice flips only where two "
                               "keys lie closer than their errors together",
           "max_route_tie_share": 0.6,
           "max_route_tie_share_why": "0.22-0.43 of served positions are "
                                      "marked over 15 seeds (CPU)"})

# A family of its own file with routed layers: the decoder family's layer,
# then a grouped top-k router (sigmoid keys, groups scored by their two best
# keys) over ``n_routed_experts``, of which the first ``held_experts`` are
# held and computed; what the others would add is left out.  No program
# serves it: ``served_hidden`` runs the same layers in bfloat16, so that
# real routing flips occur against the float32 reference.
ROUTED_FAMILY = '''"""Decoder with routed experts, a few held (test family)."""
from __future__ import annotations

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp

from chip import family
from chip.reference import F32, decoder, matmul, rmsnorm, topk_near
from chip.weights import BF16

dec = family.load(Path(__file__).with_name("decoder.py"))


@dataclasses.dataclass(frozen=True)
class Shape(dec.Shape):
    experts: int
    experts_per_token: int
    groups: int
    topk_groups: int
    moe_ff: int
    held: int


def shape(config):
    c = config["config"]
    return Shape(family=config["family"], **dec.sizes(config),
                 experts=c["n_routed_experts"],
                 experts_per_token=c["num_experts_per_tok"],
                 groups=c["n_group"], topk_groups=c["topk_group"],
                 moe_ff=c["moe_intermediate_size"],
                 held=config["held_experts"])


def leaves(s):
    L, d, f, e = s.layers, s.d_model, s.moe_ff, s.held
    blk = ("stack", "moe")
    return dec.leaves(s) + [
        (blk + ("ln", "scale"), (L, d), BF16, "norm", 0.0),
        (blk + ("router",), (L, d, s.experts), BF16, "normal", d ** -0.5),
        (blk + ("wi",), (L, e, d, f), BF16, "normal", d ** -0.5),
        (blk + ("wg",), (L, e, d, f), BF16, "normal", d ** -0.5),
        # three times the usual scale: a flip moves logits far past rounding
        (blk + ("wo",), (L, e, f, d), BF16, "normal", 3 * f ** -0.5)]


def _moe(s, x, p, quant, margin):
    keys = jax.nn.sigmoid(matmul(x, p["router"], quant))       # (B, T, E)
    per = s.experts // s.groups
    grouped = keys.reshape(*keys.shape[:-1], s.groups, per)
    # a group scores the sum of two keys: it moves as far as two keys do
    kept, gnear = topk_near(jax.lax.top_k(grouped, 2)[0].sum(-1),
                            s.topk_groups, 2 * margin)
    kept, gnear = jnp.repeat(kept, per, -1), jnp.repeat(gnear, per, -1)
    chosen, near = topk_near(jnp.where(kept, keys, -jnp.inf),
                             s.experts_per_token, margin)
    w = jnp.where(chosen, keys, 0.0)
    w = w / w.sum(-1, keepdims=True)
    out = 0.0
    for e in range(s.held):
        up = matmul(x, p["wi"][e], quant)
        gate = matmul(x, p["wg"][e], quant)
        out = out + w[..., e:e + 1] * matmul(jax.nn.silu(gate) * up,
                                             p["wo"][e], quant)
    # a held expert's own choice near its boundary, or, as the weights are
    # normalised over the chosen experts, any group near its boundary while
    # a held expert is chosen
    held_chosen = jnp.any(chosen[..., :s.held], -1)
    return out, (jnp.any((near | gnear)[..., :s.held], -1)
                 | held_chosen & jnp.any(gnear, -1))


def _forward(params, tokens, s, dtype, quant=False, margin=0.0):
    cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
    h = params["embed"]["table"][tokens].astype(dtype)

    def body(h, p):
        dense, moe = cast(p)
        h = decoder._layer(s, quant, h, dense).astype(dtype)
        x = rmsnorm(h, moe["ln"]["scale"], s.norm_eps)
        out, ties = _moe(s, x, moe, quant, margin)
        return (h + out).astype(dtype), ties

    stack = params["stack"]
    h, ties = jax.lax.scan(body, h, (stack["dense_stack"], stack["moe"]))
    h = rmsnorm(h, params["final_norm"]["scale"].astype(dtype), s.norm_eps)
    return h.astype(F32), jnp.any(ties, 0)


def hidden(params, tokens, s, quant=False):
    return _forward(params, tokens, s, F32, quant)[0]


def hidden_ties(params, tokens, s, margin):
    return _forward(params, tokens, s, F32, margin=margin)


def served_hidden(params, tokens, s):
    """The same layers with bfloat16 weights and activations."""
    return _forward(params, tokens, s, jnp.bfloat16)[0]


head = dec.head
'''

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
    shutil.copytree(family.HERE / "families", chip / "families",
                    ignore=shutil.ignore_patterns("__pycache__"),
                    dirs_exist_ok=True)
    (chip / "families" / "tiny-untied.py").write_text(UNTIED_FAMILY)
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
