"""The decoder and Mamba2 families give the numbers they gave before they
moved into family files.

Recorded with the code in which ``shapes``, ``weights``, ``counts`` and
``check`` still branched on the family name: the leaf specs, the program
sizes and the counts of both committed configurations, and a digest of the
weights that ``weights.make`` draws at the tiny sizes for one seed.  A
change to any of them changes what the benchmark reads.
"""
from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

from chip import cells, counts, layer, shapes, weights
from chip.tests import tiny

SEED = 2**31 + 5
R = 0.02209708691207961         # 2048 ** -0.5, as the old code computed it

# (path, shape, served dtype, init kind, scale), in draw order
LEAVES = {
    "qwen3-1.7b": [
        ("stack/dense_stack/ln1/scale", (28, 2048), "bfloat16", "norm", 0.0),
        ("stack/dense_stack/ln2/scale", (28, 2048), "bfloat16", "norm", 0.0),
        ("stack/dense_stack/attn/wq/w", (28, 2048, 2048), "bfloat16",
         "normal", R),
        ("stack/dense_stack/attn/wk/w", (28, 2048, 1024), "bfloat16",
         "normal", R),
        ("stack/dense_stack/attn/wv/w", (28, 2048, 1024), "bfloat16",
         "normal", R),
        ("stack/dense_stack/attn/wo/w", (28, 2048, 2048), "bfloat16",
         "normal", R),
        ("stack/dense_stack/mlp/wi/w", (28, 2048, 6144), "bfloat16",
         "normal", R),
        ("stack/dense_stack/mlp/wg/w", (28, 2048, 6144), "bfloat16",
         "normal", R),
        ("stack/dense_stack/mlp/wo/w", (28, 6144, 2048), "bfloat16",
         "normal", 0.01275775907699572),
        ("stack/dense_stack/attn/qnorm/scale", (28, 128), "bfloat16", "norm",
         0.0),
        ("stack/dense_stack/attn/knorm/scale", (28, 128), "bfloat16", "norm",
         0.0),
        ("final_norm/scale", (2048,), "bfloat16", "norm", 0.0),
        ("embed/table", (152064, 2048), "bfloat16", "normal", R),
    ],
    "mamba2-370m": [
        ("stack/ssm_stack/ln/scale", (48, 1024), "bfloat16", "norm", 0.0),
        ("stack/ssm_stack/mamba/in_proj/w", (48, 1024, 4384), "bfloat16",
         "normal", 0.03125),
        ("stack/ssm_stack/mamba/conv_w", (48, 4, 2304), "bfloat16", "normal",
         0.5),
        ("stack/ssm_stack/mamba/conv_b", (48, 2304), "bfloat16", "normal",
         0.1),
        ("stack/ssm_stack/mamba/a_log", (48, 32), "float32", "a_log", 0.0),
        ("stack/ssm_stack/mamba/d_skip", (48, 32), "float32", "norm", 0.0),
        ("stack/ssm_stack/mamba/dt_bias", (48, 32), "float32", "dt_bias",
         0.0),
        ("stack/ssm_stack/mamba/norm/scale", (48, 2048), "bfloat16", "norm",
         0.0),
        ("stack/ssm_stack/mamba/out_proj/w", (48, 2048, 1024), "bfloat16",
         "normal", R),
        ("final_norm/scale", (1024,), "bfloat16", "norm", 0.0),
        ("embed/table", (50432, 1024), "bfloat16", "normal", 0.03125),
    ],
}

CTXS = [100, 700, 2000]         # contexts of the pinned decode step
COUNTS = {
    "qwen3-1.7b": {
        "nbytes": 3441674240,
        "non_embedding_params": 1409410048,
        "head_params": 311164928,
        "weight_read_bytes": 3441149952,
        "decode_token_flops": [3441379328, 3601713152, 3910682624],
        "prefill_flops": [181503918080, 1473981710336],
        "decode_step_bytes": 3762276352,
    },
    "mamba2-370m": {
        "nbytes": 736997376,
        "non_embedding_params": 316851712,
        "head_params": 51483648,
        "weight_read_bytes": 736679936,
        "decode_token_flops": [799585280, 799585280, 799585280],
        "prefill_flops": [44686518272, 356771375104],
        "decode_step_bytes": 1042651136,
    },
}

PROGRAM_SIZES = {
    "qwen3-1.7b": {
        "n_layers": 28, "d_model": 2048, "vocab_size": 151936,
        "norm_eps": 1e-06, "tie_embeddings": True, "n_heads": 16,
        "n_kv_heads": 8, "head_dim_": 128, "d_ff": 6144,
        "rope_theta": 1000000.0, "qk_norm": True, "act": "swiglu",
        "norm": "rmsnorm", "pos_embed": "rope", "attn_bias": False,
        "sliding_window": 0, "moe": None, "mla": None},
    "mamba2-370m": {
        "n_layers": 48, "d_model": 1024, "vocab_size": 50277,
        "norm_eps": 1e-05, "tie_embeddings": True, "ssm.d_state": 128,
        "ssm.d_conv": 4, "ssm.expand": 2, "ssm.head_dim": 64,
        "ssm.n_groups": 1, "family": "ssm"},
}

# sha256 over each leaf's (path, shape, dtype) and bytes, in draw order
DIGESTS = {
    "tiny-decoder":
        "c9ae9c891b0e08ffccad04d3d2fb83a965ee60a60fce9ae55da9280d4d4334c7",
    "tiny-mamba2":
        "122792fead5071eee5986677cff36897f721b4e12c42efebf42b2bcd03af4a4b",
}


def committed(name):
    path = cells.REPO_ROOT / "benchmarks" / "chip" / "configs" / f"{name}.json"
    return shapes.from_config(json.loads(path.read_text()))


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_leaf_specs_are_pinned(name):
    got = [("/".join(p), shape, dtype, kind, scale)
           for p, shape, dtype, kind, scale in weights.leaves(committed(name))]
    assert got == LEAVES[name]


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_and_program_sizes_are_pinned(name):
    s = committed(name)
    got = {
        "nbytes": weights.nbytes(s),
        "non_embedding_params": counts.non_embedding_params(s),
        "head_params": counts.head_params(s),
        "weight_read_bytes": counts.weight_read_bytes(s),
        "decode_token_flops": [counts.decode_token_flops(s, c)
                               for c in (1, 700, 2047)],
        "prefill_flops": [counts.prefill_flops(s, t) for t in (64, 512)],
        "decode_step_bytes": counts.decode_step_bytes(s, CTXS),
    }
    assert got == COUNTS[name]
    step = layer.Step(0.0, 1.0, (64,), tuple(CTXS))    # as the reader passes it
    assert counts.decode_step_bytes(s, step) == got["decode_step_bytes"]
    assert all(type(v) is int for v in got.values() if not isinstance(v, list))
    assert shapes.program_sizes(s) == PROGRAM_SIZES[name]


@pytest.mark.parametrize("config", [tiny.DECODER, tiny.MAMBA2],
                         ids=lambda c: c["name"])
def test_weights_are_pinned(config):
    s = shapes.from_config(config)
    params = weights.make(s, SEED)
    h = hashlib.sha256()
    for path, *_ in weights.leaves(s):
        x = params
        for key in path:
            x = x[key]
        a = np.asarray(x)
        h.update(repr((path, a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DIGESTS[config["name"]]
