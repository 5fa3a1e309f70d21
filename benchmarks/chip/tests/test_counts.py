"""Operation and byte counts, and the table of peaks."""
from __future__ import annotations

import json
import math

import pytest

from chip import cells, counts, shapes, weights


def shape(name):
    path = cells.REPO_ROOT / "benchmarks" / "chip" / "configs" / f"{name}.json"
    return shapes.from_config(json.loads(path.read_text()))


QWEN = "qwen3-1.7b"
MAMBA = "mamba2-370m"


def test_qwen3_params_and_flops_per_token():
    s = shape(QWEN)
    total = counts.non_embedding_params(s) + counts.head_params(s)
    assert total == pytest.approx(1.72e9, rel=0.01)
    attention = 28 * 4 * 16 * 128 * 700
    assert counts.decode_token_flops(s, 700) == 2 * total + attention
    # the LM head is about 18% of a token's matmul FLOPs
    assert counts.head_params(s) / total == pytest.approx(0.18, abs=0.01)


def test_qwen3_decode_bytes_are_weights_plus_live_kv():
    s = shape(QWEN)
    assert counts.kv_bytes_per_token(s) == 114_688
    w = counts.weight_read_bytes(s)
    assert w == pytest.approx(3.44e9, rel=0.01)
    ctxs = [100, 700, 2000]
    assert counts.decode_step_bytes(s, ctxs) == w + 2800 * 114_688


def test_mamba2_decode_bytes_are_weights_plus_state_both_ways():
    s = shape(MAMBA)
    assert counts.non_embedding_params(s) + counts.head_params(s) == \
        pytest.approx(368e6, rel=0.01)
    state = counts.state_bytes_per_sequence(s)
    assert state == 48 * 32 * 128 * 64 * 4 + 48 * 3 * (2048 + 256) * 2
    w = counts.weight_read_bytes(s)
    assert counts.decode_step_bytes(s, [5, 9, 1000]) == w + 2 * 3 * state
    # the context length does not change a state-space step
    assert counts.decode_token_flops(s, 10) == counts.decode_token_flops(s, 999)


def test_prefill_counts_the_head_once_and_causal_attention():
    s = shape(QWEN)
    t = 256
    dense = 2 * counts.non_embedding_params(s) * t
    attn = 28 * 4 * 16 * 128 * t * (t + 1) // 2
    assert counts.prefill_flops(s, t) == dense + 2 * counts.head_params(s) + attn


def test_weight_bytes_follow_the_served_types():
    s = shape(MAMBA)
    fp32 = 3 * 48 * 32                      # A_log, D, dt_bias per layer
    n_params = sum(math.prod(sh) for _, sh, *_ in weights.leaves(s))
    assert weights.nbytes(s) == 2 * n_params + 2 * fp32


def test_peaks_by_device_kind():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")
