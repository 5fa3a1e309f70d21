"""Operations and bytes a step needs, from the sizes and the live lengths.

These count what the algorithm needs, whatever implements the step: the
FLOPs of the matrix products with the real vocabulary (padding rows are not
needed), attention over the live context only, and the bytes of the weights
in their served type plus the live cache or state.  A roofline share or an
``mfu`` divides these by measured device time and the chip's peaks, so a
step that reads more than it needs shows as a lower share.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

from chip import weights
from chip.shapes import Shape

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

KV_BYTES = 2                    # served KV cache: bfloat16
SSM_STATE_BYTES = 4             # recurrent state: float32
CONV_STATE_BYTES = 2            # conv window: bfloat16


def peaks(device_kind: str) -> Dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def layer_params(s: Shape) -> int:
    """Parameters of one layer (its norms included)."""
    d = s.d_model
    if s.family == "decoder":
        q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
        attn = d * q + 2 * d * kv + q * d + (2 * s.head_dim if s.qk_norm else 0)
        return attn + 3 * d * s.d_ff + 2 * d
    h = s.ssm_heads
    return (d * s.in_proj_dim + (s.d_conv + 1) * s.conv_dim + 3 * h
            + s.d_inner + s.d_inner * d + d)


def non_embedding_params(s: Shape) -> int:
    return s.layers * layer_params(s) + s.d_model       # + final norm


def head_params(s: Shape) -> int:
    return s.vocab * s.d_model


def _mixer_flops(s: Shape, ctx: int) -> int:
    """Per-token FLOPs beyond the weight matmuls: attention over ``ctx``
    positions (QK and PV), or the SSM state update (decay, outer product,
    add) and read-out."""
    if s.family == "decoder":
        return s.layers * 4 * s.heads * s.head_dim * ctx
    return s.layers * 5 * s.ssm_heads * s.d_state * s.ssm_head_dim


def decode_token_flops(s: Shape, ctx: int) -> int:
    """One decoded token that attends over ``ctx`` positions (itself
    included): 2 x (non-embedding params + LM head) plus the mixer."""
    return 2 * (non_embedding_params(s) + head_params(s)) + _mixer_flops(s, ctx)


def prefill_flops(s: Shape, tokens: int) -> int:
    """A prompt of ``tokens``: every position through the layers, causal
    attention, and the LM head at the last position only."""
    mixer = (s.layers * 4 * s.heads * s.head_dim * tokens * (tokens + 1) // 2
             if s.family == "decoder" else tokens * _mixer_flops(s, 0))
    return 2 * non_embedding_params(s) * tokens + 2 * head_params(s) + mixer


def weight_read_bytes(s: Shape) -> int:
    """Weights a decode step must read: every served leaf but the embedding
    table, plus the LM head over the real vocabulary (the tied table)."""
    table = s.vocab_rows * s.d_model * 2
    return weights.nbytes(s) - table + head_params(s) * 2


def kv_bytes_per_token(s: Shape) -> int:
    return 2 * s.layers * s.kv_heads * s.head_dim * KV_BYTES


def state_bytes_per_sequence(s: Shape) -> int:
    ssm = s.layers * s.ssm_heads * s.d_state * s.ssm_head_dim * SSM_STATE_BYTES
    conv = s.layers * (s.d_conv - 1) * s.conv_dim * CONV_STATE_BYTES
    return ssm + conv


def decode_step_bytes(s: Shape, ctxs: Iterable[int]) -> int:
    """Bytes one decode step needs over the live sequences, where sequence
    ``i`` attends over ``ctxs[i]`` positions: the weights once, then the
    live keys and values read (the new token's written), or each live
    sequence's state read and written."""
    ctxs = list(ctxs)
    if s.family == "decoder":
        return weight_read_bytes(s) + sum(ctxs) * kv_bytes_per_token(s)
    return weight_read_bytes(s) + 2 * len(ctxs) * state_bytes_per_sequence(s)
