"""Operations and bytes a step needs, from the sizes and the live lengths.

These count what the algorithm needs, whatever implements the step: the
FLOPs of the matrix products with the real vocabulary (padding rows are not
needed), attention over the live context only, and the bytes of the weights
in their served type plus the live cache or state.  A roofline share or an
``mfu`` divides these by measured device time and the chip's peaks, so a
step that reads more than it needs shows as a lower share.

Each family counts for itself (``chip.family``); the functions here hand a
shape to its family.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

from chip import family
from chip.shapes import Shape

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def non_embedding_params(s: Shape) -> int:
    """Parameters of the layers and the final norm."""
    return family.of(s).non_embedding_params(s)


def head_params(s: Shape) -> int:
    """Parameters of the LM head over the real vocabulary."""
    return family.of(s).head_params(s)


def weight_read_bytes(s: Shape) -> int:
    """Weights a decode step must read."""
    return family.of(s).weight_read_bytes(s)


def decode_token_flops(s: Shape, ctx: int) -> int:
    """One decoded token that attends over ``ctx`` positions (itself
    included)."""
    return family.of(s).decode_token_flops(s, ctx)


def prefill_flops(s: Shape, tokens: int) -> int:
    """A prompt of ``tokens``, with the LM head at its last position only."""
    return family.of(s).prefill_flops(s, tokens)


def decode_step_bytes(s: Shape, step) -> int:
    """Bytes one decode step needs: the weights once, then the cache or
    state of the live sequences.  ``step`` is the harness's ``layer.Step``.
    A bare list of contexts, a step that admitted nothing, is taken only
    for ``tests/test_counts.py``, which predates the family files."""
    if not hasattr(step, "ctxs"):
        from chip.layer import Step     # layer imports this module
        step = Step(0.0, 0.0, (), tuple(step))
    return family.of(s).decode_step_bytes(s, step)


# Each of these two is one family's own; they stay here only for
# ``tests/test_counts.py``, which predates the family files.

def kv_bytes_per_token(s: Shape) -> int:
    """KV-cache bytes of one position, for a family with attention."""
    return family.of(s).kv_bytes_per_token(s)


def state_bytes_per_sequence(s: Shape) -> int:
    """Recurrent state bytes of one sequence, for a state-space family."""
    return family.of(s).state_bytes_per_sequence(s)
