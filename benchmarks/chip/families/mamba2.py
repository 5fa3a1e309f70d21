"""Mamba2 stack with a tied LM head (arXiv:2405.21060, mamba_ssm
``Mamba2``), as the program's ``family: ssm`` serves it.

Reads a mamba_ssm ``config.json`` (``configs/<name>.json`` ``config``) and
the sizes it leaves to the library's defaults (``assumed``).  The reference
is ``chip.reference.mamba2``.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import List

from chip import family, shapes, weights
from chip.layer import Step
from chip.reference import mamba2
from chip.weights import BF16, Leaf

SSM_STATE_BYTES = 4             # recurrent state: float32
CONV_STATE_BYTES = 2            # conv window: bfloat16

# the tied LM head is the decoder family's: ``head``, ``head_params`` and
# ``weight_read_bytes`` below are its own
dec = family.load(Path(__file__).with_name("decoder.py"))


@dataclasses.dataclass(frozen=True)
class Shape(shapes.Shape):
    d_state: int
    d_conv: int
    expand: int
    ssm_head_dim: int
    n_groups: int

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        return 2 * self.d_inner + 2 * self.n_groups * self.d_state \
            + self.ssm_heads


def shape(config: dict) -> Shape:
    cfg, assumed = config["config"], config["assumed"]
    if not cfg.get("tie_embeddings", False):
        raise ValueError("mamba2 reference reads a tied LM head")
    if cfg.get("attn_layer_idx"):
        raise ValueError("mamba2 reference has no attention layers")
    return Shape(
        family="mamba2", layers=cfg["n_layer"], d_model=cfg["d_model"],
        vocab=cfg["vocab_size"], norm_eps=float(assumed["norm_epsilon"]),
        d_state=assumed["d_state"], d_conv=assumed["d_conv"],
        expand=assumed["expand"], ssm_head_dim=assumed["headdim"],
        n_groups=assumed["ngroups"])


def program_sizes(s: Shape) -> dict:
    return dict(shapes.common_sizes(s), tie_embeddings=True,
                **{"ssm.d_state": s.d_state, "ssm.d_conv": s.d_conv,
                   "ssm.expand": s.expand, "ssm.head_dim": s.ssm_head_dim,
                   "ssm.n_groups": s.n_groups, "family": "ssm"})


def leaves(s: Shape) -> List[Leaf]:
    L, d, H = s.layers, s.d_model, s.ssm_heads
    blk = ("stack", "ssm_stack")
    m = blk + ("mamba",)
    return [
        (blk + ("ln", "scale"), (L, d), BF16, "norm", 0.0),
        (m + ("in_proj", "w"), (L, d, s.in_proj_dim), BF16, "normal",
         d ** -0.5),
        (m + ("conv_w",), (L, s.d_conv, s.conv_dim), BF16, "normal",
         s.d_conv ** -0.5),
        (m + ("conv_b",), (L, s.conv_dim), BF16, "normal", 0.1),
        (m + ("a_log",), (L, H), weights.F32, "a_log", 0.0),
        (m + ("d_skip",), (L, H), weights.F32, "norm", 0.0),
        (m + ("dt_bias",), (L, H), weights.F32, "dt_bias", 0.0),
        (m + ("norm", "scale"), (L, s.d_inner), BF16, "norm", 0.0),
        (m + ("out_proj", "w"), (L, s.d_inner, d), BF16, "normal",
         s.d_inner ** -0.5),
    ] + weights.final_norm_and_embed(s)


hidden = mamba2.hidden
head = dec.head


# -- counts ----------------------------------------------------------------

def layer_params(s: Shape) -> int:
    """Parameters of one layer (its norms included)."""
    d, h = s.d_model, s.ssm_heads
    return (d * s.in_proj_dim + (s.d_conv + 1) * s.conv_dim + 3 * h
            + s.d_inner + s.d_inner * d + d)


def non_embedding_params(s: Shape) -> int:
    return s.layers * layer_params(s) + s.d_model       # + final norm


head_params = dec.head_params
weight_read_bytes = dec.weight_read_bytes


def state_flops(s: Shape) -> int:
    """Per-token FLOPs of the state update (decay, outer product, add) and
    its read-out; the context length does not enter."""
    return s.layers * 5 * s.ssm_heads * s.d_state * s.ssm_head_dim


def decode_token_flops(s: Shape, ctx: int) -> int:
    """2 x (non-embedding params + LM head) plus the state update."""
    return 2 * (non_embedding_params(s) + head_params(s)) + state_flops(s)


def prefill_flops(s: Shape, tokens: int) -> int:
    """Every position through the layers, and the LM head at the last
    position only."""
    return 2 * non_embedding_params(s) * tokens + 2 * head_params(s) \
        + tokens * state_flops(s)


def state_bytes_per_sequence(s: Shape) -> int:
    ssm = s.layers * s.ssm_heads * s.d_state * s.ssm_head_dim * SSM_STATE_BYTES
    conv = s.layers * (s.d_conv - 1) * s.conv_dim * CONV_STATE_BYTES
    return ssm + conv


def decode_step_bytes(s: Shape, step: Step) -> int:
    """The weights once, then each live sequence's state read and
    written."""
    return weight_read_bytes(s) + 2 * len(step.ctxs) * state_bytes_per_sequence(s)
