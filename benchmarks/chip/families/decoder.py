"""Dense decoder with a tied LM head (Qwen3-style: GQA, qk-norm, RoPE,
SwiGLU), as the program's ``family: dense`` serves it.

Reads a Hugging Face ``config.json`` (``configs/<name>.json`` ``config``)
and the file's ``architecture`` (``qk_norm``).  The reference is
``chip.reference.decoder``.
"""
from __future__ import annotations

import dataclasses
from typing import List

from chip import shapes, weights
from chip.layer import Step
from chip.reference import F32, decoder
from chip.weights import BF16, Leaf

KV_BYTES = 2                    # served KV cache: bfloat16


@dataclasses.dataclass(frozen=True)
class Shape(shapes.Shape):
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    qk_norm: bool


def sizes(config: dict) -> dict:
    """``Shape`` fields of a decoder configuration file, but ``family``."""
    cfg, arch = config["config"], config.get("architecture", {})
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"decoder reference has SwiGLU only, config says "
                         f"hidden_act={cfg['hidden_act']}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        layers=cfg["num_hidden_layers"], d_model=d, vocab=cfg["vocab_size"],
        norm_eps=float(cfg["rms_norm_eps"]), heads=h,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // h,
        d_ff=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        qk_norm=bool(arch.get("qk_norm", False)))


def shape(config: dict) -> Shape:
    if not config["config"].get("tie_word_embeddings", False):
        raise ValueError("decoder reference reads a tied LM head")
    return Shape(family="decoder", **sizes(config))


def program_sizes(s: Shape) -> dict:
    return dict(shapes.common_sizes(s), tie_embeddings=True,
                n_heads=s.heads, n_kv_heads=s.kv_heads, head_dim_=s.head_dim,
                d_ff=s.d_ff, rope_theta=s.rope_theta, qk_norm=s.qk_norm,
                act="swiglu", norm="rmsnorm", pos_embed="rope",
                attn_bias=False, sliding_window=0, moe=None, mla=None)


def leaves(s: Shape) -> List[Leaf]:
    L, d, hd = s.layers, s.d_model, s.head_dim
    q, kv = s.heads * hd, s.kv_heads * hd
    blk = ("stack", "dense_stack")
    leaves = [
        (blk + ("ln1", "scale"), (L, d), BF16, "norm", 0.0),
        (blk + ("ln2", "scale"), (L, d), BF16, "norm", 0.0),
        (blk + ("attn", "wq", "w"), (L, d, q), BF16, "normal", d ** -0.5),
        (blk + ("attn", "wk", "w"), (L, d, kv), BF16, "normal", d ** -0.5),
        (blk + ("attn", "wv", "w"), (L, d, kv), BF16, "normal", d ** -0.5),
        (blk + ("attn", "wo", "w"), (L, q, d), BF16, "normal", q ** -0.5),
        (blk + ("mlp", "wi", "w"), (L, d, s.d_ff), BF16, "normal", d ** -0.5),
        (blk + ("mlp", "wg", "w"), (L, d, s.d_ff), BF16, "normal", d ** -0.5),
        (blk + ("mlp", "wo", "w"), (L, s.d_ff, d), BF16, "normal",
         s.d_ff ** -0.5),
    ]
    if s.qk_norm:
        leaves += [(blk + ("attn", "qnorm", "scale"), (L, hd), BF16, "norm", 0.),
                   (blk + ("attn", "knorm", "scale"), (L, hd), BF16, "norm", 0.)]
    return leaves + weights.final_norm_and_embed(s)


hidden = decoder.hidden


def head(params, s: Shape):
    return params["embed"]["table"][:s.vocab].astype(F32)


# -- counts ----------------------------------------------------------------

def layer_params(s: Shape) -> int:
    """Parameters of one layer (its norms included)."""
    d = s.d_model
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    attn = d * q + 2 * d * kv + q * d + (2 * s.head_dim if s.qk_norm else 0)
    return attn + 3 * d * s.d_ff + 2 * d


def non_embedding_params(s: Shape) -> int:
    return s.layers * layer_params(s) + s.d_model       # + final norm


def head_params(s: Shape) -> int:
    return s.vocab * s.d_model


def weight_read_bytes(s: Shape) -> int:
    """Weights a decode step reads where the LM head is the tied embedding
    table: every served leaf but the table, plus the table's rows over the
    real vocabulary (bfloat16)."""
    table = s.vocab_rows * s.d_model * 2
    return weights.nbytes(s) - table + head_params(s) * 2


def attention_flops(s: Shape, ctx: int) -> int:
    """Per-token FLOPs of attention over ``ctx`` positions (QK and PV)."""
    return s.layers * 4 * s.heads * s.head_dim * ctx


def decode_token_flops(s: Shape, ctx: int) -> int:
    """2 x (non-embedding params + LM head) plus attention."""
    return 2 * (non_embedding_params(s) + head_params(s)) \
        + attention_flops(s, ctx)


def prefill_flops(s: Shape, tokens: int) -> int:
    """Every position through the layers, causal attention, and the LM head
    at the last position only."""
    mixer = s.layers * 4 * s.heads * s.head_dim * tokens * (tokens + 1) // 2
    return 2 * non_embedding_params(s) * tokens + 2 * head_params(s) + mixer


def kv_bytes_per_token(s: Shape) -> int:
    return 2 * s.layers * s.kv_heads * s.head_dim * KV_BYTES


def decode_step_bytes(s: Shape, step: Step) -> int:
    """The weights once, then the live keys and values read (the new
    token's written): sequence ``i`` attends over ``step.ctxs[i]``."""
    return weight_read_bytes(s) + sum(step.ctxs) * kv_bytes_per_token(s)
