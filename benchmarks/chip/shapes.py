"""Model sizes read from a configuration file's published ``config``.

The benchmark's own view of a model: the weights, the counts and the
reference read these sizes, never the program's registry.  One reader per
family; a configuration file names its family.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Shape:
    family: str                 # "decoder" | "mamba2"
    layers: int
    d_model: int
    vocab: int
    norm_eps: float
    # decoder
    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    rope_theta: float = 0.0
    qk_norm: bool = False
    # mamba2
    d_state: int = 0
    d_conv: int = 0
    expand: int = 0
    ssm_head_dim: int = 0
    n_groups: int = 0

    @property
    def vocab_rows(self) -> int:
        """Rows of the stored embedding table: the program pads the vocabulary
        to a multiple of 256 and masks the padded logits."""
        return -(-self.vocab // 256) * 256

    # -- mamba2 derived sizes --------------------------------------------
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


def _decoder(cfg: dict, arch: dict) -> Shape:
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"decoder reference has SwiGLU only, config says "
                         f"hidden_act={cfg['hidden_act']}")
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("decoder reference reads a tied LM head")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Shape(
        family="decoder", layers=cfg["num_hidden_layers"], d_model=d,
        vocab=cfg["vocab_size"], norm_eps=float(cfg["rms_norm_eps"]),
        heads=h, kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // h,
        d_ff=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        qk_norm=bool(arch.get("qk_norm", False)))


def _mamba2(cfg: dict, assumed: dict) -> Shape:
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


def from_config(config: dict) -> Shape:
    """Sizes of a configuration file (``configs/<name>.json``)."""
    family = config["family"]
    if family == "decoder":
        return _decoder(config["config"], config.get("architecture", {}))
    if family == "mamba2":
        return _mamba2(config["config"], config["assumed"])
    raise ValueError(f"no reader for model family {family!r}")


def program_sizes(shape: Shape) -> dict:
    """The program's ``ArchConfig`` attributes that must equal these sizes
    (dotted paths into nested configs)."""
    out = {"n_layers": shape.layers, "d_model": shape.d_model,
           "vocab_size": shape.vocab, "norm_eps": shape.norm_eps,
           "tie_embeddings": True}
    if shape.family == "decoder":
        out.update({"n_heads": shape.heads, "n_kv_heads": shape.kv_heads,
                    "head_dim_": shape.head_dim, "d_ff": shape.d_ff,
                    "rope_theta": shape.rope_theta, "qk_norm": shape.qk_norm,
                    "act": "swiglu", "norm": "rmsnorm", "pos_embed": "rope",
                    "attn_bias": False, "sliding_window": 0,
                    "moe": None, "mla": None})
    else:
        out.update({"ssm.d_state": shape.d_state, "ssm.d_conv": shape.d_conv,
                    "ssm.expand": shape.expand,
                    "ssm.head_dim": shape.ssm_head_dim,
                    "ssm.n_groups": shape.n_groups, "family": "ssm"})
    return out


def get_path(obj, path: str) -> Optional[object]:
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj
