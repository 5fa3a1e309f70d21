"""Architecture configuration system.

Every assigned architecture is an :class:`ArchConfig`; the full configs live
in sibling modules (one per arch) and register themselves in :data:`ARCHS`.
``reduced()`` returns the family-preserving smoke-test variant; the full
configs are only ever lowered via ShapeDtypeStructs (dry-run), never
allocated on this host.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
from jax import ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0               # shared (always-on) experts
    first_dense_layers: int = 0     # leading dense layers (deepseek: 3)
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    sharding: str = "ep"            # "ep": experts on model axis; "tp": inside-expert


@dataclasses.dataclass(frozen=True)
class MLAConfig:                    # DeepSeek-V3 multi-head latent attention
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:                    # Mamba2 / SSD
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64              # P
    n_groups: int = 1               # B/C groups (GQA-like)
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class HybridConfig:                 # Zamba2: shared attention block
    shared_every: int = 6           # apply the shared block every N ssm blocks
    n_shared_blocks: int = 2        # distinct shared blocks, used round-robin
    lora_rank: int = 64             # per-application LoRA on the shared block


#: tensor-parallel modes whose mesh posture is output-dim sharding with
#: no partial sums across 'model' (the paper's reduction-free dataflow;
#: 'ame_pim' shares it and adds the PIM cluster stack map) — consulted by
#: sharding.rules and models.layers so the two cannot drift
OUTPUT_SHARDED_TP_MODES = ("allgather", "ame_pim")


@dataclasses.dataclass(frozen=True)
class Policy:
    """Numerics + distribution policy (per arch, overridable per run)."""

    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: bool = True
    fsdp: bool = False              # shard params/opt-state over 'data' too
    microbatches: int = 1           # grad-accumulation splits of the batch
    moment_dtype: str = "float32"   # adam m/v dtype: float32|bfloat16|int8
    factored_v: bool = False        # adafactor-style factored second moment
    sp: bool = False                # sequence-parallel residual stream
    remat_policy: str = "full"      # full | dots (save matmul outputs)
    tp_mode: str = "allreduce"      # 'allreduce' (megatron) | 'allgather'
                                    # ('allgather' = the paper's reduction-free
                                    #  dataflow at mesh level, DESIGN.md §3)
                                    # | 'ame_pim' (allgather specs + PIM
                                    #  cluster stack map, sharding.rules)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense|moe|vlm|hybrid|ssm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # explicit (gemma: 256); default dm/heads
    act: str = "swiglu"             # swiglu|geglu|gelu
    norm: str = "rmsnorm"           # rmsnorm|layernorm
    pos_embed: str = "rope"         # rope|learned
    qk_norm: bool = False
    rope_theta: float = 1e6
    attn_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    sliding_window: int = 0         # >0: SWA (mixtral)
    encoder_only: bool = False      # hubert
    modality: str = "text"          # text|vision_text|audio_frames
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    mtp: bool = False               # multi-token-prediction aux head
    policy: Policy = dataclasses.field(default_factory=Policy)
    source: str = ""                # provenance note

    # -- derived -----------------------------------------------------------

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up so the 'model' axis (16) always divides it."""
        return -(-self.vocab_size // 256) * 256

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def quadratic_attention(self) -> bool:
        """True if the arch has no sub-quadratic path for 500k context."""
        if self.family in ("ssm",):
            return False
        if self.hybrid is not None:
            return False            # mamba backbone + sparse shared attn
        return self.sliding_window == 0

    def compute_dtype_(self):
        return jnp.bfloat16 if self.policy.compute_dtype == "bfloat16" else jnp.float32

    def param_dtype_(self):
        return jnp.bfloat16 if self.policy.param_dtype == "bfloat16" else jnp.float32

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def with_policy(self, **kw) -> "ArchConfig":
        return self.replace(policy=dataclasses.replace(self.policy, **kw))

    # -- smoke-test variant --------------------------------------------------

    def reduced(self) -> "ArchConfig":
        """Family-preserving tiny variant for CPU smoke tests."""
        kw = dict(
            n_layers=min(self.n_layers, 4 if self.hybrid is None else 7),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads > 1 else 1,
            d_ff=256,
            vocab_size=512,
            head_dim=32 if self.head_dim else None,
            sliding_window=min(self.sliding_window, 16) if self.sliding_window else 0,
        )
        if self.moe:
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=4, top_k=2, d_ff_expert=64,
                n_shared=min(self.moe.n_shared, 1),
                first_dense_layers=min(self.moe.first_dense_layers, 1))
        if self.mla:
            kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=32,
                                  qk_nope_dim=16, qk_rope_dim=16,
                                  v_head_dim=32)
            kw["head_dim"] = None
        if self.ssm:
            kw["ssm"] = dataclasses.replace(self.ssm, d_state=16, head_dim=16,
                                            chunk=32)
        if self.hybrid:
            kw["hybrid"] = dataclasses.replace(self.hybrid, shared_every=3,
                                               lora_rank=8)
        kw["policy"] = dataclasses.replace(
            self.policy, param_dtype="float32", compute_dtype="float32",
            microbatches=1, fsdp=False)
        return self.replace(**kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned grid)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train|prefill|decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def applicable(cfg: ArchConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Whether this (arch x shape) cell runs, and why not if skipped."""
    if cfg.encoder_only and shape.kind == "decode":
        return False, "encoder-only arch has no decode step"
    if shape.name == "long_500k" and cfg.quadratic_attention:
        return False, "full quadratic attention at 500k context"
    return True, ""


def input_specs(cfg: ArchConfig, shape: ShapeSpec,
                reduced: bool = False) -> Dict[str, ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    Modality frontends are stubs per the brief: vision supplies precomputed
    patch embeddings, audio supplies precomputed frame embeddings.
    """
    b, t = shape.global_batch, shape.seq_len
    if reduced:
        b, t = min(b, 2), min(t, 64)
    i32, f = jnp.int32, cfg.compute_dtype_()
    d = cfg.d_model
    if shape.kind == "train":
        if cfg.modality == "audio_frames":
            return {
                "frames": ShapeDtypeStruct((b, t, d), f),
                "mask": ShapeDtypeStruct((b, t), jnp.bool_),
                "targets": ShapeDtypeStruct((b, t), i32),
            }
        out = {
            "tokens": ShapeDtypeStruct((b, t), i32),
            "targets": ShapeDtypeStruct((b, t), i32),
            "loss_mask": ShapeDtypeStruct((b, t), f),
        }
        if cfg.modality == "vision_text":
            npatch = max(t // 4, 16)
            tt = t - npatch
            out["vision_embeds"] = ShapeDtypeStruct((b, npatch, d), f)
            out["tokens"] = ShapeDtypeStruct((b, tt), i32)
            out["targets"] = ShapeDtypeStruct((b, tt), i32)
            out["loss_mask"] = ShapeDtypeStruct((b, tt), f)
        return out
    if shape.kind == "prefill":
        if cfg.modality == "audio_frames":
            return {"frames": ShapeDtypeStruct((b, t, d), f)}
        out = {"tokens": ShapeDtypeStruct((b, t), i32)}
        if cfg.modality == "vision_text":
            npatch = max(t // 4, 16)
            out["vision_embeds"] = ShapeDtypeStruct((b, npatch, d), f)
            out["tokens"] = ShapeDtypeStruct((b, t - npatch), i32)
        return out
    # decode: one new token against a cache of length t
    return {"tokens": ShapeDtypeStruct((b, 1), i32),
            "positions": ShapeDtypeStruct((b,), i32)}


#: registry, populated by the per-arch modules
ARCHS: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    ARCHS[cfg.name] = cfg
    return cfg


def get(name: str) -> ArchConfig:
    import repro.configs  # noqa: F401  (triggers per-arch registration)
    return ARCHS[name]


def all_names():
    import repro.configs  # noqa: F401
    return sorted(ARCHS)
