"""deepseek-v3-671b [moe] — 61L d_model=7168 128H d_ff=2048(expert)
vocab=129280; MLA, 1 shared + 256 routed top-8, MTP.  [arXiv:2412.19437; hf]

d_ff=18432 is the dense-layer (first 3 layers) intermediate size; the
assigned d_ff=2048 is the per-expert intermediate.  Memory policy: bf16
params, int8 blockwise Adam moments + factored v, 8 microbatches — the
671B config must fit 256 x 16 GB on the single-pod mesh (EXPERIMENTS.md).
"""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, Policy, register

DEEPSEEK_V3_671B = register(ArchConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,
    vocab_size=129280,
    act="swiglu",
    rope_theta=1e4,
    moe=MoEConfig(num_experts=256, top_k=8, d_ff_expert=2048, n_shared=1,
                  first_dense_layers=3, capacity_factor=1.25,
                  sharding="ep"),
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
                  qk_rope_dim=64, v_head_dim=128),
    mtp=True,
    policy=Policy(param_dtype="bfloat16", compute_dtype="bfloat16",
                  fsdp=True, sp=True, microbatches=4, moment_dtype="int8",
                  remat_policy="save_collectives",
                  factored_v=True),
    source="arXiv:2412.19437",
))
