"""DeepSeek-V2 236B [arXiv:2405.04434; hf] — MLA (kv_lora=512) + 2 shared / 160 routed top-6."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,           # MLA: per-assignment annotation; realized via compressed KV
    d_ff=1536,                # routed-expert hidden
    vocab=102400,
    mlp_gated=True,
    act="silu",
    qkv_bias=False,
    rope_theta=1e4,
    rope_scaling="yarn",
    rope_factor=40.0,
    rope_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm="rmsnorm",
    norm_eps=1e-6,
    # MoE
    n_experts=160,
    n_shared_experts=2,
    top_k=6,
    d_ff_expert=1536,
    first_k_dense=1,
    d_ff_dense=12288,
    capacity_factor=1.25,
    n_group=8,                # group_limited_greedy: top-3 of 8 groups
    topk_group=3,
    norm_topk_prob=False,     # softmax scores, unnormalized, x 16
    routed_scaling_factor=16.0,
    # MLA
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=1536,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
    source="arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2",
)
