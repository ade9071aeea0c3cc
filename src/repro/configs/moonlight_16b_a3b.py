"""moonlight-16b-a3b [moe] — DeepSeek-V3 block: latent attention (MLA) and
sigmoid-routed experts with shared experts.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]
27L d_model=2048 16H; MLA: q 2048→16×(128+64) (no q_lora), kv_a 2048→512+64,
kv_b 512→16×(128+128), v 128; layer 0 dense SwiGLU 11264, layers 1–26 MoE:
64 routed experts of 1408 (top-6, sigmoid + score-correction bias, no group
limit, normalized, ×2.446) and 2 shared (one SwiGLU of 2816); vocab=163840,
untied, rope_theta 50000, RMSNorm eps 1e-5.

The preset is one chip's share of an EP4 host: it holds routed experts
0–15 of every MoE layer (``experts_held``/``expert_offset``); the router
spans all 64.  Served over the paged latent pool only.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,  # qk_nope_head_dim + qk_rope_head_dim
    d_ff=1408,  # per routed expert
    vocab_size=163_840,
    rope_theta=50_000.0,
    attention="mla",
    mla_kv_rank=512,
    mla_nope_dim=128,
    mla_rope_dim=64,
    mla_v_dim=128,
    n_experts=64,
    experts_per_token=6,
    moe_router="sigmoid_bias",
    moe_routed_scaling=2.446,
    moe_shared_experts=2,
    experts_held=16,
    expert_offset=0,
    first_dense_layers=1,
    dense_d_ff=11_264,
)
