"""moonshot-v1-16b-a3b [moe] — a generic GQA, softmax-routed MoE at
Moonlight-like widths, used by the CPU tests of the capacity-buffer expert
path (``moe.moe_apply``).  It is NOT Moonlight-16B-A3B: no latent attention,
no shared experts, no sigmoid routing, no leading dense layer, and 48
layers; the published model is ``moonlight_16b_a3b.py``.
48L d_model=2048 16H (GQA kv=16) d_ff=1408 (per expert) vocab=163840,
MoE 64e top-6.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=1408,
    vocab_size=163_840,
    n_experts=64,
    experts_per_token=6,
)
