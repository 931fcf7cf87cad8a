"""Moonlight-16B-A3B: the DeepSeek-V3 block — multi-head latent attention
and a 64-expert MoE with shared experts [hf:moonshotai/Moonlight-16B-A3B]

CONFIG is the published config.json at every width, on one chip's share of
an 8-way expert-parallel deployment: this chip holds routed experts 0-7 of
64 (the router still scores all 64); attention, the shared experts, the
leading dense layer, the embedding and the head are replicated. TINY is the
reduced same-family variant for CPU tests (1 dense + 2 MoE layers, 4 of 8
experts held, top-2).
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="mla_moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=11264, vocab_size=163840,
    rope_theta=50000.0, norm_eps=1e-5,
    n_experts=64, experts_per_token=6,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_dense_layers=1, moe_d_ff=1408, n_shared_experts=2,
    routed_scaling_factor=2.446, first_held_expert=0, n_held_experts=8)

TINY = ModelConfig(
    name="moonlight-tiny", family="mla_moe", n_layers=3, d_model=128,
    n_heads=4, n_kv_heads=4, d_ff=256, vocab_size=512,
    rope_theta=50000.0, norm_eps=1e-5,
    n_experts=8, experts_per_token=2,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
    v_head_dim=16, n_dense_layers=1, moe_d_ff=64, n_shared_experts=2,
    routed_scaling_factor=2.446, first_held_expert=0, n_held_experts=4,
    tp=1)
