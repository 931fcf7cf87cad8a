"""Operations and bytes of a DeepSeek-V3-block model's decode (latent
attention, held routed experts, shared experts), from the published shapes
alone (bench/configs/moonlight_16b_a3b.json), as bench/work.py counts them
for the dense and hybrid models: multiply-adds count two operations;
weights, activations and caches count at bfloat16 (2 bytes).

`s` is the configuration's `shapes`: vocab, d_model, n_layers,
n_dense_layers, n_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, d_ff, moe_d_ff, n_experts, n_shared_experts.
"""

from __future__ import annotations

from bench.work import BF16


def latent_width(s) -> int:
    """Values a position holds in one layer's latent cache: c_kv and the
    shared rope key."""
    return s["kv_lora_rank"] + s["qk_rope_head_dim"]


def attention_params(s) -> int:
    """One latent-attention block with its two RMSNorms: W_q, W_kv_a, the
    latent norm, W_kv_b, W_o."""
    D, H, R = s["d_model"], s["n_heads"], s["kv_lora_rank"]
    N, Dr, Dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    return (D * H * (N + Dr) + D * (R + Dr) + R + R * H * (N + Dv)
            + H * Dv * D + 2 * D)


def expert_params(s) -> int:
    return 3 * s["d_model"] * s["moe_d_ff"]


def fixed_params(s) -> int:
    """What every decode step reads whatever the routing: every layer's
    attention, the dense layers' SwiGLU, each MoE layer's router,
    correction bias and shared experts, the final norm and the head (the
    embedding is gathered, a row a token)."""
    D, L, Ld = s["d_model"], s["n_layers"], s["n_dense_layers"]
    moe = (s["n_shared_experts"] * expert_params(s)
           + (D + 1) * s["n_experts"])
    return (L * attention_params(s) + Ld * 3 * D * s["d_ff"]
            + (L - Ld) * moe + D * s["vocab"] + D)


def mla_decode_call(s, batch: int, context: int) -> tuple[float, float]:
    """(flops, bytes) of one layer's absorbed latent-attention decode over
    `context` cached positions: each head's 576-wide query against the
    latent cache (R + Dr a position) and the 512-wide weighted sum; the
    rows' cache read once, queries and outputs."""
    H, R = s["n_heads"], s["kv_lora_rank"]
    W = latent_width(s)
    flops = 2 * batch * H * context * (W + R)
    nbytes = (batch * context * W + batch * H * (W + R)) * BF16
    return float(flops), float(nbytes)


def decode_step(s, batch: int, context: int, experts_hit: float,
                pairs_held: float) -> tuple[float, float]:
    """(flops, bytes) of one decode step of `batch` rows whose new token
    attends to `context` positions, given the step's held experts hit and
    (token, held expert) pairs computed, each summed over the MoE layers.
    A held expert that no token picked is not read."""
    w = fixed_params(s)
    H, R, L = s["n_heads"], s["kv_lora_rank"], s["n_layers"]
    flops = (2 * batch * w + 2 * pairs_held * expert_params(s)
             + L * 2 * batch * H * context * (latent_width(s) + R))
    nbytes = ((w + experts_hit * expert_params(s)) * BF16
              + batch * L * latent_width(s) * context * BF16
              + batch * s["d_model"] * BF16)
    return float(flops), float(nbytes)
