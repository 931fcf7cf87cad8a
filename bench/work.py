"""Operations and bytes that the work of a call needs, from the published
shapes alone, so that a share of a roofline or of a peak reads the same
work whatever implements it. Multiply-adds count two operations. Weights,
activations and caches count at bfloat16 (2 bytes) unless said otherwise.

`s` is a configuration's `shapes` (bench/configs/*.json). Dense model:
vocab, d_model, n_layers, n_heads, n_kv_heads, head_dim, d_ff. Hybrid
model: vocab, d_model, n_layers, n_heads, head_dim, d_ff, d_state,
ssm_head_dim, expand, n_groups, conv_width, attn_every.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


# -- dense decoder ------------------------------------------------------------

def dense_layer_params(s) -> int:
    D, H, Hk, Dh, F = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                       s["head_dim"], s["d_ff"])
    return D * (H + 2 * Hk) * Dh + H * Dh * D + 3 * D * F + 2 * D


def dense_head_params(s) -> int:
    return s["d_model"] * s["vocab"] + s["d_model"]


def kv_bytes_per_token(s, kv_heads=None) -> int:
    """K and V of one token over all layers."""
    hk = s["n_kv_heads"] if kv_heads is None else kv_heads
    return s["n_layers"] * 2 * hk * s["head_dim"] * BF16


def dense_decode_step(s, batch: int, context: int) -> tuple[float, float]:
    """(flops, bytes) of one decode step of `batch` rows whose new token
    attends to `context` positions (itself included)."""
    L, H, Dh = s["n_layers"], s["n_heads"], s["head_dim"]
    w = L * dense_layer_params(s) + dense_head_params(s)
    flops = batch * (2 * w + 4 * L * H * Dh * context)
    nbytes = (w * BF16 + batch * kv_bytes_per_token(s) * context
              + batch * s["d_model"] * BF16)
    return float(flops), float(nbytes)


def decode_attention_call(s, batch: int, context: int) -> tuple[float, float]:
    """(flops, bytes) of one layer's decode attention over `context`
    cached positions."""
    H, Hk, Dh = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    flops = 4 * batch * H * Dh * context
    nbytes = (batch * context * 2 * Hk * Dh + 2 * batch * H * Dh) * BF16
    return float(flops), float(nbytes)


# -- Mamba2 + shared attention -------------------------------------------------

def mamba_layer_params(s) -> int:
    D, N, G, W = s["d_model"], s["d_state"], s["n_groups"], s["conv_width"]
    Din = s["expand"] * D
    Hs = Din // s["ssm_head_dim"]
    proj = D * (2 * Din + 2 * G * N + Hs) + Din * D
    conv = (W + 1) * (Din + 2 * G * N)
    return proj + conv + 3 * Hs + Din + D


def shared_block_params(s) -> int:
    D, H, Dh, F = s["d_model"], s["n_heads"], s["head_dim"], s["d_ff"]
    return 4 * D * H * Dh + 3 * D * F + 2 * D


def shared_uses(s) -> int:
    return s["n_layers"] // s["attn_every"]


def ssd_flops_per_token(s) -> float:
    """The SSD recurrence per token of one layer: the state update
    (decay, outer product, add: 3 N P a head) and the read-out (2 N P)."""
    Hs = s["expand"] * s["d_model"] // s["ssm_head_dim"]
    return float(5 * Hs * s["d_state"] * s["ssm_head_dim"])


def hybrid_train_step(s, batch: int, length: int) -> float:
    """Model flops of one training step (forward and backward, three times
    the forward; nothing recomputed counts)."""
    tokens = batch * length
    matmul_params = (s["n_layers"] * mamba_layer_params(s)
                     + shared_uses(s) * shared_block_params(s)
                     + s["d_model"] * s["vocab"])
    fwd = (2 * matmul_params * tokens
           + s["n_layers"] * ssd_flops_per_token(s) * tokens
           + shared_uses(s) * 2 * batch * s["n_heads"] * s["head_dim"]
           * length * length)
    return float(3 * fwd)


def mamba2_ssd_call(s, batch: int, length: int) -> tuple[float, float]:
    """(flops, bytes) of one layer's SSD scan: x (bf16), dt (f32), B and C
    (bf16) read; y (bf16) and the final f32 state written."""
    Din = s["expand"] * s["d_model"]
    Hs = Din // s["ssm_head_dim"]
    GN = s["n_groups"] * s["d_state"]
    tokens = batch * length
    flops = ssd_flops_per_token(s) * tokens
    nbytes = (tokens * (Din * BF16 + Hs * F32 + 2 * GN * BF16 + Din * BF16)
              + batch * Hs * s["d_state"] * s["ssm_head_dim"] * F32)
    return float(flops), float(nbytes)
