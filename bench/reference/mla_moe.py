"""Plain f32 forward of a DeepSeek-V3-block decoder (Moonlight-16B-A3B as
this repo builds it), in the expanded form: token embedding; per layer
RMSNorm, multi-head latent attention, residual, RMSNorm, then the leading
layers' SwiGLU or the MoE layer, residual; final RMSNorm and an untied
output head.

Latent attention: q = h W_q split per head into a no-rope part and a rope
part; [c | k_pe] = h W_kv_a, c RMS-normed, k_pe one rope key shared by all
heads; W_kv_b maps c to each head's no-rope key and its value; causal
softmax attention over [no-rope | rope] at scale 1/sqrt(width), then W_o.
Rotary embeddings in the halves convention.

MoE layer: sigmoid scores of all routed experts; the top k of score plus
correction bias; each pick weighted by its score over the picks' sum, times
the routed scaling factor. Of the routed experts only the held ones (the
chip's share, `first_held_expert` on) are computed, each over every token
and weighed by its routing weight there (zero where not picked); the shared
experts are added for every token.

Weights come in `bench.weights_mla`'s layout. The layers run in two scans
that widen one layer's bfloat16 weights to f32 at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import F32, causal_attention, mm, rms_norm, rope, swiglu


def _attention(x, p, positions, s, quant):
    R, N = s["kv_lora_rank"], s["qk_nope_head_dim"]
    h = rms_norm(x, p["ln1"], s["norm_eps"])
    q = mm("btd,dhk->bthk", h, p["wq"], quant)
    kv = mm("btd,dc->btc", h, p["wkv_a"], quant)
    c = rms_norm(kv[..., :R], p["kv_norm"], s["norm_eps"])
    k_pe = rope(kv[..., None, R:], positions, s["rope_theta"])   # [B,T,1,Dr]
    q_pe = rope(q[..., N:], positions, s["rope_theta"])
    kvb = mm("btc,chk->bthk", c, p["wkv_b"], quant)
    k = jnp.concatenate(
        [kvb[..., :N], jnp.broadcast_to(k_pe, kvb.shape[:3] + k_pe.shape[3:])],
        -1)
    q = jnp.concatenate([q[..., :N], q_pe], -1)
    o = causal_attention(q, k, kvb[..., N:], quant)
    return x + mm("bthk,hkd->btd", o, p["wo"], quant)


def _moe(h, p, s, quant):
    scores = jax.nn.sigmoid(mm("btd,de->bte", h, p["router"], quant))
    _, idx = jax.lax.top_k(scores + p["e_bias"], s["experts_per_token"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * s["routed_scaling_factor"]
    held = s["first_held_expert"] + jnp.arange(s["n_held_experts"])
    gate = jnp.sum(w[..., None] * (idx[..., None] == held), axis=-2)
    ex = p["experts"]
    g = mm("btd,edf->btef", h, ex["w_gate"], quant)
    u = mm("btd,edf->btef", h, ex["w_up"], quant)
    a = g * jnp.reciprocal(1.0 + jnp.exp(-g)) * u * gate[..., None]
    sh = p["shared"]
    return (mm("btef,efd->btd", a, ex["w_down"], quant)
            + swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"], quant))


def moe_layer(h, p, shapes: dict, quant: bool = False):
    """The MoE layer alone on normed input h [B,T,D] (f32), at `highest`
    precision: the held experts' part plus the shared experts."""
    with jax.default_matmul_precision("highest"):
        return _moe(h.astype(F32), p, shapes, quant)


@functools.partial(jax.jit, static_argnames=("shapes", "quant"))
def logits_at(weights, tokens, rows, cols, *, shapes: tuple,
              quant: bool = False):
    """f32 logits [len(rows), V] at positions (rows[i], cols[i]) of the
    forward over `tokens` [B, T]; `shapes` the configuration's shapes as
    sorted (key, value) pairs."""
    s = dict(shapes)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = weights["embed"][tokens].astype(F32)

    def dense(x, p):
        x = _attention(x, p, positions, s, quant)
        h = rms_norm(x, p["ln2"], s["norm_eps"])
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant), None

    def moe(x, p):
        x = _attention(x, p, positions, s, quant)
        return x + _moe(rms_norm(x, p["ln2"], s["norm_eps"]), p, s,
                        quant), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(dense, x, weights["dense_layers"])
        x, _ = jax.lax.scan(moe, x, weights["moe_layers"])
        h = rms_norm(x[rows, cols], weights["final_norm"], s["norm_eps"])
        return mm("nd,dv->nv", h, weights["lm_head"], quant)
