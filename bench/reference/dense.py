"""Plain f32 forward of a llama-style decoder (MiniCPM-2B as this repo
builds it): token embedding; per layer RMSNorm, multi-head attention with
rotary embeddings (halves convention) and a causal softmax, residual,
RMSNorm, SwiGLU, residual; final RMSNorm and an untied output head.

Weights come in `bench.weights.dense_weights`' layout (published head
count, unpadded vocabulary). The layers run in a scan that widens one
layer's bfloat16 weights to f32 at a time, so the reference fits beside
nothing else on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import F32, causal_attention, mm, rms_norm, rope, swiglu


def _layer(x, p, positions, eps, theta, quant):
    h = rms_norm(x, p["ln1"], eps)
    q = rope(mm("btd,dhk->bthk", h, p["wq"], quant), positions, theta)
    k = rope(mm("btd,dhk->bthk", h, p["wk"], quant), positions, theta)
    v = mm("btd,dhk->bthk", h, p["wv"], quant)
    x = x + mm("bthk,hkd->btd", causal_attention(q, k, v, quant), p["wo"],
               quant)
    h = rms_norm(x, p["ln2"], eps)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def logits_at(weights, tokens, rows, cols, *, eps: float, theta: float,
              quant: bool = False):
    """f32 logits [len(rows), V] at positions (rows[i], cols[i]) of the
    forward over `tokens` [B, T]."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = weights["embed"][tokens].astype(F32)

    def body(x, p):
        return _layer(x, p, positions, eps, theta, quant), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(body, x, weights["layers"])
        h = rms_norm(x[rows, cols], weights["final_norm"], eps)
        return mm("nd,dv->nv", h, weights["lm_head"], quant)
