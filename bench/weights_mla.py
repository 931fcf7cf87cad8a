"""Weights of a DeepSeek-V3-block model (latent attention, held routed
experts, shared experts) made from the seed, in the published layout.

As bench/weights.py does for the dense and hybrid models: the benchmark
makes the weights itself, so the plain reference never takes an array the
program under test has made, and one jitted call builds the whole tree on
the device. The layout follows the published checkpoint's tensors, stored
input-major: per layer `wq` [D, H, qk_nope + qk_rope] (no query LoRA),
`wkv_a` [D, kv_lora_rank + qk_rope] (kv_a_proj_with_mqa), `kv_norm`
[kv_lora_rank], `wkv_b` [kv_lora_rank, H, qk_nope + v] (each head's K half,
then its V half), `wo` [H, v, D]; the leading dense layers' SwiGLU; the MoE
layers' `router` [D, n_experts] (all experts, as published), the
`e_bias` correction bias [n_experts] (f32), the held experts' SwiGLUs and
the shared experts' one SwiGLU of n_shared x moe_d_ff.

`s` is a configuration's `shapes`: vocab, d_model, n_layers,
n_dense_layers, n_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, d_ff, moe_d_ff, n_experts, n_held_experts, n_shared_experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.weights import BF16, F32, _dense, _norm, seed_words


def mla_moe_weights(key, s: dict):
    V, D, L, Ld = s["vocab"], s["d_model"], s["n_layers"], s["n_dense_layers"]
    H, R, N = s["n_heads"], s["kv_lora_rank"], s["qk_nope_head_dim"]
    Dr, Dv, F = s["qk_rope_head_dim"], s["v_head_dim"], s["d_ff"]
    Fe, E, Eh = s["moe_d_ff"], s["n_experts"], s["n_held_experts"]
    Fs = s["n_shared_experts"] * Fe
    Lm = L - Ld
    k = iter(jax.random.split(key, 40))

    def attention(n):
        return {"ln1": _norm(next(k), (n, D)),
                "wq": _dense(next(k), (n, D, H, N + Dr), D),
                "wkv_a": _dense(next(k), (n, D, R + Dr), D),
                "kv_norm": _norm(next(k), (n, R)),
                "wkv_b": _dense(next(k), (n, R, H, N + Dv), R),
                "wo": _dense(next(k), (n, H, Dv, D), H * Dv),
                "ln2": _norm(next(k), (n, D))}

    def swiglu(lead, f):
        return {"w_gate": _dense(next(k), lead + (D, f), D),
                "w_up": _dense(next(k), lead + (D, f), D),
                "w_down": _dense(next(k), lead + (f, D), f)}

    return {
        "embed": (0.02 * jax.random.normal(next(k), (V, D), F32)).astype(BF16),
        "lm_head": _dense(next(k), (D, V), D),
        "final_norm": _norm(next(k), (D,)),
        "dense_layers": {**attention(Ld), **swiglu((Ld,), F)},
        "moe_layers": {
            **attention(Lm),
            "router": _dense(next(k), (Lm, D, E), D),
            "e_bias": 0.01 * jax.random.normal(next(k), (Lm, E), F32),
            "experts": swiglu((Lm, Eh), Fe),
            "shared": swiglu((Lm,), Fs),
        },
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _make(shapes: tuple, words):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    return mla_moe_weights(key, dict(shapes))


def make(shapes: dict, seed: int):
    """The weights from `seed` on the default device, in one jitted call."""
    return _make(tuple(sorted(shapes.items())), seed_words(seed))
