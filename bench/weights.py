"""Model weights made from the seed, in the published layout.

The benchmark makes the weights itself, so that the plain references never
take an array the program under test has made. `make(kind, shapes, seed)`
returns a pytree in the layout the published description uses (for the
dense model: 36 query heads, an unpadded vocabulary) in the dtype it is
served in; the drivers lay it out for the program. One jitted call builds
the whole tree on the device.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32


def _dense(key, shape, fan_in, dtype=BF16):
    return (jax.random.normal(key, shape, F32) / math.sqrt(fan_in)).astype(
        dtype)


def _norm(key, shape, dtype=BF16):
    return (1.0 + 0.05 * jax.random.normal(key, shape, F32)).astype(dtype)


def dense_weights(key, s: dict):
    """Llama-style decoder: s has vocab, d_model, n_layers, n_heads,
    n_kv_heads, head_dim, d_ff."""
    V, D, L = s["vocab"], s["d_model"], s["n_layers"]
    H, Hk, Dh, F = s["n_heads"], s["n_kv_heads"], s["head_dim"], s["d_ff"]
    k = iter(jax.random.split(key, 16))
    return {
        "embed": (0.02 * jax.random.normal(next(k), (V, D), F32)).astype(BF16),
        "lm_head": _dense(next(k), (D, V), D),
        "final_norm": _norm(next(k), (D,)),
        "layers": {
            "ln1": _norm(next(k), (L, D)),
            "wq": _dense(next(k), (L, D, H, Dh), D),
            "wk": _dense(next(k), (L, D, Hk, Dh), D),
            "wv": _dense(next(k), (L, D, Hk, Dh), D),
            "wo": _dense(next(k), (L, H, Dh, D), H * Dh),
            "ln2": _norm(next(k), (L, D)),
            "w_gate": _dense(next(k), (L, D, F), D),
            "w_up": _dense(next(k), (L, D, F), D),
            "w_down": _dense(next(k), (L, F, D), F),
        },
    }


def hybrid_weights(key, s: dict):
    """Mamba2 backbone with one shared attention + SwiGLU block: s has
    vocab, d_model, n_layers, n_heads, head_dim, d_ff, d_state,
    ssm_head_dim, expand, n_groups, conv_width."""
    V, D, L = s["vocab"], s["d_model"], s["n_layers"]
    H, Dh, F = s["n_heads"], s["head_dim"], s["d_ff"]
    N, P, G, W = s["d_state"], s["ssm_head_dim"], s["n_groups"], s["conv_width"]
    Din = s["expand"] * D
    Hs = Din // P
    k = iter(jax.random.split(key, 32))
    # dt of each head log-uniform in [1e-3, 1e-1] (Mamba2's initialisation),
    # stored as the inverse softplus; A = -exp(A_log) with A in [1, 16]
    dt = jnp.exp(jax.random.uniform(next(k), (L, Hs), F32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "embed": (0.02 * jax.random.normal(next(k), (V, D), F32)).astype(BF16),
        "lm_head": _dense(next(k), (D, V), D),
        "final_norm": _norm(next(k), (D,)),
        "layers": {
            "ln": _norm(next(k), (L, D)),
            "w_z": _dense(next(k), (L, D, Din), D),
            "w_x": _dense(next(k), (L, D, Din), D),
            "w_B": _dense(next(k), (L, D, G * N), D),
            "w_C": _dense(next(k), (L, D, G * N), D),
            "w_dt": _dense(next(k), (L, D, Hs), D),
            "conv_x_w": _dense(next(k), (L, W, Din), W),
            "conv_x_b": (0.1 * jax.random.normal(next(k), (L, Din), F32)
                         ).astype(BF16),
            "conv_B_w": _dense(next(k), (L, W, G * N), W),
            "conv_B_b": (0.1 * jax.random.normal(next(k), (L, G * N), F32)
                         ).astype(BF16),
            "conv_C_w": _dense(next(k), (L, W, G * N), W),
            "conv_C_b": (0.1 * jax.random.normal(next(k), (L, G * N), F32)
                         ).astype(BF16),
            "A_log": jnp.log(jax.random.uniform(next(k), (L, Hs), F32,
                                                1.0, 16.0)),
            "D": 1.0 + 0.1 * jax.random.normal(next(k), (L, Hs), F32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm_w": _norm(next(k), (L, Din)),
            "w_out": _dense(next(k), (L, Din, D), Din),
        },
        "shared": {
            "ln1": _norm(next(k), (D,)),
            "wq": _dense(next(k), (D, H, Dh), D),
            "wk": _dense(next(k), (D, H, Dh), D),
            "wv": _dense(next(k), (D, H, Dh), D),
            "wo": _dense(next(k), (H, Dh, D), H * Dh),
            "ln2": _norm(next(k), (D,)),
            "w_gate": _dense(next(k), (D, F), D),
            "w_up": _dense(next(k), (D, F), D),
            "w_down": _dense(next(k), (F, D), F),
        },
    }


KINDS = {"dense": dense_weights, "hybrid": hybrid_weights}


def seed_words(seed: int) -> np.ndarray:
    """Any whole number >= 0 (wider than 32 bits too) as two uint32 words,
    to pass into a jitted program as a traced argument."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def tree(kind: str, shapes: dict, words):
    """The weights of a `kind` model of `shapes` from the seed's
    `seed_words`; traceable inside `jax.jit` (the seed stays traced, so one
    compiled program serves every seed)."""
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    return KINDS[kind](key, dict(shapes))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(kind: str, shapes: tuple, words):
    return tree(kind, dict(shapes), words)


def make(kind: str, shapes: dict, seed: int):
    """The weights from `seed` on the default device, in one jitted call."""
    return _make(kind, tuple(sorted(shapes.items())), seed_words(seed))
