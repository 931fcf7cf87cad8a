"""Plain float32 references, written from the published descriptions.

They import nothing of the program under test. Every matrix product runs at
`highest` precision (a float32 product on a TPU is otherwise one bfloat16
pass). `quant=True` computes every product in fp8, as fp8 training does:
its operands in e4m3 and, in the backward pass, the incoming gradient in
e5m2, each scaled per tensor into its format's range. That is the step
below the bfloat16 that the configurations state, and the control that the
comparison must reject.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round(x, dtype, top: float):
    """x rounded to the fp8 `dtype` under a per-tensor scale, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


def fp8(x):
    """x rounded to float8_e4m3fn under a per-tensor scale, back in f32."""
    return _round(x.astype(F32), jnp.float8_e4m3fn, E4M3_MAX)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec: str, a, b):
    return jnp.einsum(spec, fp8(a), fp8(b))


def _mm_fp8_fwd(spec, a, b):
    a8, b8 = fp8(a), fp8(b)
    return jnp.einsum(spec, a8, b8), (a8, b8)


def _mm_fp8_bwd(spec, res, dy):
    _, pull = jax.vjp(functools.partial(jnp.einsum, spec), *res)
    return pull(_round(dy, jnp.float8_e5m2, E5M2_MAX))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(spec: str, a, b, quant: bool):
    """einsum of two f32 operands; in fp8 under `quant`."""
    a, b = a.astype(F32), b.astype(F32)
    if quant:
        return _mm_fp8(spec, a, b)
    return jnp.einsum(spec, a, b)


def rms_norm(x, w, eps):
    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                       + eps)) * w.astype(F32)


def rope(x, positions, theta):
    """Rotary embedding, halves convention: x [B, T, H, Dh]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, :, None] * freq          # [B, T, half]
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, quant: bool):
    """Softmax attention with a causal mask: q, k, v [B, T, H, Dh]."""
    T = q.shape[1]
    s = mm("bqhd,bkhd->bhqk", q, k, quant) / jnp.sqrt(F32(q.shape[-1]))
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = p / jnp.sum(p, -1, keepdims=True)
    return mm("bhqk,bkhd->bqhd", p, v, quant)


def swiglu(x, w_gate, w_up, w_down, quant: bool):
    g = mm("btd,df->btf", x, w_gate, quant)
    u = mm("btd,df->btf", x, w_up, quant)
    return mm("btf,fd->btd", g * jnp.reciprocal(1.0 + jnp.exp(-g)) * u,
              w_down, quant)
