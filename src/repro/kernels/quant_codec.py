"""Blockwise int8 quantization codec as a Pallas TPU kernel — the hot loop of
the error-bounded collectives (the paper-technique data path: every gradient
byte that crosses ICI goes through this).

One grid row handles ROWS_PER_STEP quantization blocks; absmax reduction and
scale/round/clip run entirely in VMEM. The dequantize side is a trivial
broadcast-multiply left to XLA (it fuses into the consumer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_PER_STEP = 32


def _kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # [R, block]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def quantize_int8(x, *, block: int = 256, interpret: bool = False):
    """x any shape -> (q [nblocks, block] int8, scale [nblocks, 1] f32).
    Zero-pads the tail block (matches ref.quantize_int8_reference)."""
    flat = jnp.ravel(x)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    rows = flat.size // block
    # pad rows so the grid tiles evenly
    rpad = (-rows) % ROWS_PER_STEP
    if rpad:
        flat = jnp.concatenate([flat, jnp.zeros((rpad * block,), flat.dtype)])
    mat = flat.reshape(-1, block)
    n_steps = mat.shape[0] // ROWS_PER_STEP

    q, s = pl.pallas_call(
        _kernel,
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((ROWS_PER_STEP, block), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((ROWS_PER_STEP, block), lambda i: (i, 0)),
                   pl.BlockSpec((ROWS_PER_STEP, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct(mat.shape, jnp.int8),
                   jax.ShapeDtypeStruct((mat.shape[0], 1), jnp.float32)),
        interpret=interpret,
        name="quantize_int8",
    )(mat)
    return q[:rows], s[:rows]
