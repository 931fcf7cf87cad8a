"""Mamba2 SSD (state-space dual) chunked scan as a Pallas TPU kernel.

The SSD form turns the sequential SSM recurrence into chunked matmuls (MXU
work) with a small cross-chunk state carry:

  within chunk (length Lc):  y_i  = sum_{j<=i} (C_i . B_j) e^{a_i - a_j} dt_j x_j
  cross chunk:               y_i += (C_i e^{a_i}) . S_prev
  carry:                     S    = e^{a_L} S_prev + sum_j e^{a_L - a_j} dt_j B_j x_j^T

with a = cumsum(dt * A) inside the chunk (A < 0 so every exponent is <= 0 —
numerically safe), computed as a matmul against the causal mask. Grid =
(B*H, T//chunk); the chunk axis is sequential on TPU so the [N, P] state
lives in VMEM scratch. dt travels as a lane-major row per chunk.

Backward: the op is exposed through jax.custom_vjp in ops.py with the
differentiable chunked jnp reference (ref.mamba2_chunked_reference) as the
bwd path — fwd runs the kernel, bwd recomputes via XLA. Exact (same math),
documented perf trade-off in DESIGN.md §6.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import pad_to, row_to_col, seq_tile

DEFAULT_CHUNK = 128


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, s0_ref,
            y_ref, sfin_ref, s_scr, *, n_chunks, n_heads):
    ci = pl.program_id(1)
    h = pl.program_id(0) % n_heads          # program rows are (batch*head)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    x = x_ref[0].astype(jnp.float32)        # [Lc, P]
    dt = dt_ref[0].astype(jnp.float32)      # [1, Lc] (lane-major row)
    Bm = B_ref[0].astype(jnp.float32)       # [Lc, N]
    Cm = C_ref[0].astype(jnp.float32)       # [Lc, N]
    A = A_ref[h]                            # scalar (SMEM)
    D = D_ref[h]

    # a_i = sum_{j<=i} dt_j A: the in-chunk cumsum as one MXU matmul against
    # the causal mask (TPU Pallas has no cumsum); decreasing since A < 0
    Lc = x.shape[0]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1))
    da = dt * A                                                # [1, Lc]
    a_row = jax.lax.dot_general(da, causal.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    a_col = row_to_col(a_row)                                  # [Lc, 1]
    a_last = jnp.sum(da, axis=1, keepdims=True)                # [1, 1]

    # cross-chunk contribution
    s_prev = s_scr[...]                                        # [N, P]
    y_inter = jax.lax.dot_general(Cm * jnp.exp(a_col), s_prev,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # within-chunk (causal decay-weighted attention-like matmul)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Lc,Lc]
    m = jnp.exp(jnp.where(causal, a_col - a_row, -jnp.inf)) * dt
    y_intra = jax.lax.dot_general(scores * m, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0] = (y_inter + y_intra + D * x).astype(y_ref.dtype)

    # state carry
    w = jnp.exp(a_last - a_col) * row_to_col(dt)               # [Lc, 1]
    s_new = (jnp.exp(a_last) * s_prev
             + jax.lax.dot_general(Bm * w, x, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    s_scr[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _emit():
        sfin_ref[0] = s_new


def mamba2_ssd(x, dt, A, B, C, D, *, chunk=DEFAULT_CHUNK, init_state=None,
               interpret=False):
    """x [Bt,T,H,P]; dt [Bt,T,H]; A,D [H]; B,C [Bt,T,G,N].
    Returns (y [Bt,T,H,P], final_state [Bt,H,N,P]). Any T: a length that
    does not tile is zero-padded, and padded steps (dt = 0) leave the state
    untouched."""
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    hpg = H // G
    chunk, t_pad = seq_tile(T, chunk)
    n_chunks = t_pad // chunk
    x, dt, B, C = (pad_to(a, 1, t_pad) for a in (x, dt, B, C))

    if init_state is None:
        init_state = jnp.zeros((Bt, H, N, P), jnp.float32)

    # layout: per (batch*head) rows
    xf = jnp.swapaxes(x, 1, 2).reshape(Bt * H, t_pad, P)
    dtf = jnp.swapaxes(dt, 1, 2).reshape(Bt * H, 1, t_pad)
    Bf = jnp.swapaxes(B, 1, 2).reshape(Bt * G, t_pad, N)
    Cf = jnp.swapaxes(C, 1, 2).reshape(Bt * G, t_pad, N)
    s0 = init_state.reshape(Bt * H, N, P)

    bc_map = lambda bh, ci, hpg=hpg, h=H, g=G: \
        ((bh // h) * g + (bh % h) // hpg, ci, 0)

    y, sfin = pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_chunks, n_heads=H),
        grid=(Bt * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # A [H] -> indexed by head
            pl.BlockSpec((1, chunk, N), bc_map),
            pl.BlockSpec((1, chunk, N), bc_map),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # D [H]
            pl.BlockSpec((1, N, P), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, N, P), lambda bh, ci: (bh, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        out_shape=(jax.ShapeDtypeStruct((Bt * H, t_pad, P), x.dtype),
                   jax.ShapeDtypeStruct((Bt * H, N, P), jnp.float32)),
        interpret=interpret,
        name="mamba2_ssd",
    )(xf, dtf, jnp.asarray(A, jnp.float32), Bf, Cf,
      jnp.asarray(D, jnp.float32), s0)
    y = jnp.swapaxes(y.reshape(Bt, H, t_pad, P), 1, 2)[:, :T]
    return y, sfin.reshape(Bt, H, N, P)
