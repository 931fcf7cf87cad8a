"""RWKV6 recurrence as a Pallas TPU kernel (chunked).

Unlike Mamba2's scalar-per-head decay, RWKV6's decay is a per-channel vector
(data-dependent), so the clean matmul dual does not apply directly. The
kernel processes chunks sequentially (grid axis) keeping the [Dh, Dh] state
in VMEM scratch, and walks the chunk with an unrolled fori loop of rank-1
outer-product updates — VPU work with the state resident in VMEM, which is
the part XLA does badly (it spills the state to HBM every step).

  y_t = r_t . (S + (u * k_t) v_t^T)
  S   = diag(exp(w_t)) S + k_t v_t^T          (w_t <= 0: log-decay)

Backward: ops.py wires jax.custom_vjp with the differentiable jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import pad_to, row_to_col, seq_tile

DEFAULT_CHUNK = 64


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sfin_ref,
            s_scr, y_scr, *, chunk, n_chunks):
    # the state is kept value-major, T = S^T [Dh_v, Dh_k], so each step
    # needs k, w and u only as rows and v as one column:
    #   y_t = r_t T^T + (r_t . (u * k_t)) v_t      T' = T * e^{w_t} + v_t^T k_t
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    u = u_ref[0].astype(jnp.float32)                        # [1, Dh]

    def step(t, s):
        row = pl.ds(t, 1)
        rt = r_ref[0, row, :]                               # [1, Dh]
        kt = k_ref[0, row, :]
        vt = v_ref[0, row, :]
        wt = w_ref[0, row, :]                               # log-decay, <= 0
        kv = row_to_col(vt) * kt                            # [Dh_v, Dh_k]
        att = s + kv * u
        y_scr[row, :] = jax.lax.dot_general(
            rt, att, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [1, Dh_v]
        return s * jnp.exp(wt) + kv

    s_fin = jax.lax.fori_loop(0, chunk, step, s_scr[...])
    s_scr[...] = s_fin
    y_ref[0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit():
        sfin_ref[0] = s_fin


def rwkv6_scan(r, k, v, w, u, *, chunk=DEFAULT_CHUNK, init_state=None,
               interpret=False):
    """r,k,v,w [B,T,H,Dh] (w = log-decay <= 0); u [H,Dh].
    Returns (y [B,T,H,Dh], final_state [B,H,Dh,Dh]). Any T: a length that
    does not tile is zero-padded, and padded steps (k = 0, w = 0) leave the
    state untouched."""
    B, T, H, Dh = r.shape
    chunk, t_pad = seq_tile(T, chunk)
    n_chunks = t_pad // chunk
    if init_state is None:
        init_state = jnp.zeros((B, H, Dh, Dh), jnp.float32)

    def flat(a):
        # f32 rows: the per-step row loads sit at dynamic sublane offsets,
        # which the TPU compiler accepts for 32-bit types only
        return jnp.swapaxes(pad_to(a, 1, t_pad), 1, 2).reshape(
            B * H, t_pad, Dh).astype(jnp.float32)

    s0 = jnp.swapaxes(init_state, 2, 3).reshape(B * H, Dh, Dh)
    row = lambda bh, ci: (bh, ci, 0)
    y, sfin = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, Dh), row),
            pl.BlockSpec((1, chunk, Dh), row),
            pl.BlockSpec((1, chunk, Dh), row),
            pl.BlockSpec((1, chunk, Dh), row),
            pl.BlockSpec((1, 1, Dh), lambda bh, ci, h=H: (bh % h, 0, 0)),
            pl.BlockSpec((1, Dh, Dh), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, Dh), row),
            pl.BlockSpec((1, Dh, Dh), lambda bh, ci: (bh, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((Dh, Dh), jnp.float32),
                        pltpu.VMEM((chunk, Dh), jnp.float32)],
        out_shape=(jax.ShapeDtypeStruct((B * H, t_pad, Dh), r.dtype),
                   jax.ShapeDtypeStruct((B * H, Dh, Dh), jnp.float32)),
        interpret=interpret,
        name="rwkv6_scan",
    )(flat(r), flat(k), flat(v), flat(w),
      jnp.asarray(u, jnp.float32).reshape(H, 1, Dh), s0)
    y = jnp.swapaxes(y.reshape(B, H, t_pad, Dh), 1, 2)[:, :T]
    return y, jnp.swapaxes(sfin.reshape(B, H, Dh, Dh), 2, 3)
