"""Latent-attention decode Pallas kernel (DeepSeek-V3 MLA, absorbed form):
every head of one row against that row's latent cache.

The absorbed query of a head is its no-rope part folded into the latent
space through W_kv_b's K half ([H, R]) beside its rope part ([H, Dr]); the
cache holds one latent `c_kv` [S, R] and one rope key `k_rope` [S, Dr] per
position, shared by all heads, and `c_kv` is also the value. So one program
takes all H heads of a row against one block of the cache: each cache block
is read once per step, where a one-head-per-program grid would read it H
times.

The kernel reads one layer of the stacked cache [L, B, S, *] in place, at
the layer index it is given (a scalar prefetched to SMEM with the rows'
lengths): a per-layer slice handed to a custom call would be copied out of
the stack first, and could sit in VMEM, where the kernel's time no longer
holds the cache's read from HBM.

Grid: (B, S//bk); the cache-block axis is sequential on TPU, so the online
softmax state of the row's heads lives in VMEM scratch. Blocks past a row's
length are neither fetched (the block index stops at the row's last block)
nor computed. Scores and the weighted sum run on the MXU on the cache's
bf16 with f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import pad_to, seq_tile

DEFAULT_BK = 2048
NEG_INF = -1e30


def cache_block(S: int, most: int) -> tuple[int, int]:
    """(block, padded length) of a cache of S slots: the largest multiple
    of 16 (a bf16 tile's rows) that divides S and is at most `most`, so
    that the cache is read in place (4,352 slots: 4 blocks of 1,088); else
    `seq_tile`'s block over a padded length."""
    for bk in range(min(most, S) // 16 * 16, 15, -16):
        if S % bk == 0:
            return bk, S
    return seq_tile(S, most)


def _kernel(lay_ref, len_ref, ql_ref, qr_ref, c_ref, kr_ref, o_ref, m_scr,
            l_scr, acc_scr, *, scale, bk, n_kb):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]

    @pl.when(ki * bk < length)
    def _body():
        c = c_ref[0, 0]                                       # [bk, R]
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql_ref[0], c, nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr_ref[0, 0], nt,
                                   preferred_element_type=jnp.float32)
             ) * scale                                        # [H, bk]
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[...]                                   # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kb - 1)
    def _emit():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def mla_decode_attention(q_lat, q_rope, c_kv, k_rope, layer, lengths, *,
                         scale, bk=DEFAULT_BK, interpret=False):
    """q_lat [B,H,R], q_rope [B,H,Dr] against layer `layer` ([] int) of the
    stacked latent cache c_kv [L,B,S,R] and rope keys k_rope [L,B,S,Dr];
    lengths [B] valid slots -> the softmax-weighted latent [B,H,R] (c_kv is
    the value). Queries are cast to the cache's dtype. A cache that does
    not tile is zero-padded here (a copy of the stack), and `lengths` (at
    most S) masks the padded slots."""
    B, H, R = q_lat.shape
    Dr = q_rope.shape[-1]
    S = c_kv.shape[2]
    bk, S = cache_block(S, bk)
    c_kv, k_rope = pad_to(c_kv, 2, S), pad_to(k_rope, 2, S)
    n_kb = S // bk

    def row(b, ki, lay, lens):
        return b, 0, 0

    def blk(b, ki, lay, lens):
        return lay[0], b, jnp.minimum(ki, jnp.maximum(lens[b] - 1, 0) // bk), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kb),
        in_specs=[pl.BlockSpec((1, H, R), row),
                  pl.BlockSpec((1, H, Dr), row),
                  pl.BlockSpec((1, 1, bk, R), blk),
                  pl.BlockSpec((1, 1, bk, Dr), blk)],
        out_specs=pl.BlockSpec((1, H, R), row),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, R), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, n_kb=n_kb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_lat.dtype),
        interpret=interpret,
        name="mla_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat.astype(c_kv.dtype), q_rope.astype(c_kv.dtype), c_kv, k_rope)
