"""Flash-decode Pallas kernel: one query token against a long KV cache.

Grid: (B*Hq, S//BK); the kv-block axis is sequential on TPU so the online-
softmax state lives in VMEM scratch. Valid-length masking (rolling caches
pass the number of valid slots per batch row) arrives via SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import pad_to, seq_tile

DEFAULT_BK = 512
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, bk, n_kb):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]
    run = (ki * bk) < length

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale              # [1, Dh]
        k = k_ref[0].astype(jnp.float32)                      # [bk, Dh]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [1,bk]
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == n_kb - 1)
    def _emit():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)


def decode_attention(q, k, v, lengths, *, group=1, bk=DEFAULT_BK,
                     interpret=False):
    """q [B,1,Hq,Dh]; k/v [B,S,Hkv,Dh]; lengths [B] -> [B,1,Hq,Dh].
    Any S: a cache that does not tile is zero-padded here, and `lengths`
    (at most S) already masks the padded slots."""
    B, _, Hq, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    bk, S = seq_tile(S, bk)
    k, v = pad_to(k, 1, S), pad_to(v, 1, S)
    n_kb = S // bk
    scale = 1.0 / (Dh ** 0.5)

    qf = jnp.swapaxes(q, 1, 2).reshape(B * Hq, 1, Dh)
    kf = jnp.swapaxes(k, 1, 2).reshape(B * Hkv, S, Dh)
    vf = jnp.swapaxes(v, 1, 2).reshape(B * Hkv, S, Dh)
    len_rep = jnp.repeat(lengths.astype(jnp.int32), Hq)

    kv_map = lambda bh, ki, g=group, h=Hq, hkv=Hkv: \
        ((bh // h) * hkv + (bh % h) // g, ki, 0)

    o = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, n_kb=n_kb),
        grid=(B * Hq, n_kb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Dh), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, Dh), kv_map),
            pl.BlockSpec((1, bk, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Dh), lambda bh, ki: (bh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1,), jnp.float32),
                        pltpu.VMEM((1,), jnp.float32),
                        pltpu.VMEM((1, Dh), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, Dh), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(len_rep, qf, kf, vf)
    return jnp.swapaxes(o.reshape(B, Hq, 1, Dh), 1, 2)
