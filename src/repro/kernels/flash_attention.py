"""Flash attention (fwd + bwd) as Pallas TPU kernels.

Tiling: queries in (BQ=128) x keys in (BK=128) VMEM blocks — MXU-aligned on
the (128, head_dim) contraction. The kv-block grid axis is innermost and
sequential on TPU, so the streaming-softmax state (m, l, acc) lives in VMEM
scratch across kv steps and the normalized output is written on the last
step. Causal + sliding-window masking, GQA via kv-head index mapping
(q head h reads kv head h // group). Backward uses the standard two-kernel
split: dq accumulates over kv blocks; dk/dv accumulate over q blocks and the
GQA group. All accumulation in f32; lse saved by the forward for the vjp.

Per-row statistics (running max and sum, lse, the backward's delta) are
`(bq, 1)` columns inside the kernels and `[rows, 1, T]` lane-major rows in
HBM, so every block obeys the TPU tiling rule (kernels/tiles.py). Sequences
that do not tile are zero-padded by the wrapper; padded keys are masked out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import col_to_row, pad_to, row_to_col, seq_tile

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _mask(scores, qi, ki, bq, bk, *, causal, window, kv_len=0):
    """Apply causal/sliding-window mask to a [bq, bk] score block located at
    query offset qi*bq, key offset ki*bk; `kv_len` > 0 also masks the
    padded keys at and beyond it."""
    if not causal and not window and not kv_len:
        return scores
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    keep = jnp.ones(scores.shape, jnp.bool_)
    if causal:
        keep = keep & (cols <= rows)
    if window:
        keep = keep & (cols > rows - window)
    if kv_len:
        keep = keep & (cols < kv_len)
    return jnp.where(keep, scores, NEG_INF)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, window, bq, bk,
                n_kb, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # with causal masking, kv blocks strictly above the diagonal contribute
    # nothing — skip their compute entirely
    run = jnp.bool_(True)
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if window:
        run = jnp.logical_and(run, (ki + 1) * bk - 1 > qi * bq - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale           # [bq, Dh]
        k = k_ref[0].astype(jnp.float32)                   # [bk, Dh]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask(s, qi, ki, bq, bk, causal=causal, window=window,
                  kv_len=kv_len)
        m_prev = m_scr[...]                                 # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == n_kb - 1)
    def _emit():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = col_to_row(m_scr[...] + jnp.log(l_safe))


def _fwd(q, k, v, *, causal, group, window, bq, bk, kv_len, interpret):
    """q [B,Hq,T,Dh]; k/v [B,Hkv,S,Dh] -> (o [B,Hq,T,Dh], lse [B*Hq,1,T])."""
    B, Hq, T, Dh = q.shape
    S = k.shape[2]
    scale = 1.0 / (Dh ** 0.5)
    n_qb, n_kb = T // bq, S // bk
    grid = (B * Hq, n_qb, n_kb)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, n_kb=n_kb,
                               kv_len=kv_len)
    out_shape = (jax.ShapeDtypeStruct((B * Hq, T, Dh), q.dtype),
                 jax.ShapeDtypeStruct((B * Hq, 1, T), jnp.float32))
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, Dh),
                         lambda bh, qi, ki, g=group, h=Hq:
                         ((bh // h) * (h // g) + (bh % h) // g, ki, 0)),
            pl.BlockSpec((1, bk, Dh),
                         lambda bh, qi, ki, g=group, h=Hq:
                         ((bh // h) * (h // g) + (bh % h) // g, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=interpret,
        name="flash_attention_fwd",
    )(q.reshape(B * Hq, T, Dh), k.reshape(B * k.shape[1], S, Dh),
      v.reshape(B * v.shape[1], S, Dh))
    return o.reshape(B, Hq, T, Dh), lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale, causal, window, bq, bk, n_kb, kv_len):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = jnp.bool_(True)
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if window:
        run = jnp.logical_and(run, (ki + 1) * bk - 1 > qi * bq - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask(s, qi, ki, bq, bk, causal=causal, window=window,
                  kv_len=kv_len)
        p = jnp.exp(s - row_to_col(lse_ref[0]))
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - row_to_col(delta_ref[0]))
        acc_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _emit():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, window, bq, bk, n_qb, group, kv_len):
    # grid: (B*Hkv, kv block, group member, q block)
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(jnp.logical_and(gi == 0, qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = jnp.bool_(True)
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if window:
        run = jnp.logical_and(run, (ki + 1) * bk - 1 > qi * bq - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask(s, qi, ki, bq, bk, causal=causal, window=window,
                  kv_len=kv_len)
        p = jnp.exp(s - row_to_col(lse_ref[0]))              # [bq, bk]
        do = do_ref[0].astype(jnp.float32)                   # [bq, Dh]
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - row_to_col(delta_ref[0]))
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(gi == pl.num_programs(2) - 1,
                             qi == n_qb - 1))
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(res, g, *, causal, group, window, bq, bk, kv_len, interpret):
    q, k, v, o, lse = res
    do = g
    B, Hq, T, Dh = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    scale = 1.0 / (Dh ** 0.5)
    n_qb, n_kb = T // bq, S // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    qf = q.reshape(B * Hq, T, Dh)
    kf = k.reshape(B * Hkv, S, Dh)
    vf = v.reshape(B * Hkv, S, Dh)
    dof = do.reshape(B * Hq, T, Dh)
    deltaf = delta.reshape(B * Hq, 1, T)

    kv_map = lambda bh, qi, ki, g=group, h=Hq: \
        ((bh // h) * (h // g) + (bh % h) // g, ki, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_kb=n_kb,
                          kv_len=kv_len),
        grid=(B * Hq, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, Dh), kv_map),
            pl.BlockSpec((1, bk, Dh), kv_map),
            pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((bq, Dh), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B * Hq, T, Dh), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qf, kf, vf, dof, lse, deltaf)

    # dk/dv: grid walks (kv block, group member, q block) for each B*Hkv
    def q_map(bhkv, ki, gi, qi, g=group, hkv=Hkv):
        return ((bhkv // hkv) * (hkv * g) + (bhkv % hkv) * g + gi, qi, 0)

    def q_map_flat(bhkv, ki, gi, qi, g=group, hkv=Hkv):
        b = bhkv // hkv
        hq = (bhkv % hkv) * g + gi
        return (b * (hkv * g) + hq, qi, 0)

    kv_self = lambda bhkv, ki, gi, qi: (bhkv, ki, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_qb=n_qb, group=group,
                          kv_len=kv_len),
        grid=(B * Hkv, n_kb, group, n_qb),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), q_map_flat),
            pl.BlockSpec((1, bk, Dh), kv_self),
            pl.BlockSpec((1, bk, Dh), kv_self),
            pl.BlockSpec((1, bq, Dh), q_map_flat),
            pl.BlockSpec((1, 1, bq), lambda bhkv, ki, gi, qi:
                         (q_map_flat(bhkv, ki, gi, qi)[0], 0, qi)),
            pl.BlockSpec((1, 1, bq), lambda bhkv, ki, gi, qi:
                         (q_map_flat(bhkv, ki, gi, qi)[0], 0, qi)),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, Dh), lambda bhkv, ki, gi, qi: (bhkv, ki, 0)),
            pl.BlockSpec((1, bk, Dh), lambda bhkv, ki, gi, qi: (bhkv, ki, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((bk, Dh), jnp.float32),
                        pltpu.VMEM((bk, Dh), jnp.float32)],
        out_shape=(jax.ShapeDtypeStruct((B * Hkv, S, Dh), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, S, Dh), v.dtype)),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qf, kf, vf, dof, lse, deltaf)

    return (dq.reshape(B, Hq, T, Dh),
            dk.reshape(B, Hkv, S, Dh),
            dv.reshape(B, Hkv, S, Dh))


# ---------------------------------------------------------------------------
# Public entry (BTHD layout) with custom vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fa(q, k, v, causal, group, window, bq, bk, kv_len, interpret):
    o, _ = _fwd(q, k, v, causal=causal, group=group, window=window,
                bq=bq, bk=bk, kv_len=kv_len, interpret=interpret)
    return o


def _fa_fwd(q, k, v, causal, group, window, bq, bk, kv_len, interpret):
    o, lse = _fwd(q, k, v, causal=causal, group=group, window=window,
                  bq=bq, bk=bk, kv_len=kv_len, interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, group, window, bq, bk, kv_len, interpret, res, g):
    return _bwd(res, g, causal=causal, group=group, window=window,
                bq=bq, bk=bk, kv_len=kv_len, interpret=interpret)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, *, causal=True, group=1, sliding_window=0,
                    bq=DEFAULT_BQ, bk=DEFAULT_BK, interpret=False):
    """Public API, [B,T,H,Dh] layout (matches models/attention.py). Any
    T and S: lengths that do not tile are zero-padded here, padded keys are
    masked in the kernels and padded query rows are sliced off."""
    T, S = q.shape[1], k.shape[1]
    bq, t_pad = seq_tile(T, bq)
    bk, s_pad = seq_tile(S, bk)
    qt = jnp.swapaxes(pad_to(q, 1, t_pad), 1, 2)
    kt = jnp.swapaxes(pad_to(k, 1, s_pad), 1, 2)
    vt = jnp.swapaxes(pad_to(v, 1, s_pad), 1, 2)
    kv_len = S if s_pad != S else 0
    o = _fa(qt, kt, vt, causal, group, sliding_window, bq, bk, kv_len,
            interpret)
    return jnp.swapaxes(o, 1, 2)[:, :T]
