"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references: kernel tests sweep shapes/dtypes and
assert_allclose against these, and non-TPU backends execute them directly
(the kernels target TPU; see kernels/__init__.py)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.tiles import pad_to, seq_tile


# ---------------------------------------------------------------------------
# Attention (flash_attention / decode_attention oracle)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, *, causal: bool = True, group: int = 1,
                  sliding_window: int = 0, lengths=None):
    """q [B,T,Hq,Dh], k/v [B,S,Hkv,Dh] with Hq = group * Hkv.

    causal assumes aligned positions (self-attention). `lengths` [B] masks
    key slots >= length (decode against a partially-filled cache).
    Accumulates in f32, returns q.dtype."""
    B, T, Hq, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert Hq == group * Hkv, (Hq, group, Hkv)
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(Dh))
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads to match q heads
    kf = jnp.repeat(kf, group, axis=2)
    vf = jnp.repeat(vf, group, axis=2)
    scores = jnp.einsum("bthk,bshk->bhts", qf, kf)
    neg = jnp.float32(-1e30)
    if causal:
        i = jnp.arange(T)[:, None]
        j = jnp.arange(S)[None, :]
        mask = j <= i
        if sliding_window:
            mask = mask & (j > i - sliding_window)
        scores = jnp.where(mask[None, None], scores, neg)
    if lengths is not None:
        valid = jnp.arange(S)[None, :] < lengths[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshk->bthk", probs, vf)
    return out.astype(q.dtype)


def mla_decode_reference(q_lat, q_rope, c_kv, k_rope, layer, lengths, *,
                         scale):
    """Latent-attention decode (absorbed form): q_lat [B,H,R] and q_rope
    [B,H,Dr] against layer `layer` of the stacked latent cache c_kv
    [L,B,S,R] and rope keys k_rope [L,B,S,Dr], shared by the heads; slots
    >= lengths [B] masked; c_kv is the value. Accumulates in f32, returns
    q_lat.dtype [B,H,R]."""
    c_kv, k_rope = c_kv[layer], k_rope[layer]
    f = lambda a: a.astype(jnp.float32)
    s = (jnp.einsum("bhr,bsr->bhs", f(q_lat), f(c_kv))
         + jnp.einsum("bhd,bsd->bhs", f(q_rope), f(k_rope))) * scale
    valid = jnp.arange(c_kv.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, jnp.float32(-1e30))
    out = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1), f(c_kv))
    return out.astype(q_lat.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD oracle (sequential scan over time) and its chunked SSD form
# ---------------------------------------------------------------------------

def mamba2_scan_reference(x, dt, A, B, C, D, *, init_state=None):
    """Sequential state-space scan (the SSD recurrence, Mamba2 eq. form).

    x  [Bt, T, H, P]   input per head (P = head channel dim)
    dt [Bt, T, H]      softplus-activated step sizes (>0)
    A  [H]             negative scalar decay per head (A < 0)
    B  [Bt, T, G, N]   input->state projection (G groups, N = state dim)
    C  [Bt, T, G, N]   state->output projection
    D  [H]             skip connection
    Heads are split evenly over groups: head h uses group h // (H // G).

    state s_{t} = exp(dt_t * A) * s_{t-1} + dt_t * B_t x_t^T   (per head: [N,P])
    y_t = C_t . s_t + D * x_t
    Returns (y [Bt,T,H,P], final_state [Bt,H,N,P]).
    """
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    hpg = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Df = D.astype(jnp.float32)

    Bh = jnp.repeat(Bf, hpg, axis=2)  # [Bt,T,H,N]
    Ch = jnp.repeat(Cf, hpg, axis=2)

    s0 = (jnp.zeros((Bt, H, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(s, inp):
        xt, dtt, bt, ct = inp  # [Bt,H,P],[Bt,H],[Bt,H,N],[Bt,H,N]
        decay = jnp.exp(dtt * Af)[..., None, None]          # [Bt,H,1,1]
        upd = (dtt[..., None, None]
               * bt[..., :, None] * xt[..., None, :])       # [Bt,H,N,P]
        s = s * decay + upd
        y = jnp.einsum("bhn,bhnp->bhp", ct, s)
        return s, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    s_fin, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1) + Df[None, None, :, None] * xf
    return y.astype(x.dtype), s_fin


def _decay(a):
    """exp(a_i - a_j) for j <= i, else 0, over the last axis of a
    decreasing cumsum `a` [..., L] -> [..., L, L]. Every kept exponent is
    <= 0; the rest are masked before the exp, so that neither the value
    nor its gradient meets an overflow."""
    L = a.shape[-1]
    causal = jnp.tril(jnp.ones((L, L), bool))
    return jnp.exp(jnp.where(causal, a[..., :, None] - a[..., None, :],
                             -jnp.inf))


def mamba2_chunked_reference(x, dt, A, B, C, D, *, chunk, init_state=None):
    """`mamba2_scan_reference` in the SSD (state-space dual) form, as
    matmuls over chunks of `chunk` steps, with no per-step loop: the
    differentiable form the kernel's backward runs. Same arguments and
    returns. With a = cumsum(dt * A) inside a chunk of length L:

      within chunk:  y_i  = sum_{j<=i} (C_i . B_j) e^{a_i - a_j} dt_j x_j
      cross chunk:   y_i += (C_i e^{a_i}) . S_prev
      carry:         S    = e^{a_L} S_prev + sum_j e^{a_L - a_j} dt_j B_j x_j^T

    The carry is one more masked-decay matmul over the chunk axis. All in
    f32 at HIGHEST precision. B and C stay per group (C . B^T is shared by
    a group's heads). A length that does not tile is zero-padded: padded
    steps (dt = 0) leave the state as it was."""
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = H // G                                  # heads per group
    c, t_pad = seq_tile(T, chunk)
    n = t_pad // c
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def chunks(v, *tail):
        return pad_to(v.astype(jnp.float32), 1, t_pad).reshape(Bt, n, c, *tail)

    xc, Bc, Cc = chunks(x, G, R, P), chunks(B, G, N), chunks(C, G, N)
    dtc = jnp.moveaxis(chunks(dt, G, R), 2, -1)            # [Bt,n,G,R,c]
    a = jnp.cumsum(dtc * A.astype(jnp.float32).reshape(G, R, 1), axis=-1)
    a_last = a[..., -1]                                    # [Bt,n,G,R]

    scores = mm("bkign,bkjgn->bkgij", Cc, Bc)              # per group
    w = scores[:, :, :, None] * _decay(a) * dtc[..., None, :]
    y = mm("bkgrij,bkjgrp->bkigrp", w, xc)

    # each chunk's own state, then the state entering each chunk (and the
    # final one) as a decay-weighted sum over the chunks before it
    w = jnp.moveaxis(jnp.exp(a_last[..., None] - a) * dtc, -1, 2)
    s_chunk = mm("bkjgn,bkjgrp->bkgrnp", Bc, w[..., None] * xc)
    s0 = (jnp.zeros((Bt, G, R, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32).reshape(Bt, G, R, N, P))
    a_in = jnp.cumsum(jnp.pad(a_last, ((0, 0), (1, 0), (0, 0), (0, 0))),
                      axis=1)                              # [Bt,n+1,G,R]
    s = mm("bgrkm,bmgrnp->bkgrnp", _decay(jnp.moveaxis(a_in, 1, -1)),
           jnp.concatenate([s0[:, None], s_chunk], axis=1))

    y = y + (mm("bkign,bkgrnp->bkigrp", Cc, s[:, :n])
             * jnp.moveaxis(jnp.exp(a), -1, 2)[..., None])
    y = y.reshape(Bt, t_pad, H, P)[:, :T] + D.astype(jnp.float32)[:, None] * x
    return y.astype(x.dtype), s[:, n].reshape(Bt, H, N, P)


# ---------------------------------------------------------------------------
# RWKV6 oracle (data-dependent decay linear attention)
# ---------------------------------------------------------------------------

def rwkv6_scan_reference(r, k, v, w, u, *, init_state=None):
    """RWKV6 ("Finch") recurrence, sequential oracle.

    r,k,v [B,T,H,Dh]; w [B,T,H,Dh] per-step decay logits (w<0 after -exp
    transform applied by caller: here w is the *log-decay*, decay=exp(w));
    u [H,Dh] bonus for the current token.

    state S [B,H,Dh,Dh] (key-major):
      y_t = (u * k_t) v_t^T . r_t  +  S_{t-1} . r_t
      S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T
    Returns (y [B,T,H,Dh], final_state).
    """
    B, T, H, Dh = r.shape
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    wf = w.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    s0 = (jnp.zeros((B, H, Dh, Dh), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(s, inp):
        rt, kt, vt, wt = inp  # each [B,H,Dh]
        att = s + (uf * kt)[..., :, None] * vt[..., None, :]   # [B,H,Dk,Dv]
        y = jnp.einsum("bhk,bhkv->bhv", rt, att)
        s = s * jnp.exp(wt)[..., :, None] + kt[..., :, None] * vt[..., None, :]
        return s, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rf, kf, vf, wf))
    s_fin, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), s_fin


# ---------------------------------------------------------------------------
# Blockwise int8 quantization oracle (ecollectives codec)
# ---------------------------------------------------------------------------

def quantize_int8_reference(x, block: int = 256):
    flat = jnp.ravel(x)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    blocks = flat.reshape(-1, block).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fleet telemetry reduction oracle (fleet control plane)
# ---------------------------------------------------------------------------

def fleet_reduce_reference(x):
    """x [n_chips, n_fields] -> (max, min, sum), each [n_fields] f32."""
    xf = x.astype(jnp.float32)
    return jnp.max(xf, axis=0), jnp.min(xf, axis=0), jnp.sum(xf, axis=0)


def fleet_percentile_reference(x, q):
    """x [n_chips] -> the q-th percentile, [] f32: the bit-reference for the
    fleet p95 tail metrics (step time, gradient error). Sort-bound, so it is
    the real implementation on every backend, not just the oracle."""
    return jnp.percentile(x.astype(jnp.float32), q)


# ---------------------------------------------------------------------------
# SOR EWLS accumulation oracle (safe-operating-region fit hot path)
# ---------------------------------------------------------------------------

def sor_accumulate_reference(x, y, w):
    """x/y/w [window, n] -> the five EWLS sums (Σw, Σwx, Σwy, Σwx², Σwxy),
    each [n] f32 — exactly the weighted sums `core.sor.fit_history` solves
    its per-(rail, chip) least squares from (invalid lanes carry w == 0)."""
    xf, yf, wf = (a.astype(jnp.float32) for a in (x, y, w))
    return (jnp.sum(wf, axis=0), jnp.sum(wf * xf, axis=0),
            jnp.sum(wf * yf, axis=0), jnp.sum(wf * xf * xf, axis=0),
            jnp.sum(wf * xf * yf, axis=0))


def sor_solve_reference(sums, log10_bound, guard, *, min_slope: float,
                        min_spread_v: float, conf_samples: float):
    """The EWLS solve on the five accumulated sums — the exact op sequence
    `core.sor.fit_history` historically ran host-side after
    `sor_accumulate`, factored out so the fused kernel path
    (`sor_fit_reference` / fleet_telemetry.sor_fit) is bit-identical to the
    unfused accumulate-then-solve split by construction. All elementwise
    f32; `log10_bound`/`guard` are per-lane arrays (per-rail overrides
    broadcast over chips). Returns (intercept, slope, v_frontier,
    confidence, n_eff, floor), each [n] f32 — `floor` is the envelope floor
    `v_frontier + guard` that `core.sor.rail_envelopes` publishes."""
    sw, sx, sy, sxx, sxy = sums
    eps = jnp.float32(1e-9)
    denom = sw * sxx - sx * sx
    slope = (sw * sxy - sx * sy) / jnp.maximum(denom, eps)
    intercept = (sy - slope * sx) / jnp.maximum(sw, eps)
    var_x = jnp.maximum(sxx / jnp.maximum(sw, eps)
                        - (sx / jnp.maximum(sw, eps)) ** 2, 0.0)

    steep = slope < -jnp.float32(min_slope)
    spread = var_x > jnp.float32(min_spread_v) ** 2
    usable = steep & spread & (denom > eps)

    bound = jnp.asarray(log10_bound, jnp.float32)
    v_frontier = jnp.where(
        usable, (bound - intercept) / jnp.where(usable, slope, -1.0), 0.0)
    v_frontier = jnp.clip(v_frontier, 0.0, 2.0)
    confidence = jnp.where(
        usable, 1.0 - jnp.exp(-sw / jnp.float32(conf_samples)), 0.0)
    floor = v_frontier + jnp.asarray(guard, jnp.float32)
    return (jnp.where(usable, intercept, 0.0).astype(jnp.float32),
            jnp.where(usable, slope, 0.0).astype(jnp.float32),
            v_frontier.astype(jnp.float32), confidence.astype(jnp.float32),
            sw.astype(jnp.float32), floor.astype(jnp.float32))


def sor_fit_reference(x, y, w, log10_bound, guard, *, min_slope: float,
                      min_spread_v: float, conf_samples: float):
    """Fused EWLS fit: accumulate + solve + envelope floor in one call —
    the jnp oracle for `fleet_telemetry.sor_fit`. Composes the two reference
    stages verbatim, so fused == unfused bit-exactly on this path."""
    return sor_solve_reference(
        sor_accumulate_reference(x, y, w), log10_bound, guard,
        min_slope=min_slope, min_spread_v=min_spread_v,
        conf_samples=conf_samples)
