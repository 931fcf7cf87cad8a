"""Fleet telemetry reduction as a Pallas TPU kernel — the hot path of
fleet-scale rail control.

A fleet controller's decisions hinge on cross-chip reductions of the per-chip
telemetry matrix `[n_chips, n_fields]` (worst-chip gradient error for BER
gating, min/max rail headroom, total power/energy). At 1000+ chips x O(10)
fields polled every control round this is a bandwidth-bound streaming
reduction, so one kernel computes all three reductions (max, min, sum) in a
single pass over the data: the grid walks chip tiles sequentially and
accumulates per-field running reductions in the output block, which stays
resident in VMEM across grid steps.

Row padding is masked inside the kernel (per-reduction neutral elements);
column padding only pollutes lanes that are sliced off afterwards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CHIPS_PER_STEP = 128   # chip-tile rows per grid step
LANES = 128            # TPU lane width; fields are padded up to this


def _kernel(x_ref, max_ref, min_ref, sum_ref, *, n_valid: int, tile: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                     # [tile, F]
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) + i * tile
    valid = rows < n_valid
    t_max = jnp.max(jnp.where(valid, x, -jnp.inf), axis=0, keepdims=True)
    t_min = jnp.min(jnp.where(valid, x, jnp.inf), axis=0, keepdims=True)
    t_sum = jnp.sum(jnp.where(valid, x, 0.0), axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        max_ref[...] = t_max
        min_ref[...] = t_min
        sum_ref[...] = t_sum

    @pl.when(i > 0)
    def _accumulate():
        max_ref[...] = jnp.maximum(max_ref[...], t_max)
        min_ref[...] = jnp.minimum(min_ref[...], t_min)
        sum_ref[...] = sum_ref[...] + t_sum


def _sor_kernel(x_ref, y_ref, w_ref,
                sw_ref, sx_ref, sy_ref, sxx_ref, sxy_ref):
    x = x_ref[...].astype(jnp.float32)                     # [window, L]
    y = y_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    wx = w * x
    sw_ref[...] = jnp.sum(w, axis=0, keepdims=True)
    sx_ref[...] = jnp.sum(wx, axis=0, keepdims=True)
    sy_ref[...] = jnp.sum(w * y, axis=0, keepdims=True)
    sxx_ref[...] = jnp.sum(wx * x, axis=0, keepdims=True)
    sxy_ref[...] = jnp.sum(wx * y, axis=0, keepdims=True)


SOR_ROWS_ALIGN = 8   # sublane alignment for the window axis


def _sor_fit_kernel(x_ref, y_ref, w_ref, bound_ref, guard_ref,
                    int_ref, slope_ref, front_ref, conf_ref, neff_ref,
                    floor_ref, *, min_slope: float, min_spread_v: float,
                    conf_samples: float):
    """One lane tile of the fused SOR fit: the five EWLS sums accumulate in
    VMEM exactly as `_sor_kernel`, then the per-lane solve + envelope floor
    run on the accumulators before anything leaves the chip — the estimate
    (6 x [1, L]) is the only thing written back, not the O(window) sums."""
    x = x_ref[...].astype(jnp.float32)                     # [window, L]
    y = y_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    wx = w * x
    sw = jnp.sum(w, axis=0, keepdims=True)                 # [1, L]
    sx = jnp.sum(wx, axis=0, keepdims=True)
    sy = jnp.sum(w * y, axis=0, keepdims=True)
    sxx = jnp.sum(wx * x, axis=0, keepdims=True)
    sxy = jnp.sum(wx * y, axis=0, keepdims=True)

    # the solve — the identical elementwise f32 op sequence as
    # ref.sor_solve_reference (bit-equivalence is pinned by tests)
    eps = jnp.float32(1e-9)
    denom = sw * sxx - sx * sx
    slope = (sw * sxy - sx * sy) / jnp.maximum(denom, eps)
    intercept = (sy - slope * sx) / jnp.maximum(sw, eps)
    var_x = jnp.maximum(sxx / jnp.maximum(sw, eps)
                        - (sx / jnp.maximum(sw, eps)) ** 2, 0.0)

    steep = slope < -jnp.float32(min_slope)
    spread = var_x > jnp.float32(min_spread_v) ** 2
    usable = steep & spread & (denom > eps)

    bound = bound_ref[...].astype(jnp.float32)             # [1, L]
    v_frontier = jnp.where(
        usable, (bound - intercept) / jnp.where(usable, slope, -1.0), 0.0)
    v_frontier = jnp.clip(v_frontier, 0.0, 2.0)
    confidence = jnp.where(
        usable, 1.0 - jnp.exp(-sw / jnp.float32(conf_samples)), 0.0)

    int_ref[...] = jnp.where(usable, intercept, 0.0)
    slope_ref[...] = jnp.where(usable, slope, 0.0)
    front_ref[...] = v_frontier
    conf_ref[...] = confidence
    neff_ref[...] = sw
    floor_ref[...] = v_frontier + guard_ref[...].astype(jnp.float32)


def sor_fit(x, y, w, log10_bound, guard, *, min_slope: float,
            min_spread_v: float, conf_samples: float,
            interpret: bool = False):
    """Fused safe-operating-region fit: EWLS accumulation + per-lane solve +
    envelope floor in ONE streaming pass over the `[window, n]` telemetry
    window (`n` = flattened n_rails x n_chips). Where `sor_accumulate`
    returns the five sums for a host-side solve, this carries the solve out
    of the same pass — the window is read once and only the 6 x [n] estimate
    (intercept, slope, v_frontier, confidence, n_eff, floor) is written
    back. `log10_bound`/`guard` are per-lane arrays (per-rail overrides
    broadcast over chips); the usability thresholds are compile-time
    scalars. Row padding carries zero weight, so no in-kernel masking;
    column padding only pollutes lanes that are sliced off afterwards."""
    window, n = x.shape
    rpad = (-window) % SOR_ROWS_ALIGN
    cpad = (-n) % LANES

    def pad(a):
        return jnp.pad(a.astype(jnp.float32), ((0, rpad), (0, cpad)))

    def pad_lane(a):
        return jnp.pad(a.astype(jnp.float32), (0, cpad)).reshape(1, -1)

    xm, ym, wm = pad(x), pad(y), pad(w)
    bm, gm = pad_lane(log10_bound), pad_lane(guard)
    rows, cols = xm.shape
    n_steps = cols // LANES

    win_spec = pl.BlockSpec((rows, LANES), lambda i: (0, i))
    lane_spec = pl.BlockSpec((1, LANES), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((1, cols), jnp.float32)
    outs = pl.pallas_call(
        functools.partial(_sor_fit_kernel, min_slope=min_slope,
                          min_spread_v=min_spread_v,
                          conf_samples=conf_samples),
        grid=(n_steps,),
        in_specs=[win_spec, win_spec, win_spec, lane_spec, lane_spec],
        out_specs=(lane_spec,) * 6,
        out_shape=(out_shape,) * 6,
        interpret=interpret,
        name="sor_fit",
    )(xm, ym, wm, bm, gm)
    return tuple(o[0, :n] for o in outs)


def sor_accumulate(x, y, w, *, interpret: bool = False):
    """Fused EWLS accumulation for the safe-operating-region fit: one pass
    over the `[window, n]` telemetry window computes all five weighted sums
    (sum w, w·x, w·y, w·x², w·x·y), each `[n]` f32 — `n` is the flattened
    n_rails x n_chips lane axis, so at O(1000) chips x 3 rails x 32-deep
    windows this is the same bandwidth-bound streaming reduction as
    `fleet_reduce`, with the five accumulators materialized in VMEM in a
    single read of the data. Row padding carries zero weight (every term is
    w-multiplied), so no in-kernel masking is needed; column padding only
    pollutes lanes that are sliced off afterwards."""
    window, n = x.shape
    rpad = (-window) % SOR_ROWS_ALIGN
    cpad = (-n) % LANES

    def pad(a):
        return jnp.pad(a.astype(jnp.float32), ((0, rpad), (0, cpad)))

    xm, ym, wm = pad(x), pad(y), pad(w)
    rows, cols = xm.shape
    n_steps = cols // LANES

    in_spec = pl.BlockSpec((rows, LANES), lambda i: (0, i))
    out_spec = pl.BlockSpec((1, LANES), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((1, cols), jnp.float32)
    outs = pl.pallas_call(
        _sor_kernel,
        grid=(n_steps,),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=(out_spec,) * 5,
        out_shape=(out_shape,) * 5,
        interpret=interpret,
        name="sor_accumulate",
    )(xm, ym, wm)
    return tuple(o[0, :n] for o in outs)


def fleet_reduce(x, *, interpret: bool = False):
    """x [n_chips, n_fields] f32 -> (max, min, sum), each [n_fields] f32."""
    n_chips, n_fields = x.shape
    fpad = (-n_fields) % LANES
    rpad = (-n_chips) % CHIPS_PER_STEP
    mat = jnp.pad(x.astype(jnp.float32), ((0, rpad), (0, fpad)))
    cols = mat.shape[1]
    n_steps = mat.shape[0] // CHIPS_PER_STEP

    out_spec = pl.BlockSpec((1, cols), lambda i: (0, 0))
    out_shape = jax.ShapeDtypeStruct((1, cols), jnp.float32)
    mx, mn, sm = pl.pallas_call(
        functools.partial(_kernel, n_valid=n_chips, tile=CHIPS_PER_STEP),
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((CHIPS_PER_STEP, cols), lambda i: (i, 0))],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=(out_shape, out_shape, out_shape),
        interpret=interpret,
        name="fleet_reduce",
    )(mat)
    return mx[0, :n_fields], mn[0, :n_fields], sm[0, :n_fields]
