"""Public jit'd wrappers for the Pallas kernels with backend dispatch.

On TPU the Pallas implementations run natively; elsewhere (this container is
CPU-only) the mathematically-identical XLA reference path executes, and the
Pallas bodies are validated in interpret mode by the kernel test suite.
Set REPRO_PALLAS=interpret to force interpret-mode Pallas everywhere
(slow; used by tests)."""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.tiles import pad_to, seq_tile


def _pallas_mode() -> str:
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env == "interpret":
        return "interpret"
    if env == "off":
        return "off"
    return "native" if jax.default_backend() == "tpu" else "off"


_TPU_KERNEL = re.compile(
    r'%([A-Za-z_]\w*?)(?:\.[\w.]+)? = [^\n]*'
    r'custom_call_target="tpu_custom_call"')


_TRANSFORMS = re.compile(r"^(?:(?:jvp|transpose|vmap|batched)_)+|_+$")


def pallas_kernels(hlo_text: str) -> set[str]:
    """Names of the Pallas TPU kernels in a compiled program, from its text
    (`jax.jit(f).lower(...).compile().as_text()`): each kernel is a
    `tpu_custom_call` named after its `pallas_call(name=...)`, which jvp
    and transpose decorate (`transpose_jvp_<name>__`) and this undoes."""
    return {_TRANSFORMS.sub("", n) for n in _TPU_KERNEL.findall(hlo_text)}


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "group",
                                             "sliding_window", "use_flash"))
def flash_attention(q, k, v, *, causal: bool = True, group: int = 1,
                    sliding_window: int = 0, use_flash: bool = True):
    """q [B,T,Hq,Dh], k/v [B,S,Hkv,Dh] -> [B,T,Hq,Dh]."""
    mode = _pallas_mode() if use_flash else "off"
    if mode != "off":
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, group=group,
                                  sliding_window=sliding_window,
                                  interpret=(mode == "interpret"))
    return ref.mha_reference(q, k, v, causal=causal, group=group,
                             sliding_window=sliding_window)


@functools.partial(jax.jit, static_argnames=("group",))
def decode_attention(q, k, v, lengths, *, group: int = 1):
    """q [B,1,Hq,Dh] against cache k/v [B,S,Hkv,Dh]; lengths [B] valid slots."""
    mode = _pallas_mode()
    if mode != "off":
        from repro.kernels import decode_attention as da
        return da.decode_attention(q, k, v, lengths, group=group,
                                   interpret=(mode == "interpret"))
    return ref.mha_reference(q, k, v, causal=False, group=group,
                             lengths=lengths)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunked scan
#
# The Pallas scans run the forward; the backward recomputes through the
# differentiable jnp reference (identical math) via custom_vjp, so training
# through the kernels is exact on TPU. Off-TPU the reference runs directly.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _mamba2_kernel_vjp(x, dt, A, B, C, D, chunk, interpret, init_state):
    from repro.kernels import mamba2_ssd as m2
    return m2.mamba2_ssd(x, dt, A, B, C, D, chunk=chunk,
                         init_state=init_state, interpret=interpret)


def _mamba2_fwd(x, dt, A, B, C, D, chunk, interpret, init_state):
    out = _mamba2_kernel_vjp(x, dt, A, B, C, D, chunk, interpret, init_state)
    return out, (x, dt, A, B, C, D, init_state)


def _mamba2_remat_reference(x, dt, A, B, C, D, init_state, *, chunk):
    """`ref.mamba2_scan_reference` run over `chunk`-step pieces, each under
    `jax.checkpoint`: its vjp keeps one state per piece and recomputes a
    piece's per-step states while differentiating it, where the plain
    sequential scan keeps all T of them ([T, B, H, N, P] f32 — 4 GiB per
    layer at zamba2_1p2b's widths for 4 x 512 tokens). Padded steps have
    dt = 0, which leaves the state as it was."""
    T = x.shape[1]
    c, t_pad = seq_tile(T, chunk)
    n = t_pad // c

    def pieces(a):
        a = pad_to(a, 1, t_pad)
        return jnp.moveaxis(a.reshape(a.shape[0], n, c, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def piece(s, xs):
        xc, dtc, bc, cc = xs
        y, s = ref.mamba2_scan_reference(xc, dtc, A, bc, cc, D, init_state=s)
        return s, y

    s, ys = jax.lax.scan(piece, init_state, tuple(map(pieces, (x, dt, B, C))))
    y = jnp.moveaxis(ys, 0, 1).reshape(x.shape[0], t_pad, *x.shape[2:])
    return y[:, :T], s


def _mamba2_bwd(chunk, interpret, res, g):
    x, dt, A, B, C, D, init_state = res
    _, vjp = jax.vjp(
        lambda *a: _mamba2_remat_reference(*a, chunk=chunk),
        x, dt, A, B, C, D,
        init_state if init_state is not None
        else jnp.zeros((x.shape[0], x.shape[2], B.shape[3], x.shape[3]),
                       jnp.float32))
    grads = vjp(g)
    return grads[:6] + (grads[6] if init_state is not None else None,)


_mamba2_kernel_vjp.defvjp(_mamba2_fwd, _mamba2_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba2_scan(x, dt, A, B, C, D, *, chunk: int = 128, init_state=None):
    mode = _pallas_mode()
    if mode != "off":
        return _mamba2_kernel_vjp(x, dt, A, B, C, D, chunk,
                                  mode == "interpret", init_state)
    return ref.mamba2_scan_reference(x, dt, A, B, C, D, init_state=init_state)


# ---------------------------------------------------------------------------
# RWKV6 recurrence (same custom_vjp pattern)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rwkv6_kernel_vjp(r, k, v, w, u, chunk, interpret, init_state):
    from repro.kernels import rwkv6_scan as r6
    return r6.rwkv6_scan(r, k, v, w, u, chunk=chunk,
                         init_state=init_state, interpret=interpret)


def _rwkv6_fwd(r, k, v, w, u, chunk, interpret, init_state):
    out = _rwkv6_kernel_vjp(r, k, v, w, u, chunk, interpret, init_state)
    return out, (r, k, v, w, u, init_state)


def _rwkv6_bwd(chunk, interpret, res, g):
    r, k, v, w, u, init_state = res
    _, vjp = jax.vjp(
        lambda *a: ref.rwkv6_scan_reference(*a[:5], init_state=a[5]),
        r, k, v, w, u,
        init_state if init_state is not None
        else jnp.zeros((r.shape[0], r.shape[2], r.shape[3], r.shape[3]),
                       jnp.float32))
    grads = vjp(g)
    return grads[:5] + (grads[5] if init_state is not None else None,)


_rwkv6_kernel_vjp.defvjp(_rwkv6_fwd, _rwkv6_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w, u, *, chunk: int = 64, init_state=None):
    mode = _pallas_mode()
    if mode != "off":
        return _rwkv6_kernel_vjp(r, k, v, w, u, chunk,
                                 mode == "interpret", init_state)
    return ref.rwkv6_scan_reference(r, k, v, w, u, init_state=init_state)


# ---------------------------------------------------------------------------
# int8 block quantization codec
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block",))
def quantize_int8(x, *, block: int = 256):
    mode = _pallas_mode()
    if mode != "off":
        from repro.kernels import quant_codec as qc
        return qc.quantize_int8(x, block=block, interpret=(mode == "interpret"))
    return ref.quantize_int8_reference(x, block=block)


# ---------------------------------------------------------------------------
# Fleet telemetry reduction (fleet control plane hot path)
# ---------------------------------------------------------------------------

@jax.jit
def fleet_reduce(x):
    """x [n_chips, n_fields] -> (max, min, sum) over chips, each [n_fields].
    One streaming pass on TPU (fleet_telemetry.py); XLA reference elsewhere."""
    mode = _pallas_mode()
    if mode != "off":
        from repro.kernels import fleet_telemetry as ft
        return ft.fleet_reduce(x, interpret=(mode == "interpret"))
    return ref.fleet_reduce_reference(x)


@jax.jit
def sor_accumulate(x, y, w):
    """x/y/w [window, n] -> the five EWLS sums (Σw, Σwx, Σwy, Σwx², Σwxy),
    each [n] f32 — the safe-operating-region fit's accumulation
    (core/sor.py), fused into one streaming pass on TPU
    (fleet_telemetry.sor_accumulate); XLA reference elsewhere."""
    mode = _pallas_mode()
    if mode != "off":
        from repro.kernels import fleet_telemetry as ft
        return ft.sor_accumulate(x, y, w, interpret=(mode == "interpret"))
    return ref.sor_accumulate_reference(x, y, w)


@functools.partial(jax.jit, static_argnames=("min_slope", "min_spread_v",
                                             "conf_samples"))
def sor_fit(x, y, w, log10_bound, guard, *, min_slope: float,
            min_spread_v: float, conf_samples: float):
    """Fused safe-operating-region fit: the five EWLS sums, the per-lane
    solve, and the envelope floor carried out of ONE streaming pass over the
    `[window, n]` telemetry window (fleet_telemetry.sor_fit on TPU; the
    composed jnp reference elsewhere — XLA fuses accumulate+solve into one
    pass under jit). Returns (intercept, slope, v_frontier, confidence,
    n_eff, floor), each [n] f32 — bit-identical to `sor_accumulate` followed
    by the host-side solve (`ref.sor_solve_reference`), pinned by tests."""
    mode = _pallas_mode()
    if mode != "off":
        from repro.kernels import fleet_telemetry as ft
        return ft.sor_fit(x, y, w, log10_bound, guard, min_slope=min_slope,
                          min_spread_v=min_spread_v,
                          conf_samples=conf_samples,
                          interpret=(mode == "interpret"))
    return ref.sor_fit_reference(x, y, w, log10_bound, guard,
                                 min_slope=min_slope,
                                 min_spread_v=min_spread_v,
                                 conf_samples=conf_samples)


@jax.jit
def fleet_percentile(x, q):
    """`[n_chips]` stat vector -> the q-th percentile, [] f32. Routed
    through the kernels layer so the sharded fleet step's only cross-shard
    traffic (the worst/mean/p95 stat vectors) flows through one seam;
    percentile is sort-bound, so there is no streaming-kernel win — the XLA
    reference runs on every backend (including TPU)."""
    return ref.fleet_percentile_reference(x, q)


def chip_specs(tree, n_chips: int, axis_name: str = "chips"):
    """Per-leaf `PartitionSpec` pytree for a fleet-state pytree: any leaf
    whose *trailing* axis is the `[n_chips]` fleet axis shards that axis
    over `axis_name`; every other leaf (scalars like `SorState.tick`, the
    window/rail leading axes of `FrameHistory`) replicates. The chip axis
    is trailing everywhere in this codebase — `PowerPlaneState` `[n]`,
    `TelemetryFrame` `[n]`, `FrameHistory` `[capacity, n_rails, n]`,
    `SorEstimate` `[n_rails, n]` — so trailing-axis matching is exact."""
    from jax.sharding import PartitionSpec as P

    def spec(leaf):
        nd = jnp.ndim(leaf)
        if nd >= 1 and jnp.shape(leaf)[-1] == n_chips:
            return P(*((None,) * (nd - 1)), axis_name)
        return P()

    return jax.tree_util.tree_map(spec, tree)


def shard_chip_tree(tree, mesh, n_chips: int, axis_name: str = "chips"):
    """`device_put` a fleet-state pytree onto `mesh` with its trailing chip
    axis sharded over `axis_name` (`chip_specs` placement) — how a caller
    makes the plane/`SorState` carry physically shard-resident before
    feeding a mesh'd train step or the sharded control round. Scalars and
    chip-less leaves replicate."""
    from jax.sharding import NamedSharding
    specs = chip_specs(tree, n_chips, axis_name)
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), tree, specs)


def sharded_fleet_reduce(x, *, mesh=None, axis_name: str = "chips",
                         use_shard_map: bool | None = None):
    """`fleet_reduce` for a fleet axis sharded across real devices.

    When `mesh` spans more than one device (the fleet axis is physically
    distributed), each device reduces its local `[n_chips/n_dev, n_fields]`
    shard through the Pallas/XLA `fleet_reduce` hot path, then the partials
    combine in-graph via `pmax`/`pmin`/`psum` inside `shard_map` — the
    worst-chip reduction never gathers per-chip telemetry onto one device.
    On a single-device (CPU) mesh, or with `mesh=None`, it falls back to the
    plain vmap-path `fleet_reduce`. `use_shard_map` overrides the guard
    (tests exercise the collective path on a 1-device mesh)."""
    if use_shard_map is None:
        use_shard_map = mesh is not None and mesh.devices.size > 1
    if not use_shard_map:
        return fleet_reduce(x)
    if mesh is None:
        raise ValueError("sharded_fleet_reduce needs a mesh for shard_map")
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has axes {mesh.axis_names}, not {axis_name!r}")
    from jax.sharding import PartitionSpec as P

    def local(xs):
        mx, mn, sm = fleet_reduce(xs)
        return (jax.lax.pmax(mx, axis_name), jax.lax.pmin(mn, axis_name),
                jax.lax.psum(sm, axis_name))

    return jax.shard_map(local, mesh=mesh, in_specs=(P(axis_name),),
                         out_specs=(P(), P(), P()), check_vma=False)(x)
