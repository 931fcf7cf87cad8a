"""Compile every Pallas kernel of `kernels/ops.py` at published widths for a
described TPU v5e chip, without a chip.

The TPU compiler is installed with jax; it compiles for a topology that is
described and not attached, and refuses what the chip would refuse (blocks
off the (8, 128) tiling, 1-D vectors, primitives with no TPU lowering) —
faults interpret mode never meets. Each test calls the kernel module itself
with `interpret=False`, since `ops` takes its CPU branch under
`JAX_PLATFORMS=cpu`, and asserts the kernel, by name, is in the compiled
program.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so under pytest-xdist only the worker
given this file touches it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.ops import pallas_kernels
from repro.models.lm import mamba_spec

F32, BF16 = jnp.float32, jnp.bfloat16
SEQ = 4096                                # the train_4k cell's sequence


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the persistent
    # cache without a chip, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernels(fn, sharding, *shapes):
    """The Pallas kernels in `fn` compiled for the described chip."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return pallas_kernels(jax.jit(fn).lower(*args).compile().as_text())


def _attn_widths(arch="minicpm_2b"):
    cfg = get_config(arch)
    plan = cfg.head_plan()
    return plan.n_q_pad, plan.n_kv_pad, plan.group, cfg.head_dim_


@pytest.mark.parametrize("T", [SEQ, 513])
def test_flash_attention_fwd_bwd_minicpm(one_chip, T):
    from repro.kernels import flash_attention as fa
    hq, hkv, group, dh = _attn_widths()

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, group=group)
            return jnp.sum(o.astype(F32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    assert _kernels(fwd_bwd, one_chip, ((1, T, hq, dh), BF16),
                    ((1, T, hkv, dh), BF16), ((1, T, hkv, dh), BF16)) == {
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv"}


@pytest.mark.parametrize("S", [552, SEQ])
def test_decode_attention_minicpm_cache(one_chip, S):
    from repro.kernels import decode_attention as da
    hq, hkv, group, dh = _attn_widths()
    assert _kernels(
        lambda q, k, v, n: da.decode_attention(q, k, v, n, group=group),
        one_chip, ((4, 1, hq, dh), BF16), ((4, S, hkv, dh), BF16),
        ((4, S, hkv, dh), BF16), ((4,), jnp.int32)) == {"decode_attention"}


def test_mamba2_ssd_zamba2(one_chip):
    from repro.kernels import mamba2_ssd as m2
    spec = mamba_spec(get_config("zamba2_1p2b"))
    H, P, G, N = spec.n_heads, spec.head_dim, spec.n_groups, spec.d_state
    assert _kernels(
        lambda *a: m2.mamba2_ssd(*a), one_chip,
        ((1, SEQ, H, P), BF16), ((1, SEQ, H), F32), ((H,), F32),
        ((1, SEQ, G, N), BF16), ((1, SEQ, G, N), BF16), ((H,), F32)) == {
        "mamba2_ssd"}


def test_rwkv6_scan_rwkv6_7b(one_chip):
    from repro.kernels import rwkv6_scan as r6
    cfg = get_config("rwkv6_7b")
    H, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    seq = ((1, SEQ, H, dh), BF16)
    assert _kernels(lambda *a: r6.rwkv6_scan(*a), one_chip, seq, seq, seq,
                    ((1, SEQ, H, dh), F32), ((H, dh), F32)) == {"rwkv6_scan"}


def test_quantize_int8_minicpm_mlp(one_chip):
    from repro.kernels import quant_codec as qc
    cfg = get_config("minicpm_2b")
    assert _kernels(lambda x: qc.quantize_int8(x), one_chip,
                    ((cfg.d_model, cfg.d_ff), F32)) == {"quantize_int8"}


N_CHIPS, N_RAILS, WINDOW = 1024, 3, 32   # a fleet of 1024 chips, 3 rails


def test_fleet_reduce(one_chip):
    from repro.kernels import fleet_telemetry as ft
    assert _kernels(lambda x: ft.fleet_reduce(x), one_chip,
                    ((N_CHIPS, 5), F32)) == {"fleet_reduce"}


def test_sor_accumulate_and_fit(one_chip):
    from repro.kernels import fleet_telemetry as ft
    win = ((WINDOW, N_RAILS * N_CHIPS), F32)
    lane = ((N_RAILS * N_CHIPS,), F32)
    assert _kernels(lambda x, y, w: ft.sor_accumulate(x, y, w),
                    one_chip, win, win, win) == {"sor_accumulate"}
    assert _kernels(
        lambda x, y, w, b, g: ft.sor_fit(x, y, w, b, g, min_slope=0.5,
                                         min_spread_v=0.005,
                                         conf_samples=8.0),
        one_chip, win, win, win, lane, lane) == {"sor_fit"}
