"""The latent-attention cell's operation and byte counts against hand
arithmetic, and its two readers on a hand-made trace."""

import json
from pathlib import Path

import pytest

from bench import spec, work_mla
from bench.trace import Event, Trace

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
MOON = json.loads((CONFIGS / "moonlight_16b_a3b.json").read_text())["shapes"]
PEAKS = spec.peaks("TPU v5 lite")


def test_parameters_add_up_to_the_chips_share():
    # attention: W_q 2048 x 16 x 192, W_kv_a 2048 x 576, the latent norm
    # 512, W_kv_b 512 x 16 x 256, W_o 16 x 128 x 2048, two RMSNorms
    attn = (2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256
            + 16 * 128 * 2048 + 2 * 2048)
    assert work_mla.attention_params(MOON) == attn
    assert work_mla.expert_params(MOON) == 3 * 2048 * 1408
    # with the 26 x 8 held experts and the embedding: the 3,364,615,296
    # parameters this chip holds
    assert (work_mla.fixed_params(MOON) + 26 * 8 * 3 * 2048 * 1408
            + 163840 * 2048) == 3_364_615_296


def test_mla_decode_call():
    f, b = work_mla.mla_decode_call(MOON, batch=16, context=4097)
    assert f == 2 * 16 * 16 * 4097 * (576 + 512)
    assert b == (16 * 4097 * 576 + 16 * 16 * (576 + 512)) * 2


def test_decode_step():
    w = work_mla.fixed_params(MOON)
    e = 3 * 2048 * 1408
    f, b = work_mla.decode_step(MOON, 16, 4100, experts_hit=160.0,
                                pairs_held=300.0)
    assert f == 2 * 16 * w + 2 * 300 * e + 27 * 2 * 16 * 16 * 4100 * 1088
    assert b == (w + 160 * e) * 2 + 16 * 27 * 576 * 4100 * 2 + 16 * 2048 * 2


def _ctx(kernel_calls: int):
    """A window of one call of 3 new tokens (2 decode steps) at 2 layers:
    the decode programs ran `kernel_calls` latent-attention kernels."""
    s = dict(MOON, n_layers=2)
    ops = [Event(f"mla_decode_attention.{i}", 100 * i, 100 * i + 10)
           for i in range(kernel_calls)]
    mods = [Event("jit_decode_step", 100 * i - 1, 100 * i + 50)
            for i in range(0, kernel_calls, 2)]
    tr = Trace({0: ops}, {0: mods}, [Event("bench.generate", -5, 10_000)])
    work = {"kind": "serve", "shapes": s, "batch": 16, "prompt_len": 4096,
            "new_tokens": 3, "moe_experts_hit": 10.0, "moe_pairs_held": 12.0}
    return {"trace": tr, "lo": -5, "hi": 10_000, "busy_ns": 0,
            "window_ns": 10_005, "work": work, "peaks": PEAKS}


def test_readers_on_a_hand_made_trace():
    ctx = _ctx(4)
    roof = spec.reader("mla_decode_roofline")(ctx)
    s, pk = ctx["work"]["shapes"], PEAKS
    want = 0.0
    for c in (4097, 4098):
        f, b = work_mla.mla_decode_call(s, 16, c)
        want += 2 * max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    assert roof == pytest.approx(100 * want / 40e-9)
    step = spec.reader("decode_step_roofline.decode_4k")(ctx)
    want = 0.0
    for c in (4097, 4098):
        f, b = work_mla.decode_step(s, 16, c, 10.0, 12.0)
        want += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    assert step == pytest.approx(100 * 2 * (want / 2) / (2 * 51e-9))


def test_readers_find_nothing_without_the_kernel_or_the_counters():
    # a program without the kernel (as at the parent) reads nothing
    assert spec.reader("mla_decode_roofline")(_ctx(0)) is None
    assert spec.reader("decode_step_roofline.decode_4k")(_ctx(0)) is None
    ctx = _ctx(4)
    ctx["work"].pop("moe_experts_hit")
    assert spec.reader("decode_step_roofline.decode_4k")(ctx) is None
