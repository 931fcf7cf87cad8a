"""The operation and byte counts against hand arithmetic."""

import json
from pathlib import Path

from bench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
MINICPM = json.loads((CONFIGS / "minicpm_2b.json").read_text())["shapes"]
ZAMBA2 = json.loads((CONFIGS / "zamba2_1p2b.json").read_text())["shapes"]


def test_kv_bytes_per_token_published_and_padded():
    # 40 layers x (K, V) x heads x 64 x 2 bytes
    assert work.kv_bytes_per_token(MINICPM) == 40 * 2 * 36 * 64 * 2 == 368_640
    assert work.kv_bytes_per_token(MINICPM, kv_heads=48) == 491_520


def test_dense_parameter_counts():
    # attention 4 x 2304 x 2304, SwiGLU 3 x 2304 x 5760, two norms
    assert work.dense_layer_params(MINICPM) == 4 * 2304 ** 2 + 3 * 2304 * 5760 \
        + 2 * 2304 == 61_051_392
    assert work.dense_head_params(MINICPM) == 2304 * 122753 + 2304


def test_dense_decode_step():
    f, b = work.dense_decode_step(MINICPM, batch=8, context=600)
    w = 40 * 61_051_392 + 2304 * 122753 + 2304
    assert f == 8 * (2 * w + 4 * 40 * 36 * 64 * 600)
    assert b == w * 2 + 8 * 368_640 * 600 + 8 * 2304 * 2


def test_decode_attention_call():
    f, b = work.decode_attention_call(MINICPM, batch=8, context=513)
    assert f == 4 * 8 * 36 * 64 * 513
    assert b == (8 * 513 * 2 * 36 * 64 + 2 * 8 * 36 * 64) * 2


def test_mamba_layer_params():
    # in-projections to z, x (4096 each), B, C (64 each), dt (64 heads);
    # out-projection; convolutions of width 4 with bias over x, B, C;
    # A_log, D, dt_bias a head; the gated norm and the layer norm
    proj = 2048 * (2 * 4096 + 2 * 64 + 64) + 4096 * 2048
    conv = 5 * (4096 + 128)
    assert work.mamba_layer_params(ZAMBA2) == proj + conv + 3 * 64 + 4096 \
        + 2048


def test_shared_block_uses_and_train_step():
    assert work.shared_uses(ZAMBA2) == 5          # after layers 6 .. 30
    shared = 4 * 2048 * 2048 + 3 * 2048 * 8192 + 2 * 2048
    assert work.shared_block_params(ZAMBA2) == shared
    tokens = 4 * 512
    fwd = (2 * (34 * work.mamba_layer_params(ZAMBA2) + 5 * shared
                + 2048 * 32000) * tokens
           + 34 * 5 * 64 * 64 * 64 * tokens
           + 5 * 2 * 4 * 32 * 64 * 512 ** 2)
    assert work.hybrid_train_step(ZAMBA2, 4, 512) == 3 * fwd


def test_mamba2_ssd_call():
    f, b = work.mamba2_ssd_call(ZAMBA2, 4, 512)
    tokens = 2048
    assert f == 5 * 64 * 64 * 64 * tokens
    assert b == tokens * (4096 * 2 + 64 * 4 + 2 * 64 * 2 + 4096 * 2) \
        + 4 * 64 * 64 * 64 * 4
