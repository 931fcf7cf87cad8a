"""Config schema: ModelConfig (architecture), ShapeConfig (assigned input
shapes), and the arch registry. One module per assigned architecture lives
next to this file; each exports CONFIG (exact paper/HF hyperparameters) and
TINY (reduced same-family config for CPU smoke tests)."""

from __future__ import annotations

import dataclasses
import importlib
import math

from repro.models.common import HeadPlan, plan_head_padding

VOCAB_ALIGN = 2048  # pad vocab to a multiple (TP-16 x 128-lane friendly)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str       # dense | moe | mla_moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    # mla_moe (DeepSeek-V3 block): latent attention widths, leading dense
    # layers, expert width beside the dense width (d_ff), shared experts,
    # sigmoid routing with a selection-only correction bias, and the range
    # of routed experts this chip holds under expert parallelism
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    n_dense_layers: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_held_expert: int = 0
    n_held_experts: int = 0
    # SSM / hybrid
    ssm_state: int = 0
    attn_every: int = 0         # hybrid: shared attn block after every k SSM blocks
    sliding_window: int = 0     # used by hybrid attn for long-context cells
    # encoder-decoder (whisper)
    n_enc_layers: int = 0
    enc_seq_len: int = 1500     # whisper: 30 s of audio after conv frontend
    # VLM stub frontend
    n_img_tokens: int = 0
    # numerics / distribution
    dtype: str = "bfloat16"
    tp: int = 16                # model-axis size the head plan targets
    remat_group: int = 0        # 0 -> auto (largest divisor of n_layers <= 8)

    # ---- derived ----------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def vocab_padded(self) -> int:
        return math.ceil(self.vocab_size / VOCAB_ALIGN) * VOCAB_ALIGN

    def head_plan(self) -> HeadPlan:
        return plan_head_padding(self.n_heads, self.n_kv_heads, self.tp)

    @property
    def remat_group_(self) -> int:
        if self.remat_group:
            return self.remat_group
        for g in (8, 7, 6, 5, 4, 3, 2, 1):
            if self.n_layers % g == 0:
                return g
        return 1

    @property
    def has_attention(self) -> bool:
        return self.family not in ("ssm",)

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks), for 6ND math."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        plan = None
        n = V * D * 2  # embed + lm_head (untied)
        for _ in range(self.n_layers):
            if self.family in ("dense", "moe", "vlm", "encdec"):
                if plan is None:
                    plan = self.head_plan()
                Dh = self.head_dim_
                n += D * (plan.n_q_pad + 2 * plan.n_kv_pad) * Dh + plan.n_q_pad * Dh * D
                if self.family == "moe" and self.n_experts:
                    n += self.n_experts * 3 * D * F + D * self.n_experts
                else:
                    n += 3 * D * F
            elif self.family == "hybrid":
                d_in = 2 * D
                n += D * (2 * d_in + 2 * self.ssm_state + d_in // 64) + d_in * D
            elif self.family == "ssm":
                n += 5 * D * D + 2 * D * F
        if self.family == "encdec":
            for _ in range(self.n_enc_layers):
                Dh = self.head_dim_
                n += 4 * D * self.n_heads * Dh + 2 * D * F
                n += 4 * D * self.n_kv_heads * Dh  # cross-attn kv
        return n

    def active_param_count(self) -> int:
        """MoE: parameters touched per token (for 6*N_active*D FLOPs math)."""
        if self.family != "moe" or not self.n_experts:
            return self.param_count()
        D, F = self.d_model, self.d_ff
        total = self.param_count()
        moe_all = self.n_layers * self.n_experts * 3 * D * F
        moe_active = self.n_layers * self.experts_per_token * 3 * D * F
        return total - moe_all + moe_active


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


# The assigned shape grid (system prompt): every LM arch x these four.
SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS = (
    "zamba2_1p2b", "minicpm_2b", "granite_20b", "mistral_large_123b",
    "qwen2p5_14b", "rwkv6_7b", "internvl2_2b", "whisper_base",
    "grok1_314b", "qwen3_moe_30b_a3b", "moonlight_16b_a3b",
)

# long_500k runs only for sub-quadratic archs (DESIGN.md §4); whisper has a
# decoder (enc-dec) so decode shapes run, with 500k skipped (full attention).
LONG_CONTEXT_ARCHS = ("zamba2_1p2b", "rwkv6_7b")


def get_config(arch: str, tiny: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.TINY if tiny else mod.CONFIG


def cells(include_skips: bool = False):
    """The (arch x shape) dry-run grid. Yields (arch, shape_name, runnable)."""
    for arch in ARCH_IDS:
        for sname in SHAPES:
            runnable = True
            if sname == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                runnable = False
            if include_skips or runnable:
                yield arch, sname, runnable
