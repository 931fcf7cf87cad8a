"""The DeepSeek-V3-block cell at a tiny size on the CPU: the plain f32
reference (bench/reference/mla_moe.py) against the program's forward on the
same weights, and whole runs of the harness past its look for a chip
through the `serve_mla` driver: sound, it comes out correct; with the fp8
control or an altered served token in the program's place, not correct.

The reference comparison runs the program in float32 on the bench's
weights widened to f32: in bfloat16 a near-tie between the sixth and
seventh expert's scores picks another expert now and then, which moves
that position's logits by a third of their range at these sizes and says
nothing about the arithmetic. In f32 the two differ only in the order of
their sums and in the absorbed against the expanded form of attention:
they read under 1e-6 of the largest logit; the tolerance is 1e-4."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights_mla
from bench.drivers import serve_mla
from bench.drivers.common import Clock, program_config
from bench.reference import mla_moe
from bench.run import run_cell

NAME = "moonlight_16b_a3b.decode_4k"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
MLA = {"vocab": 500, "d_model": 64, "n_layers": 3, "n_dense_layers": 1,
       "n_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 16, "v_head_dim": 16, "d_ff": 96, "moe_d_ff": 32,
       "n_experts": 8, "n_held_experts": 4, "first_held_expert": 2,
       "experts_per_token": 2, "n_shared_experts": 2,
       "routed_scaling_factor": 2.446, "norm_eps": 1e-05,
       "rope_theta": 50000.0}
# the control test's size: a widest gap swings with the sample, so it
# scores as many served tokens as a full-size run (2 rows x 256)
SMALL_MLA = dict(MLA, d_model=128, kv_lora_rank=64, d_ff=256, moe_d_ff=64,
                 vocab=4000)


def program(s: dict) -> dict:
    return {"arch": "moonlight_16b_a3b", "overrides": {
        "n_layers": s["n_layers"], "d_model": s["d_model"],
        "n_heads": s["n_heads"], "n_kv_heads": s["n_heads"],
        "d_ff": s["d_ff"], "vocab_size": s["vocab"],
        "kv_lora_rank": s["kv_lora_rank"],
        "qk_nope_head_dim": s["qk_nope_head_dim"],
        "qk_rope_head_dim": s["qk_rope_head_dim"],
        "v_head_dim": s["v_head_dim"], "n_dense_layers": s["n_dense_layers"],
        "moe_d_ff": s["moe_d_ff"], "n_experts": s["n_experts"],
        "experts_per_token": s["experts_per_token"],
        "n_shared_experts": s["n_shared_experts"],
        "first_held_expert": s["first_held_expert"],
        "n_held_experts": s["n_held_experts"]}}


def cell(shapes: dict = MLA, **traffic) -> dict:
    """The real cell, its configuration and traffic cut to a tiny size."""
    c = copy.deepcopy(spec.cell(spec.benchmark(), NAME))
    c["config"]["shapes"] = dict(shapes)
    c["config"]["program"] = program(shapes)
    c["traffic"].update({"batch": 2, "prompt_len": 8, "new_tokens": 4,
                         "check_rows": 2, **traffic})
    return c


def _run(c, seed=11):
    return run_cell(c, seed, 0.3, False, Clock(), DEVICE,
                    spec.peaks("TPU v5 lite"))


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 17])
def test_reference_matches_the_program_forward(seed):
    from repro.models.lm import forward_logits
    c = cell()["config"]
    s = c["shapes"]
    cfg = dataclasses.replace(program_config(c), dtype="float32")
    toks = np.random.default_rng(seed % 1000).integers(
        0, s["vocab"], (2, 24), dtype=np.int32)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        serve_mla.program_params(cfg, s, seed))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: forward_logits(p, t, cfg))(
            params, jnp.asarray(toks))
    got = np.asarray(got[..., :s["vocab"]], np.float32).reshape(-1, s["vocab"])
    rows, cols = np.repeat(np.arange(2), 24), np.tile(np.arange(24), 2)

    def ref(quant):
        return np.asarray(mla_moe.logits_at(
            weights_mla.make(s, seed), jnp.asarray(toks), jnp.asarray(rows),
            jnp.asarray(cols), shapes=tuple(sorted(s.items())), quant=quant))

    want = ref(False)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-4, err
    # and the fp8 control is further off than the program
    err8 = np.max(np.abs(ref(True) - want)) / np.max(np.abs(want))
    assert err8 > 2 * err, (err8, err)


def test_sound_run_is_correct_and_counts_held_pairs():
    c = cell()
    d = serve_mla.DRIVER(c, 7)
    d.setup()
    d.window(0.3, Clock())
    w = d.trace_work()
    # 2 rows x 2 picks on each of the 2 MoE layers: at most 8 held pairs a
    # step, and at most that many experts hit
    assert 0 < w["moe_pairs_held"] <= 8
    assert 0 < w["moe_experts_hit"] <= w["moe_pairs_held"]
    r = _run(c)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0


def test_fp8_control_is_not_correct(monkeypatch):
    check = serve_mla.ServeMla.check
    monkeypatch.setattr(serve_mla.ServeMla, "check",
                        lambda self: check(self, "fp8"))
    r = _run(cell(SMALL_MLA, batch=4, new_tokens=64, check_rows=8), seed=23)
    assert not r["correct"], r["check"]


def test_served_token_altered_is_not_correct(monkeypatch):
    make = serve_mla.build_engine

    def broken(cfg, traffic, params):
        engine = make(cfg, traffic, params)
        decode = engine._decode

        def altered(params, cache, batch):
            logits, cache = decode(params, cache, batch)
            # every row serves token 7 whatever the model says
            return logits.at[:, -1, 7].set(1e4), cache
        engine._decode = altered
        return engine
    monkeypatch.setattr(serve_mla, "build_engine", broken)
    r = _run(cell(check_rows=4))
    assert not r["correct"], r["check"]
