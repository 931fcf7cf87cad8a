"""The mla_moe family (DeepSeek-V3 block: latent attention, held routed
experts, shared experts) against the plain f32 reference of the benchmark
(bench/reference/mla_moe.py), at TINY sizes on the CPU with seeded random
weights; the `mla_decode_attention` kernel against `kernels/ref.py`; and
the dense and hybrid families' trees, which this family must leave alone.

The model comparisons run the program in float32 on the reference's
weights: in bfloat16 a near-tie between the k-th and the next expert's
score picks another expert now and then, which moves that position's logits
by a third of their range at these sizes and says nothing of the
arithmetic. In f32 the two differ only in the order of their sums and in
the absorbed against the expanded form of attention, under 1e-6 of the
largest logit."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights_mla
from bench.drivers.serve_mla import program_params
from bench.reference import mla_moe as mla_ref
from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.mla_decode_attention import mla_decode_attention
from repro.models import lm, mlp, registry

F32 = jnp.float32
TOL = 1e-4   # f32 against f32: sums in another order (reads under 1e-6)


def _cfg(first_held=0, n_held=None):
    cfg = dataclasses.replace(get_config("moonlight_16b_a3b", tiny=True),
                              dtype="float32", first_held_expert=first_held)
    return dataclasses.replace(cfg,
                               n_held_experts=n_held or cfg.n_held_experts)


def _shapes(cfg) -> dict:
    return {"vocab": cfg.vocab_size, "d_model": cfg.d_model,
            "n_layers": cfg.n_layers, "n_dense_layers": cfg.n_dense_layers,
            "n_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "d_ff": cfg.d_ff,
            "moe_d_ff": cfg.moe_d_ff, "n_experts": cfg.n_experts,
            "n_held_experts": cfg.n_held_experts,
            "first_held_expert": cfg.first_held_expert,
            "experts_per_token": cfg.experts_per_token,
            "n_shared_experts": cfg.n_shared_experts,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def _params(cfg, seed):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), program_params(
        cfg, _shapes(cfg), seed))


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


@pytest.mark.parametrize("seed,first_held", [(0, 0), (2 ** 33 + 17, 4)])
def test_prefill_then_decode_match_the_reference_forward(seed, first_held):
    """Prefill of 12 tokens, then 5 decode steps through the latent cache
    (teacher-forced), against the reference's one forward over all 17."""
    cfg = _cfg(first_held)
    api, s = registry.build(cfg), _shapes(cfg)
    params = _params(cfg, seed)
    toks = np.random.default_rng(seed % 97).integers(
        0, cfg.vocab_size, (2, 17), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache, cur = api.prefill_fn(params, jnp.asarray(toks[:, :12]),
                                            24)
        got = [logits[:, -1]]
        for t in range(12, 16):
            logits, cache = api.decode_fn(params, cache, {
                "tokens": jnp.asarray(toks[:, t:t + 1]),
                "cur_index": jnp.int32(t)})
            got.append(logits[:, -1])
    got = jnp.stack(got, 1)[..., :cfg.vocab_size].reshape(-1, cfg.vocab_size)
    rows, cols = np.repeat(np.arange(2), 5), np.tile(np.arange(11, 16), 2)
    want = mla_ref.logits_at(weights_mla.make(s, seed), jnp.asarray(toks),
                             jnp.asarray(rows), jnp.asarray(cols),
                             shapes=tuple(sorted(s.items())))
    assert cur == 12
    assert _rel(got, want) < TOL


def _kernel_inputs(S, lengths, layer, seed=0):
    L, B, H, R, Dr = 3, len(lengths), 4, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    return (jax.random.normal(ks[0], (B, H, R), bf),
            jax.random.normal(ks[1], (B, H, Dr), bf),
            jax.random.normal(ks[2], (L, B, S, R), bf),
            jax.random.normal(ks[3], (L, B, S, Dr), bf),
            jnp.int32(layer), jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("path", ["kernel", "ops"])
@pytest.mark.parametrize("S,bk,lengths,layer", [
    (512, 128, (1, 200, 512), 2), (300, 128, (300, 129, 7), 1)])
def test_mla_decode_attention_matches_reference(monkeypatch, path, S, bk,
                                                lengths, layer):
    """One layer of a stacked cache, ragged per-row lengths (the blocks
    past a row's length are not fetched); a cache that does not tile (300)
    pads. The kernel weighs the bf16 cache with bf16 probabilities (f32
    sums), the reference in f32 throughout: 2e-2 is a few bf16 roundings
    of an output of order 1."""
    args = _kernel_inputs(S, lengths, layer)
    scale = 192 ** -0.5
    if path == "kernel":
        got = mla_decode_attention(*args, scale=scale, bk=bk, interpret=True)
    else:
        monkeypatch.setattr(ops, "_pallas_mode", lambda: "interpret")
        got = jax.jit(ops.mla_decode_attention.__wrapped__,
                      static_argnames=("scale",))(*args, scale=scale)
    want = ref.mla_decode_reference(*args, scale=scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def _moe_case(n_held, first_held, seed=3):
    cfg = _cfg(first_held, n_held)
    s = _shapes(cfg)
    w = weights_mla.make(dict(s, n_held_experts=cfg.n_experts,
                              first_held_expert=0), seed)["moe_layers"]
    one = jax.tree_util.tree_map(lambda a: a[0].astype(F32), w)
    ex = jax.tree_util.tree_map(
        lambda a: a[first_held:first_held + n_held], one["experts"])
    p = {"router": one["router"], "e_bias": one["e_bias"],
         "w_gate": ex["w_gate"], "w_in": ex["w_up"], "w_out": ex["w_down"],
         "shared": {"w_gate": one["shared"]["w_gate"],
                    "w_in": one["shared"]["w_up"],
                    "w_out": one["shared"]["w_down"]}}
    h = jax.random.normal(jax.random.PRNGKey(seed), (3, 40, cfg.d_model), F32)
    return cfg, s, one, p, h


@pytest.mark.parametrize("n_held", [1, 2, 4])
def test_held_shares_add_up_to_the_uncut_layer(n_held):
    """Each disjoint share of the 8 experts run as the held set; their
    outputs, with the shared experts counted once, sum to the reference
    layer with every expert held."""
    n_experts = _cfg().n_experts
    total, shared = 0.0, None
    with jax.default_matmul_precision("highest"):
        for first in range(0, n_experts, n_held):
            cfg, s, one, p, h = _moe_case(n_held, first)
            y, _ = mlp.held_moe_apply(p, h, lm.held_moe_spec(cfg))
            total = total + y
            shared = mlp.swiglu(p["shared"], h)
        want = mla_ref.moe_layer(h, one, dict(s, n_held_experts=n_experts,
                                              first_held_expert=0))
    got = total - (n_experts // n_held - 1) * shared
    assert _rel(got, want) < TOL


@pytest.mark.parametrize("tile", [0, 7])
def test_no_pair_is_dropped(monkeypatch, tile):
    """The pairs counted are the routed pairs to held experts, and every
    one is computed: the few-token path (every held expert over every row)
    and the grouped path in passes of `tile` pairs (0: not run) give the
    same output."""
    cfg, s, one, p, h = _moe_case(4, 2)
    spec = lm.held_moe_spec(cfg)
    with jax.default_matmul_precision("highest"):
        y1, counts = mlp.held_moe_apply(p, h, spec)
        if tile:
            monkeypatch.setattr(mlp, "HELD_TILE", tile)
            y2, counts2 = mlp.held_moe_apply(p, h, spec)
            assert counts2 == counts
            assert _rel(y2, y1) < TOL
    idx, _ = mlp.route_sigmoid(p, h.reshape(-1, cfg.d_model), spec)
    idx = np.asarray(idx)
    held = (idx >= 2) & (idx < 6)
    assert int(counts["moe_pairs_held"]) == int(held.sum()) > 0
    assert int(counts["moe_experts_hit"]) == len(np.unique(idx[held]))


def _tree(t):
    return sorted((jax.tree_util.keystr(k), a.shape, str(a.dtype))
                  for k, a in jax.tree_util.tree_flatten_with_path(t)[0])


# sha256 (first 16 hex digits) of repr((params, cache)) as `_tree` lists
# every leaf's (path, shape, dtype), TINY params and a (2, 16) cache,
# taken on the tree before the mla_moe family came in
TREES = {"minicpm_2b": "0df1649562a40bb6", "zamba2_1p2b": "a26b2d068161a1a7"}


@pytest.mark.parametrize("arch", sorted(TREES))
def test_dense_and_hybrid_trees_unchanged(arch):
    api = registry.build(get_config(arch, tiny=True))
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: api.init_decode_cache(2, 16))
    listed = repr((_tree(params), _tree(cache)))
    assert hashlib.sha256(listed.encode()).hexdigest()[:16] == TREES[arch]


def test_generate_pulls_the_held_expert_counters_in_its_one_sync(tmp_path):
    """The decode steps' sums ride the call's one `serve.sync` pull: its
    stats and `engine.stats` get the same counts."""
    import glob

    from jax.profiler import ProfileData

    from repro.core.control_plane import InGraphRailController
    from repro.core.policy import POLICIES
    from repro.core.sor import SorConfig
    from repro.serve.engine import ServeEngine

    cfg = get_config("moonlight_16b_a3b", tiny=True)
    eng = ServeEngine(cfg, registry.build(cfg).init(jax.random.PRNGKey(0)),
                      max_len=16, batch_size=2,
                      controller=InGraphRailController(
                          POLICIES["phase-aware"],
                          sor=SorConfig(ingest="frames")))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8), dtype=np.int32)
    eng.generate(prompts, 4)                      # compiles outside the trace
    before = (eng.stats.moe_pairs_held, eng.stats.moe_experts_hit)
    with jax.profiler.trace(str(tmp_path)):
        eng.generate(prompts, 4)
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    syncs = [dict(e.stats) for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU" for line in p.lines
             for e in line.events if e.name == "serve.sync"]
    pairs = eng.stats.moe_pairs_held - before[0]
    hit = eng.stats.moe_experts_hit - before[1]
    assert syncs == [{"steps": 4, "moe_pairs_held": pairs,
                      "moe_experts_hit": hit}]
    # 3 decode steps x 2 rows x 2 picks x 2 MoE layers at most
    assert 0 < hit <= pairs <= 24
