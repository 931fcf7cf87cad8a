"""The plain f32 references against the program's model at a tiny size on
the CPU, on the same weights (the bench's, laid out for the program).

The program computes in bfloat16, the references in f32, so they agree to
bfloat16's rounding: about 1e-2 of the largest value after a few layers.
The tolerances below are a few times what the tiny sizes read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.drivers.common import program_config
from bench.drivers.serve import program_params
from bench.drivers.train import PROGRAM_LEAF, program_tree
from bench.reference import dense, hybrid
from bench.tests import tiny


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 17])
def test_dense_reference_matches_the_program_forward(seed):
    from repro.models.lm import forward_logits
    c = tiny.cell("minicpm_2b.decode")["config"]
    s, cfg = c["shapes"], program_config(c)
    toks = np.random.default_rng(seed % 1000).integers(
        0, s["vocab"], (2, 24), dtype=np.int32)
    got = jax.jit(lambda p, t: forward_logits(p, t, cfg))(
        program_params(cfg, s, seed), jnp.asarray(toks))[..., :s["vocab"]]
    rows = np.repeat(np.arange(2), 24)
    cols = np.tile(np.arange(24), 2)
    want = dense.logits_at(weights.make("dense", s, seed), jnp.asarray(toks),
                           jnp.asarray(rows), jnp.asarray(cols),
                           eps=s["norm_eps"], theta=s["rope_theta"])
    got = np.asarray(got, np.float32).reshape(-1, s["vocab"])
    err = np.max(np.abs(got - np.asarray(want))) / np.max(np.abs(want))
    assert err < 3e-2, err
    # and the fp8 control is further off than the program
    lo = dense.logits_at(weights.make("dense", s, seed), jnp.asarray(toks),
                         jnp.asarray(rows), jnp.asarray(cols),
                         eps=s["norm_eps"], theta=s["rope_theta"],
                         quant=True)
    err8 = np.max(np.abs(np.asarray(lo) - np.asarray(want))) / \
        np.max(np.abs(want))
    assert err8 > 2 * err, (err8, err)


def test_hybrid_reference_matches_the_program_loss_and_gradients():
    from repro.models import registry
    c = tiny.cell("zamba2_1p2b.train")["config"]
    s, cfg = c["shapes"], program_config(c)
    seed = 5
    api = registry.build(cfg, remat="full")
    w = weights.make("hybrid", s, seed)
    params = program_tree(w, cfg.vocab_padded)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, s["vocab"], (2, 65), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    (loss, _), g = jax.jit(jax.value_and_grad(api.loss_fn, has_aux=True))(
        params, batch)
    ref = {n: g_ for n, _, g_ in _collect(hybrid.grads_by_layer(
        w, batch["tokens"], batch["labels"], s))}
    assert abs(float(loss) - float(ref["loss"])) < 1e-2 * float(ref["loss"])
    flat = {jax.tree_util.keystr(p, simple=True, separator="/"): a
            for p, a in jax.tree_util.tree_flatten_with_path(g)[0]}
    norms = {n: float(jnp.linalg.norm(ref[n])) for n in PROGRAM_LEAF}
    med = float(np.median(list(norms.values())))
    for n, path in PROGRAM_LEAF.items():
        a = np.asarray(flat[path], np.float32)
        b = np.asarray(ref[n], np.float32)
        if n == "embed":
            a = a[:b.shape[0]]
        elif n == "lm_head":
            a = a[:, :b.shape[1]]
        gap = abs(np.linalg.norm(a) - np.linalg.norm(b)) / max(
            np.linalg.norm(b), med)
        assert gap < 3e-2, (n, gap)


def _collect(gen):
    """Stacks per-layer gradients back into whole leaves."""
    out, layers = {}, {}
    for name, i, g in gen:
        if i is None:
            out[name] = g
        else:
            layers.setdefault(name, {})[i] = g
    for name, parts in layers.items():
        out[name] = jnp.stack([parts[i] for i in sorted(parts)])
    for name, g in out.items():
        yield name, None, g
