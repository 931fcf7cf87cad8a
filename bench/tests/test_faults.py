"""A whole run of the harness past its look for a chip, at a tiny size on
the CPU: sound, it comes out correct; with the timed path broken
underneath, `correct` comes out false."""

import jax
import jax.numpy as jnp
import pytest

from bench.drivers import serve, train
from bench.drivers.common import Clock
from bench.run import run_cell
from bench.spec import peaks
from bench.tests import tiny

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = peaks("TPU v5 lite")


def _run(cell, seed=11):
    return run_cell(cell, seed, 0.3, False, Clock(), DEVICE, PEAKS)


def _wrap_train_step(monkeypatch, fault):
    build = train.Train.build

    def broken(self):
        build(self)
        self.trainer.train_step = fault(self.trainer.train_step)

    monkeypatch.setattr(train.Train, "build", broken)


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def test_sound_runs_are_correct():
    for name in ("minicpm_2b.decode", "zamba2_1p2b.train"):
        r = _run(tiny.cell(name))
        assert r["correct"], (name, r["check"])
        assert r["failed"] == 0 and r["attempted"] > 0


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    def fault(step):
        def f(params, opt, plane, ef, batch):
            out = step(_copy(params), _copy(opt), plane, ef, batch)
            return (params, opt) + tuple(out[2:])
        return f
    _wrap_train_step(monkeypatch, fault)
    r = _run(tiny.cell("zamba2_1p2b.train"))
    assert not r["correct"], r["check"]


def test_train_step_on_half_the_batch(monkeypatch):
    def fault(step):
        def f(params, opt, plane, ef, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt, plane, ef, half)
        return f
    _wrap_train_step(monkeypatch, fault)
    r = _run(tiny.cell("zamba2_1p2b.train"))
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("at_step", [0, 2])
def test_served_token_altered_where_it_is_produced(monkeypatch, at_step):
    make = serve.build_engine

    def broken(cfg, traffic, params):
        engine = make(cfg, traffic, params)
        decode, calls = engine._decode, [0]

        def altered(params, cache, batch):
            logits, cache = decode(params, cache, batch)
            if calls[0] % (traffic["new_tokens"] - 1) == at_step:
                # every row serves token 7 whatever the model says
                logits = logits.at[:, -1, 7].set(1e4)
            calls[0] += 1
            return logits, cache
        engine._decode = altered
        return engine
    monkeypatch.setattr(serve, "build_engine", broken)
    cell = tiny.cell("minicpm_2b.decode", check_rows=4)
    r = _run(cell)
    assert not r["correct"], r["check"]
