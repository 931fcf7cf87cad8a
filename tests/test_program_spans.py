"""Host spans of the serving engine and the trainer, and the names of their
jitted programs, as the JAX profiler records them: a TINY `generate` and a
two-step `Trainer.run`, each traced on the CPU and read back with
`ProfileData`."""

import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.control_plane import HostRailController, InGraphRailController
from repro.core.policy import POLICIES
from repro.core.power_plane import PowerPlaneState, StepProfile
from repro.core.sor import SorConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import registry
from repro.optim import adamw
from repro.serve.engine import ServeEngine
from repro.train.step import StepConfig, jit_train_step, make_train_step
from repro.train.trainer import Trainer, TrainerConfig

CFG = get_config("minicpm_2b", tiny=True)
PROFILE = StepProfile(flops_per_chip=5e9, hbm_bytes_per_chip=5e8,
                      ici_bytes_per_chip=2e8, grad_bytes_per_chip=1.8e8)
NEW = 4


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict

    def holds(self, other: "Span") -> bool:
        return (other is not self and self.start <= other.start
                and other.end <= self.end)


def _traced(log_dir, fn):
    """(fn's result, host events of the trace, by start)."""
    with jax.profiler.trace(str(log_dir)):
        out = fn()
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                               dict(e.stats) if e.name.startswith(
                                   ("serve.", "train.")) else {})
                          for e in line.events]
    return out, sorted(spans, key=lambda s: (s.start, -s.end))


def _children(parent: Span, spans: list, prefix: str) -> list:
    """The `prefix` spans directly inside `parent`, in order."""
    inner = [s for s in spans if s.name.startswith(prefix)
             and parent.holds(s)]
    return [s for s in inner if not any(o.holds(s) for o in inner)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    params = registry.build(CFG).init(jax.random.PRNGKey(0))
    eng = ServeEngine(CFG, params, max_len=24, batch_size=2,
                      prefill_profile=PROFILE, decode_profile=PROFILE,
                      controller=InGraphRailController(
                          POLICIES["phase-aware"],
                          sor=SorConfig(ingest="frames")))
    prompts = np.random.default_rng(0).integers(
        0, CFG.vocab_size, (2, 8), dtype=np.int32)
    plain = eng.generate(prompts, NEW)
    traced, spans = _traced(tmp_path_factory.mktemp("serve"),
                            lambda: eng.generate(prompts, NEW))
    return plain, traced, spans


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    api = registry.build(CFG, remat="none")
    params = api.init(jax.random.PRNGKey(0))
    opt_cfg = adamw.AdamWConfig(grad_clip_norm=1.0)
    step = jit_train_step(make_train_step(
        lambda p, b: api.loss_fn(p, b), opt_cfg,
        lambda s: jnp.float32(1e-3) + 0.0 * s, PROFILE, StepConfig()),
        donate=False)
    data = SyntheticLM(DataConfig(vocab_size=CFG.vocab_size, seq_len=16,
                                  global_batch=2, seed=0))
    tr = Trainer(step, data, TrainerConfig(
        total_steps=1, ckpt_every=1,
        ckpt_dir=str(tmp_path_factory.mktemp("ckpt")), async_ckpt=False,
        controller=HostRailController(POLICIES["phase-aware"])),
        {"params": params, "opt": adamw.init_state(params, opt_cfg),
         "plane": PowerPlaneState.nominal(), "ef": None})
    tr.run()                 # compiles the step outside the trace
    tr.start_step, tr.cfg.total_steps = 1, 3
    _, spans = _traced(tmp_path_factory.mktemp("train"), tr.run)
    return spans


def test_generate_opens_with_one_call_span_holding_the_prefill(served):
    _, _, spans = served
    (gen,) = [s for s in spans if s.name == "serve.generate"]
    assert gen.stats == {"call": 2, "batch": 2}
    top = _children(gen, spans, "serve.")
    assert top[0].name == "serve.prefill"
    # the prefill's accounting dispatches its one program in
    # `serve.control`; its sums are pulled at the end of the call
    acc = _children(top[0], spans, "serve.")
    assert [s.name for s in acc] == ["serve.account"]
    assert [s.name for s in _children(acc[0], spans, "serve.")] == \
        ["serve.control"]


def test_each_decode_step_has_its_spans_in_order(served):
    _, _, spans = served
    (gen,) = [s for s in spans if s.name == "serve.generate"]
    top = _children(gen, spans, "serve.")
    steps = top[1:-2]
    assert [s.name for s in steps] == \
        ["serve.decode", "serve.account", "serve.sample"] * (NEW - 1)
    # one pull of the whole call's accounting, once the tokens are fetched:
    # the prefill's step and each decode step's
    assert [s.name for s in top[-2:]] == ["serve.fetch", "serve.sync"]
    assert top[-1].stats == {"steps": NEW}
    for i in range(NEW - 1):
        dec, acc, smp = steps[3 * i:3 * i + 3]
        assert dec.stats == smp.stats == {"call": 2, "token": i}
        assert acc.stats == {}
        assert [s.name for s in _children(acc, spans, "serve.")] == \
            ["serve.control"]


def test_tokens_do_not_change_under_the_profiler(served):
    plain, traced, _ = served
    assert plain.shape == (2, NEW)
    np.testing.assert_array_equal(plain, traced)


def test_jitted_programs_carry_their_names(served):
    _, _, spans = served
    names = {s.name for s in spans}
    assert {"PjitFunction(decode_step)",
            "PjitFunction(control_round)"} <= names
    dec = [s for s in spans if s.name == "serve.decode"]
    assert all(any(p.name == "PjitFunction(decode_step)" and d.holds(p)
                   for p in spans) for d in dec)


def test_trainer_step_holds_each_stage(trained):
    steps = [s for s in trained if s.name == "train.step"]
    assert [s.stats for s in steps] == [{"step": 1}, {"step": 2}]
    for st in steps:
        assert [s.name for s in _children(st, trained, "train.")] == [
            "train.batch", "train.dispatch", "train.wait", "train.control",
            "train.telemetry", "train.ckpt"]
