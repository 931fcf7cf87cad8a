"""The serving engine's per-step accounting and control round: `generate`
against the eager loop it replaces (accounting, a pull of each step's energy
and time, then the controller's round, one step at a time), and the
profiler's view of how often the engine pulls."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.control_plane import HostRailController, InGraphRailController
from repro.core.hwspec import FleetSpec
from repro.core.policy import POLICIES
from repro.core.power_plane import (PowerPlaneState, StepProfile,
                                    account_and_observe,
                                    account_fleet_and_observe)
from repro.core.sor import SorConfig
from repro.core.telemetry import scalar_view
from repro.models import registry
from repro.serve.engine import ServeEngine

CFG = get_config("minicpm_2b", tiny=True)
PREFILL = StepProfile(5e10, 5e8, 0.0)
DECODE = StepProfile(5e8, 5e8, 2e8)
MAX_LEN, BATCH, NEW, CALLS = 24, 2, 6, 2
FLEET = FleetSpec.sample(4, seed=7)


def _controller(kind: str):
    policy = POLICIES["phase-aware"]
    if kind == "host":
        return HostRailController(policy, n_chips=1)
    sor = SorConfig(ingest="frames") if kind in ("sor", "fleet") else None
    return InGraphRailController(policy, sor=sor)


@pytest.fixture(scope="module")
def params():
    return registry.build(CFG).init(jax.random.PRNGKey(0))


def _prompts(call: int) -> np.ndarray:
    return np.random.default_rng(call).integers(
        0, CFG.vocab_size, (BATCH, 8), dtype=np.int32)


def _engine(params, kind: str) -> ServeEngine:
    return ServeEngine(CFG, params, max_len=MAX_LEN, batch_size=BATCH,
                       prefill_profile=PREFILL, decode_profile=DECODE,
                       controller=_controller(kind),
                       fleet=FLEET if kind == "fleet" else None)


def _eager(params, kind: str):
    """The eager loop, step by step: account, pull the step's energy and
    time (the fleet mean), run the round. Returns (tokens of each call,
    plane, [energy_j, fleet_energy_j, model_time_s])."""
    api = registry.build(CFG)
    prefill = jax.jit(lambda p, t: api.prefill_fn(p, t, MAX_LEN))
    decode = jax.jit(api.decode_fn)
    ctrl = _controller(kind)
    fleet = FLEET if kind == "fleet" else None
    n = FLEET.n_chips if fleet is not None else 1
    state = {"plane": (PowerPlaneState.from_fleet(fleet) if fleet is not None
                       else PowerPlaneState.nominal()),
             "sor": None}
    sums = [0.0, 0.0, 0.0]

    def account(profile):
        if fleet is not None:
            plane, frame, m = account_fleet_and_observe(
                profile, state["plane"], fleet)
        else:
            plane, frame, m = account_and_observe(profile, state["plane"])
        e = scalar_view(m["energy_step_j"])
        sums[0] += e
        sums[1] += e * n
        sums[2] += scalar_view(m["t_step_s"])
        if getattr(ctrl, "sor", None) is not None and hasattr(
                ctrl, "control_step_sor"):
            if state["sor"] is None:
                state["sor"] = ctrl.init_sor(n if fleet is not None else None)
            plane, state["sor"] = ctrl.control_step_sor(plane, frame,
                                                        state["sor"])
        else:
            plane = ctrl.control_step(plane, frame)
        state["plane"] = plane

    calls = []
    for call in range(CALLS):
        p = _prompts(call)
        logits, cache, _ = prefill(params, jnp.asarray(p))
        account(PREFILL)
        out = [jnp.argmax(logits[:, -1, :CFG.vocab_size], -1)[:, None]
               .astype(np.int32)]
        for i in range(NEW - 1):
            logits, cache = decode(params, cache, {
                "tokens": out[-1], "cur_index": jnp.int32(8 + i)})
            account(DECODE)
            out.append(jnp.argmax(logits[:, -1, :CFG.vocab_size], -1)
                       [:, None].astype(np.int32))
        calls.append(np.asarray(jnp.concatenate(out, axis=1)))
    return calls, state["plane"], sums


@pytest.mark.parametrize("kind", ["sor", "no_sor", "fleet", "host"])
def test_generate_matches_the_eager_loop(params, kind):
    eng = _engine(params, kind)
    served = [eng.generate(_prompts(call), NEW) for call in range(CALLS)]
    tokens, plane, sums = _eager(params, kind)
    for got, want in zip(served, tokens):
        np.testing.assert_array_equal(got, want)
    for field in ("v_core", "v_hbm", "v_io", "comp_level", "energy_j",
                  "step"):
        np.testing.assert_allclose(
            np.asarray(getattr(eng.plane, field), np.float64),
            np.asarray(getattr(plane, field), np.float64),
            rtol=1e-6, err_msg=field)
    got = [eng.stats.energy_j, eng.stats.fleet_energy_j,
           eng.stats.model_time_s]
    np.testing.assert_allclose(got, sums, rtol=1e-6)
    assert eng.stats.decode_tokens == CALLS * (NEW - 1) * BATCH
    ctrl = eng.controller
    assert ctrl.last_request is not None
    if kind in ("sor", "fleet"):
        assert ctrl.last_envelope is not None


def _syncs(log_dir, eng: ServeEngine) -> list:
    """Stats of each `serve.sync` span of one traced `generate`."""
    with jax.profiler.trace(str(log_dir)):
        eng.generate(_prompts(1), NEW)
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    return [dict(e.stats)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name == "serve.sync"]


@pytest.mark.parametrize("kind, want", [
    ("sor", [{"steps": 1 + (NEW - 1)}]),
    ("host", [{"steps": 1}] * NEW),
])
def test_serve_sync_counts_the_steps_it_pulls(params, tmp_path, kind, want):
    eng = _engine(params, kind)
    eng.generate(_prompts(0), NEW)       # compiles outside the trace
    assert _syncs(tmp_path, eng) == want
