"""Batched serving engine: continuous prefill + greedy decode with KV caches,
power-plane energy accounting per token, and the serve-side host controller.

Serving is where the paper's "communication-light phases" argument (§I) bites
hardest: decode is HBM-bound, so the PhaseAware policy undervolts VDD_CORE
and VDD_IO during decode and restores them for prefill bursts — the serving
analogue of the transceiver case study.

Fleet serving (`fleet=` constructor arg): the engine drives a `[n_chips]`
power plane seeded from a `hwspec.FleetSpec` — every decode/prefill step is
accounted at each chip's own process-varied operating point, and a bare
policy is wrapped in `WorstChipGate` so no chip undervolts past what the
worst chip's telemetry allows (serving replicas step together; the fleet is
only as fast and as safe as its weakest chip). Default is the original
scalar single-chip behavior.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import sor as sor_mod
from repro.core.control_plane import (RAIL_LANES, InGraphRailController,
                                      _concrete_or_none, _run_policy,
                                      as_controller, pinned_lane_masks,
                                      pinned_rails, rail_floors,
                                      sharded_control_round, with_sor)
from repro.core.hwspec import FleetSpec
from repro.core.policy import WorstChipGate
from repro.core.power_plane import (BatchShares, PowerPlaneState,
                                    StepProfile, account_and_observe,
                                    account_fleet_and_observe,
                                    batched_lane_time_s, chip_power_w_jnp,
                                    step_time_s)
from repro.core.rails import TPU_V5E_RAIL_MAP
from repro.core.telemetry import scalar_view
from repro.models import registry

# per-rail failure observables the serve loop reads back each tick (the
# over-bound goodput-degrade signal) — extras keys overlaid by the caller's
# observe() hook plus the typed grad_error field
_OBS_KEYS = ("grad_error", "straggle_rate", "hbm_error_rate")


@dataclasses.dataclass
class ServeStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    energy_j: float = 0.0          # per-chip (fleet mean) energy
    model_time_s: float = 0.0
    fleet_energy_j: float = 0.0    # whole-fleet energy (mean x n_chips)
    decode_sheds: int = 0          # decode batches deferred by admission gate
    defer_time_s: float = 0.0      # simulated time spent waiting out sheds
    # shed/defer breakdown: which rail's envelope floor pinned the fleet,
    # and the reason code the deferral carried (the aggregate counters stay
    # for back-compat; these are their per-rail / per-reason split)
    sheds_by_rail: dict = dataclasses.field(default_factory=dict)
    sheds_by_reason: dict = dataclasses.field(default_factory=dict)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_len: int,
                 batch_size: int,
                 prefill_profile: StepProfile | None = None,
                 decode_profile: StepProfile | None = None,
                 controller=None, policy=None,
                 fleet: FleetSpec | None = None,
                 sor: "sor_mod.SorConfig | None" = None,
                 admission_gate: bool = False,
                 router=None, mesh=None,
                 shard_control: "bool | None" = None,
                 batch_cap: "int | None" = None,
                 batch_shares: "BatchShares | None" = None):
        self.cfg = cfg
        self.params = params
        self.api = registry.build(cfg)
        self.max_len = max_len
        self.batch_size = batch_size
        self.fleet_spec = fleet
        self.plane = (PowerPlaneState.from_fleet(fleet) if fleet is not None
                      else PowerPlaneState.nominal())
        # single actuation path: a RailController (a bare policy is wrapped
        # into the in-graph controller for back-compat; on a fleet plane a
        # bare policy is additionally gated on the worst chip's telemetry)
        if controller is not None and policy is not None:
            raise ValueError("pass either controller= or policy=, not both")
        if (fleet is not None and policy is not None
                and not isinstance(policy, WorstChipGate)
                and not hasattr(policy, "control_step")):
            policy = WorstChipGate(policy)
        self.controller = as_controller(controller if controller is not None
                                        else policy)
        # learned safe-operating-region state (core/sor.py): the engine's
        # serving loop is eager, so it threads the functional SorState itself
        if sor is not None:
            if not isinstance(self.controller, InGraphRailController):
                raise ValueError("sor= needs an in-graph policy/controller "
                                 "(the serve loop threads SorState through "
                                 "InGraphRailController.control_round); "
                                 "for a HostRailController pass sor= to the "
                                 "controller itself")
            # shared semantics with make_fleet_train_step (control_plane.
            # with_sor): validate, reject legacy policies, never mutate a
            # caller-owned controller, conflict loudly
            self.controller = with_sor(self.controller, sor)
        self._sor_state = None
        # admission gate: shed/defer decode batches while the arbitrated
        # request shows any chip pinned at any requested rail's envelope
        # floor (all-rails admission — a VDD_HBM floor during decode gates
        # exactly like the historical VDD_IO check)
        self.admission_gate = admission_gate
        self.last_shed_reason: str | None = None
        self._last_pinned_rails: list[str] = []
        # headroom-aware placement (serve/router.py): serve_trace() routes a
        # traffic trace over the fleet by per-rail voltage headroom
        self.router = router
        if router is not None and fleet is None:
            raise ValueError("router= places work across a fleet; pass "
                             "fleet=FleetSpec (n_chips=1 degenerates to the "
                             "plain engine)")
        self.last_trace: dict | None = None
        # continuous batching: `batch_cap=B` makes each chip a token-level
        # decode batch over its B resident lanes — the fused tick's rate
        # model shares the roofline terms across lanes (batched_lane_time_s)
        # instead of granting every slot the chip's full single-lane rate.
        # Lanes ARE the router's slots, so the cap must equal the router's
        # capacity; None keeps the historical full-rate-per-slot model, and
        # batch_cap=1 degenerates to it EXACTLY (the rate model is bitwise
        # the base model at b=1), so both reuse the unbatched tick graph —
        # the PR-9 ledger bit-equality oracle.
        if batch_cap is not None:
            if router is None:
                raise ValueError("batch_cap batches a chip's resident "
                                 "lanes; pass router= (the lanes are the "
                                 "router's slots)")
            if batch_cap < 1:
                raise ValueError(f"batch_cap must be >= 1, got {batch_cap}")
            if batch_cap != router.capacity:
                raise ValueError(
                    f"batch_cap={batch_cap} must equal the router's "
                    f"capacity ({router.capacity}) — lanes are the "
                    f"router's slots, one number describes both")
        self.batch_cap = batch_cap
        self.batch_shares = batch_shares or BatchShares()
        self._batched = batch_cap is not None and batch_cap > 1
        if batch_shares is not None and batch_cap is None:
            raise ValueError("batch_shares= tunes the batched rate model; "
                             "pass batch_cap= as well")
        self.prefill_profile = prefill_profile or StepProfile(1e9, 1e9, 0.0)
        self.decode_profile = decode_profile or StepProfile(1e8, 1e9, 0.0)
        self.stats = ServeStats()
        # fleet-scale serving: `mesh=` threads a 1-D "chips" device mesh
        # into the fused serve tick so the in-tick learned control round
        # runs shard-parallel (control_plane.sharded_control_round under
        # the router). `shard_control` mirrors FleetStepConfig: None
        # auto-enables when the mesh spans more than one device; True
        # forces the shard_map path even on a 1-device mesh (the
        # bit-equality pin); False leaves a supplied mesh unused.
        self.mesh = mesh
        if shard_control is None:
            shard_control = mesh is not None and mesh.devices.size > 1
        if shard_control:
            if mesh is None:
                raise ValueError("shard_control=True needs a mesh")
            if fleet is None:
                raise ValueError("mesh= shards the [n_chips] serve plane; "
                                 "pass fleet=FleetSpec")
            if not (isinstance(self.controller, InGraphRailController)
                    and self.controller.sor is not None):
                raise ValueError(
                    "mesh= shards the in-tick learned control round; build "
                    "the engine with an in-graph controller carrying "
                    "sor=SorConfig(...) (cross-chip policies are rejected "
                    "— their fleet reduction would only see one shard)")
            if self.n_chips % mesh.devices.size:
                raise ValueError(
                    f"n_chips={self.n_chips} is not divisible by the mesh "
                    f"size {mesh.devices.size}")
            self._sharded_round = sharded_control_round(self.controller,
                                                        mesh)
        else:
            self._sharded_round = None
        self.shard_control = bool(shard_control)
        self._tick_cache: dict = {}   # (observe id, tick_s, bound) -> jit
        # per-step accounting: an in-graph (or no) controller runs the
        # accounting, the observation and its round as one `control_round`
        # program per step; any other controller decides on the host from
        # concrete telemetry, so it gets the accounting alone
        self._fused = (self.controller is None
                       or isinstance(self.controller, InGraphRailController))
        self._step_cache: dict = {}   # StepProfile -> jitted step program
        # (energy_step_j, t_step_s) device scalars of the accounted steps
        # not yet added to `stats`
        self._unpulled: list = []

        self._calls = 0              # generate() calls, the spans' `call`

        # named programs: the device trace lists each execution by the
        # jitted function's name
        def decode_step(params, cache, batch):
            return self.api.decode_fn(params, cache, batch)

        def prefill(params, toks):
            return self.api.prefill_fn(params, toks, max_len)

        self._decode = jax.jit(decode_step)
        self._prefill = jax.jit(prefill) if self.api.prefill_fn else None

    @property
    def n_chips(self) -> int:
        return self.plane.n_chips

    def _control_tick(self, frame) -> None:
        """One controller round on `frame`, dispatched on its own: the
        host control path's per-step round and the routed trace loop's."""
        if self.controller is None:
            return
        c = self.controller
        with jax.profiler.TraceAnnotation("serve.control"):
            if getattr(c, "sor", None) is not None and hasattr(
                    c, "control_step_sor"):
                if self._sor_state is None:
                    self._sor_state = c.init_sor(
                        self.n_chips if self.plane.is_fleet else None)
                # one fused control round per decision: observe + refit
                # (amortized by refresh_every) + decide + arbitrate run
                # as a single cached jitted program, so per-decision
                # controller cost stays flat as the fleet grows
                self.plane, self._sor_state = c.control_step_sor(
                    self.plane, frame, self._sor_state)
            else:
                self.plane = c.control_step(self.plane, frame)

    def _step_program(self, profile: StepProfile):
        """The jitted per-step program for `profile`, built once.

        Fused (in-graph or no controller): `control_round(plane, sor_state)
        -> (plane', sor_state', request, envelope, energy_step_j,
        t_step_s)` runs the accounting, the observation and the controller's
        round (`InGraphRailController.control_round` with SOR, decide +
        arbitrate without) as one program; the SorState is donated when
        the controller donates. Otherwise `account(plane) -> (plane', frame,
        energy_step_j, t_step_s)`, the accounting alone."""
        fn = self._step_cache.get(profile)
        if fn is not None:
            return fn
        spec, c = self.fleet_spec, self.controller

        def observe(plane):
            if spec is not None:
                return account_fleet_and_observe(profile, plane, spec)
            return account_and_observe(profile, plane)

        if not self._fused:
            def account(plane):
                plane, frame, m = observe(plane)
                return plane, frame, m["energy_step_j"], m["t_step_s"]

            fn = jax.jit(account)
        else:
            use_sor = c is not None and c.sor is not None

            def control_round(plane, sor_state):
                plane, frame, m = observe(plane)
                request = env = None
                if use_sor:
                    plane, sor_state, request, env = c.control_round(
                        plane, frame, sor_state)
                elif c is not None:
                    plane, request = _run_policy(
                        c.policy, plane, frame, frame, c.rail_map,
                        host=False)
                return (plane, sor_state, request, env, m["energy_step_j"],
                        m["t_step_s"])

            fn = jax.jit(control_round,
                         donate_argnums=(1,) if use_sor and c.donate else ())
        self._step_cache[profile] = fn
        return fn

    def _account_step(self, profile: StepProfile) -> None:
        """Account one step and run the controller's round after it. The
        step's energy and time stay on the device (`_unpulled`) until
        `_pull`, except on the host path, whose round pulls the telemetry
        anyway."""
        c = self.controller
        step = self._step_program(profile)
        with jax.profiler.TraceAnnotation("serve.account"):
            if self._fused:
                if (c is not None and c.sor is not None
                        and self._sor_state is None):
                    self._sor_state = c.init_sor(
                        self.n_chips if self.plane.is_fleet else None)
                with jax.profiler.TraceAnnotation("serve.control"):
                    (self.plane, self._sor_state, request, env, e,
                     t) = step(self.plane, self._sor_state)
                if c is not None:
                    c.last_request, c.last_envelope = request, env
                self._unpulled.append((e, t))
            else:
                self.plane, frame, e, t = step(self.plane)
                self._unpulled.append((e, t))
                self._pull()
                self._control_tick(frame)

    def _pull(self) -> None:
        """Add the unpulled steps' energy and time to `stats`, in step
        order, from one `device_get`: `serve.sync`, stat `steps` the number
        of steps it carries. Array-aware reductions (TelemetryLog's
        scalar-view convention): scalars pass through, [n_chips] metrics
        report the fleet mean."""
        if not self._unpulled:
            return
        steps, self._unpulled = self._unpulled, []
        with jax.profiler.TraceAnnotation("serve.sync", steps=len(steps)):
            for e, t in jax.device_get(steps):
                e = scalar_view(e)
                self.stats.energy_j += e
                self.stats.fleet_energy_j += e * self.n_chips
                self.stats.model_time_s += scalar_view(t)

    def _account(self, profile: StepProfile, n: int = 1):
        for _ in range(n):
            self._account_step(profile)
        self._pull()

    def _worst_chip_pinned(self) -> bool:
        """Did the latest arbitration pin any chip at any requested rail's
        envelope floor (request wanted at/below what the envelope holds)?
        Records the per-rail breakdown for the shed counters; the shed
        signal carries the arbitrated `RailRequest.reason`."""
        c = self.controller
        req = getattr(c, "last_request", None) if c is not None else None
        env = getattr(c, "last_envelope", None) if c is not None else None
        if req is None:
            return False
        masks = pinned_rails(self.plane, req, envelope=env)
        rails = [r for r, m in masks.items() if m.any()]
        if not rails:
            return False
        self._last_pinned_rails = rails
        self.last_shed_reason = req.reason or "pinned-at-envelope-floor"
        return True

    def _defer_tick(self) -> None:
        """Admission shed: the batch waits out one *accounted* decode tick
        before being admitted — simulated time passes and the control loop
        runs (so the controller genuinely gets a round to back off the
        floor, e.g. escalate compression or raise the rail); a real
        deployment would route the deferred batch to another replica."""
        self.stats.decode_sheds += 1
        reason = self.last_shed_reason or "pinned-at-envelope-floor"
        self.stats.sheds_by_reason[reason] = (
            self.stats.sheds_by_reason.get(reason, 0) + 1)
        for rail in self._last_pinned_rails:
            self.stats.sheds_by_rail[rail] = (
                self.stats.sheds_by_rail.get(rail, 0) + 1)
        self.stats.defer_time_s += scalar_view(
            step_time_s(self.decode_profile, self.plane))
        self._account_step(self.decode_profile)

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 eos_id: int | None = None) -> np.ndarray:
        """prompts [B, Tp] int32 -> generated [B, max_new_tokens].

        Host spans name each stage for the profiler: `serve.generate`
        (stats `call`, this engine's call count from 1, and `batch`) holds
        `serve.prefill`, then per decode step `serve.decode` and
        `serve.sample` (stats `call` and `token`, the step from 0) with
        `_account_step`'s `serve.account` between them, then `serve.fetch`
        and last `serve.sync`, the one pull of the call's accounted energy
        and time (per step instead on the host control path).
        """
        self._calls += 1
        call = self._calls
        with jax.profiler.TraceAnnotation("serve.generate", call=call,
                                          batch=len(prompts)):
            B, Tp = prompts.shape
            assert B == self.batch_size, (B, self.batch_size)
            if self._prefill is None:
                raise NotImplementedError(
                    "encdec serving uses serve_encdec()")
            with jax.profiler.TraceAnnotation("serve.prefill"):
                toks = jnp.asarray(prompts, jnp.int32)
                logits, cache, cur = self._prefill(self.params, toks)
                self._account_step(self.prefill_profile)
                self.stats.prefill_tokens += B * Tp
                next_tok = jnp.argmax(logits[:, -1, : self.cfg.vocab_size],
                                      axis=-1).astype(jnp.int32)[:, None]
                cur_index = jnp.int32(Tp)

            out = [next_tok]
            for i in range(max_new_tokens - 1):
                if self.admission_gate and self._worst_chip_pinned():
                    self._defer_tick()
                with jax.profiler.TraceAnnotation("serve.decode", call=call,
                                                  token=i):
                    logits, cache = self._decode(
                        self.params, cache,
                        {"tokens": out[-1], "cur_index": cur_index})
                self._account_step(self.decode_profile)
                with jax.profiler.TraceAnnotation("serve.sample", call=call,
                                                  token=i):
                    self.stats.decode_tokens += B
                    nxt = jnp.argmax(logits[:, -1, : self.cfg.vocab_size],
                                     axis=-1).astype(jnp.int32)[:, None]
                    out.append(nxt)
                    cur_index = cur_index + 1
                    done = eos_id is not None and bool(jnp.all(nxt == eos_id))
                if done:
                    break
            with jax.profiler.TraceAnnotation("serve.fetch"):
                tokens = np.asarray(jnp.concatenate(out, axis=1))
            self._pull()
            return tokens

    def serve_trace(self, trace, *, max_ticks: int = 20_000,
                    observe=None, tick_s: "float | None" = None,
                    error_bound: float = 5e-3, degrade: float = 0.5,
                    prefill_speedup: float = 8.0,
                    fused: "bool | None" = None,
                    fast_forward: bool = False,
                    migrate_after_ticks: "int | None" = None,
                    migrate_stall_s_per_token: float = 1e-3):
        """Route a seeded traffic trace (`serve/traffic.py`) over the fleet
        and return the per-request SLO ledger (`serve/router.py`).

        A modeled continuous-batching loop in simulated time — no model
        forward runs; what is modeled is exactly what the control plane
        governs: per-chip step time (f ∝ v, process variation), per-chip
        busy/idle power, and per-chip reliability. Each tick:

        1. arrivals with `t_arrival_s <= now` join the FIFO queue;
        2. the fleet is accounted (`account_fleet_and_observe`) and the
           caller's `observe(plane, frame, tick, busy_frac)` overlays the
           per-rail failure observables (measured error world — the bench
           couples onsets to load, the consolidated-margins drift);
        3. the controller runs one round (SOR learning included), exactly
           the `_account` control path;
        4. per-rail headroom and the pinned-chip drain mask feed the
           router, which places queued requests head-of-line FIFO (a
           request it cannot place defers — reason `capacity` when every
           slot is full, `pinned-drain` when only pinned chips had room);
        5. resident requests progress at their chip's modeled rate
           (`tick_s / t_step_chip` decode tokens per tick, batched decode:
           every slot advances together; prefill runs `prefill_speedup` x
           faster). A chip whose measured observables sit over
           `error_bound` this tick delivers only `degrade` of its rate —
           the goodput cost of operating past the frontier (the BER
           retransmission analogue), which is what makes zero-headroom
           placement genuinely expensive;
        6. energy is accounted busy/idle-blended per chip (idle slots do
           not burn dynamic power) into the ledger and the engine stats;
           each resident request is charged its share of its chip's busy
           energy.

        `fused` selects the tick's device path (docs/serve.md "serving at
        fleet scale"). `None` (default) auto-resolves: in-graph
        controllers (and controller-less engines) run ONE jitted
        `serve_tick` per tick — accounting, observe overlay, control
        round, busy/idle energy rescale and the per-chip rate/over-bound
        flags compile into a single dispatch whose packed host bundle is
        the tick's only device transfer, and slot bookkeeping runs as
        numpy `[n_chips, capacity]` arrays. Host-actuated controllers
        (PMBus path) fall back to the historical per-tick loop, which
        `fused=False` also forces — the oracle the fused path's ledger is
        pinned against in tests. With a `mesh=` engine the fused tick's
        learned round runs shard-parallel (`sharded_control_round`).

        `fast_forward=True` (fused path only) jumps simulated time to the
        next arrival whenever the queue is empty and no slot is resident —
        the skipped ticks run no accounting and no control round, so the
        trajectory is NOT tick-for-tick identical to a fast_forward=False
        run across idle gaps (default off; `last_trace` reports the ticks
        skipped).

        `migrate_after_ticks=K` (fused path, headroom-planner routers
        only) arms in-flight migration: a chip whose pinned/over-bound
        flag has held for K consecutive ticks gets its resident
        decode-phase lanes re-placed by `router.plan_migration` onto the
        deepest-headroom unpinned chips, most-decode-left first. A
        migrated lane pays a KV-transfer stall of
        `migrate_stall_s_per_token x tokens processed so far` before it
        progresses again (it occupies its destination lane throughout),
        and the ledger records a "migrated" event with source/destination.
        Sustained `pinned-drain` pressure thereby MOVES work instead of
        only deferring admits; a triggered chip that keeps lanes (no
        eligible destination) re-arms after another K ticks.

        `tick_s` defaults to the fleet-mean decode step time at the current
        operating point. Deterministic given (trace, observe, controller):
        placement ties break by chip index and all randomness lives in the
        caller's seeded trace/observe."""
        if self.router is None:
            raise ValueError("serve_trace needs the engine built with "
                             "router= (HeadroomRouter or RoundRobinRouter)")
        if self.fleet_spec is None:
            raise ValueError("serve_trace routes over a fleet plane; pass "
                             "fleet=FleetSpec")
        from repro.serve.router import RequestLedger
        # routers carry placement state (the round-robin cursor) — reset it
        # per trace so back-to-back traces on one engine place identically
        reset = getattr(self.router, "reset", None)
        if callable(reset):
            reset()
        if fused is None:
            fused = (self.controller is None
                     or isinstance(self.controller, InGraphRailController))
        if fused and self.controller is not None and not isinstance(
                self.controller, InGraphRailController):
            raise ValueError(
                "fused=True compiles the control round into the serve "
                "tick; a host-actuated controller (PMBus path) needs "
                "fused=False")
        if fast_forward and not fused:
            raise ValueError("fast_forward rides the fused tick path; "
                             "drop fused=False (or the host controller)")
        if self._batched and not fused:
            raise ValueError(
                "continuous batching (batch_cap >= 2) rides the fused "
                "tick path — the loop path is kept verbatim as the "
                "batch-cap=1 semantics oracle; drop fused=False")
        if migrate_after_ticks is not None:
            if migrate_after_ticks < 1:
                raise ValueError(f"migrate_after_ticks must be >= 1, got "
                                 f"{migrate_after_ticks}")
            if not fused:
                raise ValueError("migration rides the fused tick path; "
                                 "drop fused=False")
            if not callable(getattr(self.router, "plan_migration", None)):
                raise ValueError(
                    "migrate_after_ticks needs a router with a migration "
                    "planner (HeadroomRouter.plan_migration) — the "
                    "round-robin baseline is headroom-blind and cannot "
                    "pick destinations")
        if tick_s is None:
            tick_s = float(scalar_view(
                step_time_s(self.decode_profile, self.plane)))
        ledger = RequestLedger()
        arrivals = sorted(trace, key=lambda r: (r.t_arrival_s, r.rid))
        kw = dict(max_ticks=max_ticks, observe=observe, tick_s=tick_s,
                  error_bound=error_bound, degrade=degrade,
                  prefill_speedup=prefill_speedup)
        if fused:
            return self._serve_trace_fused(
                arrivals, ledger, fast_forward=fast_forward,
                migrate_after_ticks=migrate_after_ticks,
                migrate_stall_s_per_token=migrate_stall_s_per_token, **kw)
        return self._serve_trace_loop(arrivals, ledger, **kw)

    # -- fused path: one jitted device round + vectorized host bookkeeping ----

    def _serve_tick_jit(self, observe, tick_s: float, error_bound: float):
        """The cached jitted serve tick for this (observe, tick_s,
        error_bound) world — cached like `control_step_sor`'s round jit so
        repeated traces dispatch without retracing."""
        key = (id(observe), float(tick_s), float(error_bound))
        fn = self._tick_cache.get(key)
        if fn is None:
            fn = self._build_serve_tick(observe, tick_s, error_bound)
            self._tick_cache[key] = fn
        return fn

    def _build_serve_tick(self, observe, tick_s: float, error_bound: float):
        """Build ONE fused serve tick: accounting -> observe overlay ->
        control round -> busy/idle energy rescale -> per-chip rate/
        over-bound flags, pure jnp, jitted as a single program. Returns
        `(plane', sor_state', bundle, request, env)` where `bundle` is the
        packed `[13, n_chips]` float32 host bundle — rows 0-3 `e_tick`,
        `e_busy`, `t_step`, `over`; rows 4-6 per-rail floors; rows 7-9
        per-rail headroom; rows 10-12 per-rail pinned masks (RAIL_LANES
        order) — the tick's ONLY device->host transfer. A continuous-
        batching engine (`batch_cap >= 2`) grows it to `[15, n_chips]`:
        row 13 the effective batch depth the rate was computed at
        (`max(round(busy_frac * batch_cap), 1)` — occupancy recovered
        exactly from the busy fraction, so the tick signature does not
        change) and row 14 the batched PER-LANE step time
        (`batched_lane_time_s` over this tick's roofline terms)."""
        spec = self.fleet_spec
        variation = {k: jnp.asarray(v) for k, v in spec.variation().items()}
        profile = self.decode_profile
        c = self.controller
        n = self.n_chips
        rail_map = (getattr(c, "rail_map", TPU_V5E_RAIL_MAP)
                    if c is not None else TPU_V5E_RAIL_MAP)
        use_sor = (c is not None and getattr(c, "sor", None) is not None
                   and hasattr(c, "control_step_sor"))
        sharded = self._sharded_round
        ts = jnp.float32(tick_s)
        batched = self._batched
        cap = jnp.float32(self.batch_cap) if batched else None
        shares = self.batch_shares

        def _b(x):
            return jnp.broadcast_to(
                jnp.atleast_1d(jnp.asarray(x, jnp.float32)), (n,))

        def tick(plane, sor_state, busy_frac, tick_idx):
            plane, frame, m = account_fleet_and_observe(profile, plane,
                                                        spec)
            if observe is not None:
                frame = observe(plane, frame, tick_idx, busy_frac)
            request = env = None
            if c is None:
                pass
            elif use_sor:
                if sharded is not None:
                    pre = plane
                    plane, sor_state, _conf_sum, _conf_min = sharded(
                        plane, frame, sor_state)
                    # the request/envelopes the bundle rows need are
                    # re-derived OUTSIDE the shard_map on the global
                    # (sharded) shapes: envelopes are elementwise in the
                    # post-ingest estimate and the decision is elementwise
                    # per chip — the same math the per-shard round
                    # arbitrated with
                    env = sor_mod.rail_envelopes(sor_state.estimate, c.sor)
                    request = c.policy.decide_env(pre, frame, env)
                else:
                    plane, sor_state, request, env = c.control_round(
                        plane, frame, sor_state)
            else:
                plane, request = _run_policy(
                    c.policy, plane, frame, frame, rail_map, host=False)
            # busy/idle-blended energy: accounting assumed every chip
            # fully busy — rescale to this tick's occupancy (idle slots
            # burn static + uncore power only) and rewrite the plane's
            # accumulator to match
            p_busy = m["power_w"]
            p_idle = chip_power_w_jnp(plane, 0.0, 0.0, 0.0, spec.base,
                                      variation=variation)
            p_eff = p_idle + (p_busy - p_idle) * busy_frac
            e_tick = p_eff * ts
            plane = dataclasses.replace(
                plane, energy_j=plane.energy_j - m["energy_step_j"]
                + e_tick)
            over = jnp.zeros((n,), bool)
            for key in _OBS_KEYS:
                v = frame.get(key)
                if v is None:
                    continue
                a = _b(v)
                over = over | ((~jnp.isnan(a))
                               & (a > jnp.float32(error_bound)))
            floors = rail_floors(plane, env, rail_map)
            held = jnp.stack([_b(getattr(plane, f))
                              for f in ("v_core", "v_hbm", "v_io")])
            pinned = pinned_lane_masks(plane, request, rail_map,
                                       envelope=env)
            rows = [
                jnp.stack([_b(e_tick), _b((p_eff - p_idle) * ts),
                           _b(m["t_step_s"]), over.astype(jnp.float32)]),
                floors,
                held - floors,
                pinned.astype(jnp.float32),
            ]
            if batched:
                # effective batch depth from the busy fraction (occ/cap is
                # exact in f32 for occ <= cap; round kills the dust) and
                # the shared-roofline per-lane step time it implies
                b_eff = jnp.maximum(jnp.round(_b(busy_frac) * cap), 1.0)
                t_lane = batched_lane_time_s(
                    _b(m["t_comp_s"]), _b(m["t_mem_s"]), _b(m["t_coll_s"]),
                    b_eff, shares)
                rows.append(jnp.stack([b_eff, t_lane]))
            bundle = jnp.concatenate(rows)
            return plane, sor_state, bundle, request, env

        donate = (1,) if (use_sor and getattr(c, "donate", False)) else ()
        return jax.jit(tick, donate_argnums=donate)

    def _serve_trace_fused(self, arrivals, ledger, *, max_ticks, observe,
                           tick_s, error_bound, degrade, prefill_speedup,
                           fast_forward, migrate_after_ticks=None,
                           migrate_stall_s_per_token=1e-3):
        """The fused serve loop: per tick, ONE jitted device dispatch and
        ONE packed bundle transfer; slot progress/finish bookkeeping runs
        as numpy `[n_chips, capacity]` lane arrays (no per-slot dicts).
        Ledger and stats are pinned equal to `_serve_trace_loop` on the
        same world (tests/test_serve_scale.py); a batched engine reads its
        per-lane rate from the bundle's grown rows, and migration (when
        armed) re-places decode-phase lanes off chips whose pinned/over
        flag held for K ticks, before placement sees the tick's queue."""
        from repro.serve.router import headroom_from_packed
        n = self.n_chips
        cap = self.router.capacity
        c = self.controller
        use_sor = (c is not None and getattr(c, "sor", None) is not None
                   and hasattr(c, "control_step_sor"))
        if use_sor and self._sor_state is None:
            self._sor_state = c.init_sor(n if self.plane.is_fleet else None)
        if self._sharded_round is not None:
            from repro.kernels import ops as _ops
            self.plane = _ops.shard_chip_tree(self.plane, self.mesh, n)
            if self._sor_state is not None:
                self._sor_state = _ops.shard_chip_tree(
                    self._sor_state, self.mesh, n)
        tick_fn = self._serve_tick_jit(observe, tick_s, error_bound)

        n_req = len(arrivals)
        arr_t = np.asarray([r.t_arrival_s for r in arrivals], np.float64)
        req_prefill = np.asarray([r.prefill_tokens for r in arrivals],
                                 np.int64)
        req_decode = np.asarray([r.decode_tokens for r in arrivals],
                                np.int64)
        # per-request busy-energy accumulator, charged to the ledger once
        # at trace end: one float64 add per resident tick in tick order —
        # float-equal to the loop path's per-tick ledger.charge
        energy_acc = np.zeros(n_req, np.float64)
        charged = np.zeros(n_req, bool)

        slot_req = np.full((n, cap), -1, np.int64)   # arrival index; -1 free
        slot_prefill = np.zeros((n, cap), np.float64)
        slot_decode = np.zeros((n, cap), np.float64)
        # KV-transfer stall left per lane (seconds): a freshly migrated
        # lane occupies its destination but makes no progress until its
        # stall drains
        slot_stall = np.zeros((n, cap), np.float64)
        migrating = migrate_after_ticks is not None
        streak = np.zeros(n, np.int64)   # consecutive pinned/over ticks
        n_migrations = 0

        pending: collections.deque = collections.deque()  # arrival indices
        ai = 0
        t = 0.0
        max_occ = 0
        degraded_ticks = 0
        resident_degraded_ticks = 0
        ticks_run = 0
        ff_ticks = 0

        for tick in range(max_ticks):
            active = slot_req >= 0
            resident = bool(active.any())
            if ai >= n_req and not pending and not resident:
                break
            if (fast_forward and not pending and not resident
                    and ai < n_req and arr_t[ai] > t):
                # idle fleet, empty queue: jump simulated time to the
                # first on-grid tick that reaches the next arrival. The
                # skipped ticks run no accounting and no control round.
                k = int(np.ceil((arr_t[ai] - t) / tick_s))
                t += k * tick_s
                ff_ticks += k
            ticks_run += 1
            while ai < n_req and arrivals[ai].t_arrival_s <= t:
                ledger.admit(arrivals[ai])
                pending.append(ai)
                ai += 1
            occ = active.sum(axis=1)
            busy_frac = jnp.asarray(
                np.minimum(occ.astype(np.float64), cap) / cap, jnp.float32)

            self.plane, self._sor_state, bundle, request, env = tick_fn(
                self.plane, self._sor_state, busy_frac, jnp.int32(tick))
            if c is not None:
                c.last_request = _concrete_or_none(request)
                c.last_envelope = _concrete_or_none(env)
            b = np.asarray(jax.device_get(bundle), np.float64)  # 1 transfer
            e_np, e_busy, t_step = b[0], b[1], b[2]
            over = b[3] > 0.5
            headroom = headroom_from_packed(b[7:10])
            pinned_rows = b[10:13] > 0.5
            pinned = pinned_rows.any(axis=0)
            # batched engines progress lanes at the shared-roofline
            # per-lane step time the tick computed (row 14); unbatched
            # (and batch_cap=1) engines keep the base step time — the
            # SAME host arithmetic either way, so batch_cap=1 stays
            # bit-equal to the historical path
            t_rate = b[14] if self._batched else t_step

            self.stats.energy_j += float(e_np.mean())
            self.stats.fleet_energy_j += float(e_np.sum())
            self.stats.model_time_s += tick_s
            ledger.tick_energy(float(e_np.sum()))
            if resident:
                chips, slots = np.nonzero(active)
                idx = slot_req[chips, slots]
                np.add.at(energy_acc, idx, e_busy[chips] / occ[chips])
                charged[idx] = True
                resident_degraded_ticks += int((over & (occ > 0)).sum())

            # in-flight migration: a chip whose pinned/over flag held K
            # consecutive ticks hands its decode-phase lanes to the
            # planner, most decode-left first; each migrated lane pays a
            # token-proportional KV-transfer stall at its destination.
            # Runs BEFORE placement, so this tick's admits see the
            # post-migration occupancy.
            if migrating:
                streak = np.where(pinned | over, streak + 1, 0)
                trig = streak >= migrate_after_ticks
                cand = (active & trig[:, None] & (slot_prefill <= 0)
                        if trig.any() else None)
                if cand is not None and cand.any():
                    c_chips, c_slots = np.nonzero(cand)
                    left = slot_decode[c_chips, c_slots]
                    order = np.lexsort(
                        (slot_req[c_chips, c_slots], -left))
                    reqs = [arrivals[int(slot_req[c_chips[k], c_slots[k]])]
                            for k in order]
                    dests = self.router.plan_migration(
                        reqs, occ, headroom, pinned=pinned, exclude=trig)
                    for k, dst in zip(order, dests):
                        if dst is None:
                            continue
                        src_c, src_s = int(c_chips[k]), int(c_slots[k])
                        i = int(slot_req[src_c, src_s])
                        d_slot = int(np.argmin(slot_req[dst]))  # first free
                        done_tokens = (req_prefill[i] + req_decode[i]
                                       - slot_decode[src_c, src_s])
                        stall_s = float(migrate_stall_s_per_token
                                        * done_tokens)
                        slot_req[dst, d_slot] = i
                        slot_prefill[dst, d_slot] = 0.0
                        slot_decode[dst, d_slot] = slot_decode[src_c, src_s]
                        slot_stall[dst, d_slot] = stall_s
                        slot_req[src_c, src_s] = -1
                        slot_stall[src_c, src_s] = 0.0
                        active[dst, d_slot] = True
                        active[src_c, src_s] = False
                        occ[dst] += 1
                        occ[src_c] -= 1
                        ledger.migrate(arrivals[i].rid, t, src_c, int(dst),
                                       stall_s=stall_s,
                                       src_streak=int(streak[src_c]))
                        n_migrations += 1
                if trig.any():
                    # triggered chips had their shot (or nothing to move);
                    # re-arm after another K hot ticks
                    streak[trig] = 0

            # placement: the whole pending queue in one vectorized router
            # pass, FIFO head-of-line semantics pinned to sequential
            # place(); an unplaceable head defers once and blocks the
            # queue behind it
            if pending:
                placed = self.router.place_batch(
                    [arrivals[i] for i in pending], occ, headroom, pinned)
                for chip in placed:
                    i = pending.popleft()
                    ledger.place(arrivals[i].rid, t, chip)
                    slot = int(np.argmin(slot_req[chip]))   # first free
                    slot_req[chip, slot] = i
                    slot_prefill[chip, slot] = float(
                        arrivals[i].prefill_tokens)
                    slot_decode[chip, slot] = float(
                        arrivals[i].decode_tokens)
                    slot_stall[chip, slot] = 0.0
                    active[chip, slot] = True
                    occ[chip] += 1
                if pending:
                    reason = ("capacity" if bool((occ >= cap).all())
                              else "pinned-drain")
                    ledger.defer(arrivals[pending[0]].rid, reason, tick_s)
                    self.stats.decode_sheds += 1
                    self.stats.sheds_by_reason[reason] = (
                        self.stats.sheds_by_reason.get(reason, 0) + 1)
                    if reason == "pinned-drain":
                        for lane, rail in enumerate(RAIL_LANES):
                            if pinned_rows[lane].any():
                                self.stats.sheds_by_rail[rail] = (
                                    self.stats.sheds_by_rail.get(rail, 0)
                                    + 1)
                    self.stats.defer_time_s += tick_s
            max_occ = max(max_occ, int(occ.max()) if n else 0)

            # progress: batched decode over the [n_chips, capacity] lane
            # arrays; over-bound chips deliver degraded goodput this tick
            rate = tick_s / np.maximum(t_rate, 1e-12)
            if over.any():
                degraded_ticks += int(over.sum())
            rate = np.where(over, rate * degrade, rate)
            t_end = t + tick_s
            rate2d = np.broadcast_to(rate[:, None], (n, cap))
            if migrating:
                # freshly migrated lanes sit out their KV-transfer stall:
                # they occupy (and count toward the batch) but advance
                # nothing until the stall drains
                stalled = active & (slot_stall > 0)
                if stalled.any():
                    slot_stall[stalled] -= tick_s
                    active = active & ~stalled
            in_prefill = active & (slot_prefill > 0)
            if in_prefill.any():
                slot_prefill[in_prefill] -= (rate2d[in_prefill]
                                             * prefill_speedup)
                pf_done = in_prefill & (slot_prefill <= 0)
                if pf_done.any():
                    self.stats.prefill_tokens += int(
                        req_prefill[slot_req[pf_done]].sum())
            # a slot whose prefill crossed zero THIS tick decodes only
            # from the next tick (the loop path's `continue`)
            in_decode = active & ~in_prefill
            if in_decode.any():
                slot_decode[in_decode] -= rate2d[in_decode]
                fin = in_decode & (slot_decode <= 0)
                if fin.any():
                    for chip, slot in zip(*np.nonzero(fin)):
                        i = slot_req[chip, slot]
                        self.stats.decode_tokens += int(req_decode[i])
                        ledger.finish(arrivals[i].rid, t_end,
                                      tokens_out=int(req_decode[i]))
                    slot_req[fin] = -1
            t = t_end

        for i in np.nonzero(charged)[0]:
            ledger.charge(arrivals[int(i)].rid, float(energy_acc[i]))

        self.last_trace = {
            "router": getattr(self.router, "name",
                              type(self.router).__name__),
            "ticks": ticks_run, "tick_s": tick_s,
            "max_occupancy": max_occ, "capacity": cap,
            "degraded_chip_ticks": degraded_ticks,
            "resident_degraded_ticks": resident_degraded_ticks,
            "unplaced": len(pending),
            "unfinished": int((slot_req >= 0).sum()),
            "fused": True,
            "fast_forward_ticks": ff_ticks,
            "batch_cap": self.batch_cap,
            "migrations": n_migrations,
        }
        return ledger

    # -- loop path: the historical per-tick host loop (the fused oracle) ------

    def _serve_trace_loop(self, arrivals, ledger, *, max_ticks, observe,
                          tick_s, error_bound, degrade, prefill_speedup):
        """The PR-8 per-tick host loop: eager accounting, one control
        dispatch and scattered device reads per tick, per-slot dict
        bookkeeping. Kept verbatim as the semantics oracle the fused path
        is pinned against, and as the only path host-actuated (PMBus)
        controllers can run."""
        from repro.serve.router import rail_headroom
        n = self.n_chips
        cap = self.router.capacity
        spec = self.fleet_spec
        variation = {k: jnp.asarray(v) for k, v in spec.variation().items()}
        account = lambda p: account_fleet_and_observe(
            self.decode_profile, p, spec)
        p_idle_fn = lambda p: chip_power_w_jnp(
            p, 0.0, 0.0, 0.0, spec.base, variation=variation)

        ai = 0
        pending: collections.deque = collections.deque()
        running: list[list[dict]] = [[] for _ in range(n)]
        t = 0.0
        max_occ = 0
        degraded_ticks = 0
        ticks_run = 0

        for tick in range(max_ticks):
            if ai >= len(arrivals) and not pending \
                    and not any(running):
                break
            ticks_run += 1
            while ai < len(arrivals) and arrivals[ai].t_arrival_s <= t:
                ledger.admit(arrivals[ai])
                pending.append(arrivals[ai])
                ai += 1
            occ = np.array([len(r) for r in running], np.float64)
            busy_frac = jnp.asarray(np.minimum(occ, cap) / cap, jnp.float32)

            self.plane, frame, m = account(self.plane)
            if observe is not None:
                frame = observe(self.plane, frame, tick, busy_frac)
            self._control_tick(frame)

            # busy/idle-blended energy: the accounting above assumed every
            # chip fully busy — rescale its step energy to this tick's
            # occupancy (idle slots burn static + uncore power only) and
            # rewrite the plane's accumulator to match
            p_busy = m["power_w"]
            p_idle = p_idle_fn(self.plane)
            p_eff = p_idle + (p_busy - p_idle) * busy_frac
            e_tick = p_eff * jnp.float32(tick_s)
            self.plane = dataclasses.replace(
                self.plane,
                energy_j=self.plane.energy_j - m["energy_step_j"] + e_tick)
            e_np = np.asarray(jax.device_get(e_tick), np.float64)
            e_busy = np.asarray(jax.device_get(
                (p_eff - p_idle) * jnp.float32(tick_s)), np.float64)
            self.stats.energy_j += float(e_np.mean())
            self.stats.fleet_energy_j += float(e_np.sum())
            self.stats.model_time_s += tick_s
            ledger.tick_energy(float(e_np.sum()))
            for i in range(n):
                if running[i]:
                    share = e_busy[i] / len(running[i])
                    for slot in running[i]:
                        ledger.charge(slot["req"].rid, share)

            # placement: headroom + drain mask from the eager round just
            # run; FIFO with head-of-line blocking (placement order is the
            # SLO order — a starved head is a deferral, not a skip). The
            # pinned masks are computed ONCE per tick (one stacked
            # transfer) and reused by the defer path — their inputs don't
            # change within a tick
            envs = getattr(self.controller, "last_envelope", None) \
                if self.controller is not None else None
            req = getattr(self.controller, "last_request", None) \
                if self.controller is not None else None
            headroom = rail_headroom(self.plane, envs)
            pin_masks = (pinned_rails(self.plane, req, envelope=envs)
                         if req is not None else {})
            pinned = np.zeros(n, bool)
            for mask in pin_masks.values():
                pinned |= mask
            while pending:
                occ_now = [len(r) for r in running]
                chip = self.router.place(pending[0], occ_now, headroom,
                                         pinned)
                if chip is None:
                    reason = ("capacity"
                              if all(o >= cap for o in occ_now)
                              else "pinned-drain")
                    ledger.defer(pending[0].rid, reason, tick_s)
                    self.stats.decode_sheds += 1
                    self.stats.sheds_by_reason[reason] = (
                        self.stats.sheds_by_reason.get(reason, 0) + 1)
                    if reason == "pinned-drain":
                        for rail, mask in pin_masks.items():
                            if mask.any():
                                self.stats.sheds_by_rail[rail] = (
                                    self.stats.sheds_by_rail.get(rail, 0)
                                    + 1)
                    self.stats.defer_time_s += tick_s
                    break
                r = pending.popleft()
                ledger.place(r.rid, t, chip)
                running[chip].append({
                    "req": r,
                    "prefill_left": float(r.prefill_tokens),
                    "decode_left": float(r.decode_tokens)})
            max_occ = max(max_occ, max(len(r) for r in running))

            # progress: batched decode — every resident slot advances at
            # the chip's modeled token rate; over-bound chips deliver
            # degraded goodput this tick
            t_step = np.asarray(jax.device_get(m["t_step_s"]), np.float64)
            rate = tick_s / np.maximum(
                np.broadcast_to(np.atleast_1d(t_step), (n,)), 1e-12)
            over = np.zeros(n, bool)
            for key in _OBS_KEYS:
                v = frame.get(key)
                if v is None:
                    continue
                a = np.asarray(jax.device_get(v), np.float64)
                a = np.broadcast_to(np.atleast_1d(a), (n,))
                over |= (~np.isnan(a)) & (a > error_bound)
            if over.any():
                degraded_ticks += int(over.sum())
            rate = np.where(over, rate * degrade, rate)
            t_end = t + tick_s
            for i in range(n):
                if not running[i]:
                    continue
                finished = []
                for slot in running[i]:
                    if slot["prefill_left"] > 0:
                        slot["prefill_left"] -= rate[i] * prefill_speedup
                        if slot["prefill_left"] <= 0:
                            self.stats.prefill_tokens += (
                                slot["req"].prefill_tokens)
                        continue
                    slot["decode_left"] -= rate[i]
                    if slot["decode_left"] <= 0:
                        finished.append(slot)
                for slot in finished:
                    running[i].remove(slot)
                    self.stats.decode_tokens += slot["req"].decode_tokens
                    ledger.finish(slot["req"].rid, t_end,
                                  tokens_out=slot["req"].decode_tokens)
            t = t_end

        self.last_trace = {
            "router": getattr(self.router, "name", type(self.router).__name__),
            "ticks": ticks_run, "tick_s": tick_s,
            "max_occupancy": max_occ, "capacity": cap,
            "degraded_chip_ticks": degraded_ticks,
            "unplaced": len(pending),
            "unfinished": sum(len(r) for r in running),
            "fused": False,
            "fast_forward_ticks": 0,
        }
        return ledger

    def summary(self) -> dict[str, Any]:
        toks = max(self.stats.decode_tokens, 1)
        out = {
            "prefill_tokens": self.stats.prefill_tokens,
            "decode_tokens": self.stats.decode_tokens,
            "energy_j": self.stats.energy_j,
            "model_time_s": self.stats.model_time_s,
            # array-aware: fleet planes report the mean operating point
            "v_core": scalar_view(self.plane.v_core),
            "v_io": scalar_view(self.plane.v_io),
            "n_chips": self.n_chips,
        }
        if self.plane.is_fleet:
            # fleet planes report joules/token from whole-fleet energy —
            # energy_j is the per-chip MEAN while decode_tokens counts the
            # whole fleet, so dividing the mean by fleet-total tokens (the
            # historical j_per_decoded_token spelling) understated the
            # fleet's cost by 1/n_chips; the scalar field stays
            # scalar-plane-only
            out["fleet_energy_j"] = self.stats.fleet_energy_j
            out["fleet_j_per_decoded_token"] = (
                self.stats.fleet_energy_j / toks)
            out["v_core_min"] = float(jnp.min(self.plane.v_core))
            out["v_io_min"] = float(jnp.min(self.plane.v_io))
            out["comp_level_min"] = int(jnp.min(self.plane.comp_level))
        else:
            out["j_per_decoded_token"] = self.stats.energy_j / toks
        if self.admission_gate or self.router is not None:
            out["decode_sheds"] = self.stats.decode_sheds
            out["defer_time_s"] = self.stats.defer_time_s
            # per-rail / per-reason split of the aggregate counters: which
            # rail's envelope floor drove the shed (all-rails admission)
            # and what reason each deferral carried
            out["decode_sheds_by_rail"] = dict(self.stats.sheds_by_rail)
            out["decode_sheds_by_reason"] = dict(self.stats.sheds_by_reason)
            if self.last_shed_reason is not None:
                out["shed_reason"] = self.last_shed_reason
        if self._sor_state is not None:
            out["sor"] = sor_mod.summary(self._sor_state.estimate,
                                         self.controller.sor)
        else:
            # a HostRailController(sor=...) learns on its own control_step;
            # surface its view the same way
            summarize = getattr(self.controller, "sor_summary", None)
            s = summarize() if callable(summarize) else None
            if s:
                out["sor"] = s
        return out
