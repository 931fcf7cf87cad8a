"""The unified rail control plane (paper §III): one `RailController`
interface serving both of VolTune's control paths.

Decision-as-data control API, stage 3 — arbitration + actuation
(docs/control_api.md). Policies return declarative `RailRequest`s
(policy.decide); this module is the single place where requests meet the
hardware: `arbitrate` clamps/merges a request into the plane state under the
per-rail safety envelopes (paper §VII-B), and the controllers actuate the
arbitrated state:

  * `InGraphRailController` (HW-path analogue): observation → decision →
    arbitration are pure jnp and compile into the jitted step — deterministic,
    zero host round-trip, and the arbitrated operating point takes effect
    immediately (the RTL FSM analogue). One elementwise decide() serves
    scalar states and `[n_chips]` fleets.

  * `HostRailController` (SW-path analogue): the policy runs host-side
    between steps and every actuation is pushed through the simulated
    PMBus/regulator stack — per-board `PowerManager`s over the
    event-scheduled multi-segment `FleetPowerManager` bus — paying the
    paper-characterized millisecond-scale command-sequence + settling cost,
    with achieved voltages (clamp + LINEAR16 quantization + settling band)
    written back into the state. With `decide_from="poll"` it closes the
    loop on its *own* READ_VOUT polling telemetry (`Provenance.POLLED`
    frames with nonzero `age_s`) instead of trainer-supplied oracle state —
    the paper's SW path acting on sampled readbacks, sampling delay included.

Both controllers run the *same* decide()+arbitrate() logic, so on the same
telemetry stream they produce the same rail trajectory up to actuation
quantization — the two-paths-one-behavior property pinned by
tests/test_control_plane.py and tests/test_control_api.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ecollectives
from repro.core.fleet import FleetPowerManager
from repro.core.hwspec import V5E, ChipSpec
from repro.core.policy import Policy, RailRequest, apply_request
from repro.core.power_manager import ControlPath
from repro.core.power_plane import PowerPlaneState
from repro.core.rails import TPU_V5E_RAIL_MAP, RailMap
from repro.core.telemetry import Provenance, TelemetryFrame, as_frame

# a controller accepts the typed observation or the legacy metrics dict
Telemetry = TelemetryFrame | dict[str, Any]

# TPU logical rails in PowerPlaneState field order.
RAIL_LANES = {"VDD_CORE": 0, "VDD_HBM": 1, "VDD_IO": 2}
_LANE_FIELDS = {"VDD_CORE": "v_core", "VDD_HBM": "v_hbm", "VDD_IO": "v_io"}


# ---------------------------------------------------------------------------
# Arbitration: requests meet the safety envelopes in exactly one place
# ---------------------------------------------------------------------------

def arbitrate(plane: PowerPlaneState, request: RailRequest,
              rail_map: RailMap = TPU_V5E_RAIL_MAP,
              envelopes: dict | None = None) -> PowerPlaneState:
    """Merge a `RailRequest` into the plane state under the per-rail safety
    envelopes: None fields keep the current value, scalar fields broadcast
    over a `[n_chips]` fleet, voltages clamp into [v_min, v_max] of their
    rail, compression levels clamp into the codec range. Pure jnp —
    identical under jit/vmap and on the host. The None-skip/broadcast merge
    itself is `policy.apply_request` (one implementation); arbitration adds
    only the clamping.

    `envelopes` optionally maps rail names to learned per-chip
    `sor.SafeEnvelope`s: a rail with an envelope clamps into
    [env.floor(v_min), env.ceil(v_max)] instead of the one shared static
    pair — weak chips get a *tighter* floor than the platform constant,
    strong chips a confidence-gated extension below it (bounded by the
    envelope's `max_extension_v`). At zero confidence the blend is bit-exact
    the static envelope, so cold start arbitrates exactly as before."""
    def clamp(want, name):
        if want is None:
            return None
        r = rail_map.by_name(name)
        env = envelopes.get(name) if envelopes else None
        if env is None:
            lo, hi = jnp.float32(r.v_min), jnp.float32(r.v_max)
        else:
            lo, hi = env.floor(r.v_min), env.ceil(r.v_max)
        return jnp.clip(jnp.asarray(want, jnp.float32), lo, hi)

    comp = request.comp_level
    if comp is not None:
        comp = jnp.clip(jnp.asarray(comp, jnp.int32),
                        ecollectives.LEVEL_LOSSLESS,
                        ecollectives.LEVEL_INT8_TOPK)

    clamped = RailRequest(v_core=clamp(request.v_core, "VDD_CORE"),
                          v_hbm=clamp(request.v_hbm, "VDD_HBM"),
                          v_io=clamp(request.v_io, "VDD_IO"),
                          comp_level=comp, reason=request.reason)
    return apply_request(plane, clamped)


def rail_floors(plane: PowerPlaneState, envelope: Any = None,
                rail_map: RailMap = TPU_V5E_RAIL_MAP) -> jnp.ndarray:
    """`[n_rails, n_chips]` float32 of per-rail arbitration floors in
    `RAIL_LANES` order: the confidence-blended learned floor
    (`SafeEnvelope.floor(static v_min)`) where a rail carries a fitted
    envelope, the platform static `Rail.v_min` where it does not. Pure
    jnp — the fused serve tick packs these rows (and the headroom rows
    derived from them) into its single host bundle, so routing reads
    floors with zero extra device syncs."""
    from repro.core.sor import envelope_for
    n = plane.n_chips
    rows = []
    for name in RAIL_LANES:
        r = rail_map.by_name(name)
        env = envelope_for(envelope, name)
        floor = (env.floor(r.v_min) if env is not None
                 else jnp.float32(r.v_min))
        rows.append(jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(floor, jnp.float32)), (n,)))
    return jnp.stack(rows)


def _pinned_lane(plane: PowerPlaneState, request: RailRequest | None,
                 name: str, envelope: Any, rail_map: RailMap,
                 atol: float):
    """Pure-jnp pinned mask for one rail, or None when the request left it
    alone — the shared arithmetic behind the host (`pinned_rails`) and
    in-graph (`pinned_lane_masks`) spellings."""
    if request is None:
        return None
    want = getattr(request, _LANE_FIELDS[name])
    if want is None:
        return None
    from repro.core.sor import envelope_for
    env = envelope_for(envelope, name)   # dict or single spelling
    r = rail_map.by_name(name)
    floor = (env.floor(r.v_min) if env is not None
             else jnp.float32(r.v_min))
    wantv = jnp.asarray(want, jnp.float32)
    held = jnp.asarray(getattr(plane, _LANE_FIELDS[name]), jnp.float32)
    return (wantv <= floor + atol) & (held <= floor + atol)


def pinned_rails(plane: PowerPlaneState, request: RailRequest | None,
                 rail_map: RailMap = TPU_V5E_RAIL_MAP,
                 envelope: Any = None, atol: float = 1e-4
                 ) -> dict[str, np.ndarray]:
    """Host-side per-rail pinning breakdown: {rail name: [n_chips] bool}
    for every rail the request actually asked for. A chip is pinned on a
    rail when the latest decision *wanted* a voltage at/below the floor
    arbitration holds it to AND the plane is already held there — the chip
    is operating at its envelope limit with the policy still pushing
    against it. `envelope` is the learned state in either spelling (a
    {rail: SafeEnvelope} dict or the historical bare VDD_IO envelope);
    rails without one pin against the platform static floor. Rails the
    request left alone (None) are absent from the result — no request, no
    pinning claim. All requested rails come back in ONE stacked device
    transfer (the historical spelling paid one blocking `device_get` per
    rail)."""
    out: dict[str, np.ndarray] = {}
    if request is None:
        return out
    n = plane.n_chips
    names, lanes = [], []
    for name in _LANE_FIELDS:
        pinned = _pinned_lane(plane, request, name, envelope, rail_map,
                              atol)
        if pinned is None:
            continue
        names.append(name)
        lanes.append(jnp.broadcast_to(jnp.atleast_1d(pinned), (n,)))
    if not names:
        return out
    masks = np.asarray(jax.device_get(jnp.stack(lanes)), bool)
    return {name: masks[i].copy() for i, name in enumerate(names)}


def pinned_lane_masks(plane: PowerPlaneState, request: RailRequest | None,
                      rail_map: RailMap = TPU_V5E_RAIL_MAP,
                      envelope: Any = None, atol: float = 1e-4
                      ) -> jnp.ndarray:
    """`[n_rails, n_chips]` bool in `RAIL_LANES` order, pure jnp: the
    `pinned_rails` masks with all-False rows for rails the request left
    alone (an absent rail makes no pinning claim, matching the host dict
    spelling where such rails are simply missing). The fused serve tick
    packs these rows into its single host bundle; `.any(axis=0)` is the
    in-graph `pinned_chip_mask`."""
    n = plane.n_chips
    rows = []
    for name in RAIL_LANES:
        pinned = _pinned_lane(plane, request, name, envelope, rail_map,
                              atol)
        rows.append(jnp.zeros((n,), bool) if pinned is None
                    else jnp.broadcast_to(jnp.atleast_1d(pinned), (n,)))
    return jnp.stack(rows)


def pinned_chip_mask(plane: PowerPlaneState, request: RailRequest | None,
                     rail_map: RailMap = TPU_V5E_RAIL_MAP,
                     envelope: Any = None, atol: float = 1e-4) -> np.ndarray:
    """[n_chips] bool: chips pinned on ANY requested rail — the drain mask
    headroom routing excludes from new placements (serve/router.py)."""
    out = np.zeros(plane.n_chips, bool)
    for mask in pinned_rails(plane, request, rail_map, envelope,
                             atol).values():
        out |= mask
    return out


def worst_chip_pinned(plane: PowerPlaneState, request: RailRequest | None,
                      rail_map: RailMap = TPU_V5E_RAIL_MAP,
                      envelope: Any = None, atol: float = 1e-4) -> bool:
    """Host-side: is any chip pinned at any requested rail's envelope floor
    — i.e. did the latest decision *want* a voltage at/below the floor
    arbitration holds it to? A pinned worst chip means the fleet has no
    safe headroom left on that rail; serve-side admission control sheds
    load on this signal rather than letting the envelope absorb unbounded
    demand. Checks EVERY rail the request touched (a VDD_HBM floor during
    decode gates exactly like the historical VDD_IO-only check); use
    `pinned_rails` for the per-rail breakdown."""
    return any(bool(mask.any())
               for mask in pinned_rails(plane, request, rail_map, envelope,
                                        atol).values())


def _has_decide(policy: Any) -> bool:
    """True when the policy implements the decision-as-data API (its own
    decide(), not the abstract base)."""
    fn = getattr(type(policy), "decide", None)
    return fn is not None and fn is not Policy.decide


def require_decide_for_sor(policy: Any) -> None:
    """A controller configured with sor= runs decide_env + envelope-clamped
    arbitration — the legacy update_* path ignores envelopes entirely, so a
    legacy policy under SOR would LEARN regions that are never consumed.
    Reject loudly instead of silently no-op'ing the learned control."""
    if policy is not None and not _has_decide(policy):
        raise ValueError(
            "sor= needs a decide(state, frame) policy; "
            f"{getattr(policy, 'name', type(policy).__name__)} only "
            "implements the legacy update_* API, which ignores learned "
            "envelopes — the SOR state would be fitted but never consumed")


def validate_in_graph_sor(cfg: Any) -> None:
    """In-graph SOR has no bus: the only observations it can learn from are
    the frames the decision consumes, so `ingest="polled"` (the host
    controller's READ_VOUT path) would be silently meaningless — reject it
    up front instead of oracle-training a 'polled-only' config."""
    if cfg is not None and cfg.ingest != "frames":
        raise ValueError(
            "in-graph SOR learns from the frames the decision consumes; "
            "use SorConfig(ingest='frames') (ingest='polled' is the "
            "HostRailController READ_VOUT path)")


def with_sor(controller: Any, sor_cfg: Any) -> Any:
    """One implementation of "give this in-graph controller a SorConfig"
    for every consumer (fleet train step, serve engine): validates the
    config and the policy, and NEVER mutates a caller-owned controller —
    a controller without SOR is rebuilt with the config; one already
    carrying the SAME config passes through; a different config is a loud
    conflict."""
    validate_in_graph_sor(sor_cfg)
    if not hasattr(controller, "control_step_sor"):
        raise ValueError(
            "sor= needs an InGraphRailController (or a bare policy); got "
            f"{type(controller).__name__}")
    require_decide_for_sor(controller.policy)
    if controller.sor is not None:
        if controller.sor != sor_cfg:
            raise ValueError(
                "conflicting SorConfig: the controller already carries its "
                "own sor=; configure it in one place")
        return controller
    return InGraphRailController(controller.policy, name=controller.name,
                                 rail_map=controller.rail_map, sor=sor_cfg)


def _concrete_or_none(tree):
    """`tree` if every leaf is a concrete array, else None. Controllers use
    this to record their latest decision (`last_request`/`last_envelope`)
    only on eager paths — inside a jitted step the values are tracers, and
    storing those would leak them (and go stale on cache hits anyway)."""
    if tree is None:
        return None
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree_util.tree_leaves(tree)):
        return None
    return tree


def _all_concrete(tree) -> bool:
    """True when no leaf is a tracer — i.e. the caller is eager, so a cached
    jitted round may be dispatched instead of retracing through op-by-op."""
    return not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree_util.tree_leaves(tree))


def _run_policy(policy: Any, plane: PowerPlaneState, frame: TelemetryFrame,
                telemetry: Any, rail_map: RailMap, *, host: bool,
                envelope: Any = None
                ) -> tuple[PowerPlaneState, RailRequest | None]:
    """decide() + arbitrate() for API-native policies; the pre-redesign
    state-mutating `update_*` methods for legacy policies that never defined
    decide() (kept working, unclamped, exactly as before). Returns
    (arbitrated plane, the pre-arbitration request) — the request is None on
    the legacy path, which never speaks decision-as-data.

    `envelope` is the learned `sor.SafeEnvelope` state — a single VDD_IO
    envelope (historical spelling) or a {rail name: SafeEnvelope} dict
    covering every fitted rail: it warm-starts the decision
    (policy.decide_env) and tightens/extends the arbitration clamp for those
    rails, in one place for both controllers."""
    if _has_decide(policy):
        if envelope is not None:
            from repro.core.sor import as_envelopes
            request = policy.decide_env(plane, frame, envelope)
            arbitrated = arbitrate(plane, request, rail_map,
                                   envelopes=as_envelopes(envelope))
        else:
            request = policy.decide(plane, frame)
            arbitrated = arbitrate(plane, request, rail_map)
        return arbitrated, request
    telem = telemetry if isinstance(telemetry, dict) else frame.to_dict()
    if jnp.ndim(plane.v_core) >= 1:
        return policy.update_fleet(plane, telem), None
    if host:
        return policy.update_host(plane, telem), None
    return policy.update_jax(plane, telem), None


@dataclasses.dataclass
class ControlPlaneStats:
    """What a control path cost, in the units the paper reports (§V-F):
    number of actuations and simulated control-path seconds."""
    decisions: int = 0
    actuations: int = 0              # rail writes that completed on a bus
    failed_actuations: int = 0       # rejected writes (e.g. outside envelope)
    actuation_seconds: float = 0.0   # fleet-time spent actuating (max-over-segments)
    serialized_seconds: float = 0.0  # single-shared-bus equivalent (sum)
    polls: int = 0                   # periodic READ_VOUT rounds completed
    polls_deferred: int = 0          # poll rounds that slipped (back-pressure)
    poll_decisions: int = 0          # decisions made from POLLED frames
    skipped_actuations: int = 0      # PMBus writes skipped by the deadband
    #                                  scheduler (target pinned at a learned
    #                                  floor within the confidence-scaled
    #                                  deadband) — saved bus transactions
    relaxed_polls: int = 0           # poll rounds fired at a deadband-
    #                                  relaxed interval (poll back-pressure
    #                                  on steady-state pinned boards)


@runtime_checkable
class RailController(Protocol):
    """The one actuation interface. `control_step` takes the current rail
    state and the latest observation (TelemetryFrame, or a legacy metrics
    dict), runs the policy, arbitrates, actuates, and returns the achieved
    state; `stats` reports what the control path cost."""

    name: str

    def control_step(self, plane: PowerPlaneState,
                     telemetry: Telemetry) -> PowerPlaneState: ...

    def stats(self) -> ControlPlaneStats: ...


def as_controller(policy_or_controller: Any, *,
                  host: bool = False) -> "RailController | None":
    """Normalize a config knob: an existing controller passes through; None
    stays None; a bare Policy is wrapped for the requesting path —
    `host=False` (in-graph slots) -> InGraphRailController,
    `host=True` (between-steps slots) -> HostDecisionController, so the
    decision runs where the SW-path analogue is expected."""
    if policy_or_controller is None:
        return None
    if hasattr(policy_or_controller, "control_step"):
        return policy_or_controller
    if host:
        return HostDecisionController(policy_or_controller)
    return InGraphRailController(policy_or_controller)


# ---------------------------------------------------------------------------
# HW-path analogue: in-graph, deterministic, fleet-vectorized
# ---------------------------------------------------------------------------

class InGraphRailController:
    """Pure-jnp controller compiled into the jitted step (paper §III-B).

    Actuation is the identity: in the HW path the arbitrated operating point
    is applied deterministically before the next step, with no bus
    transaction on the modelled timeline (its cost is pinned separately by
    the Table VII/IX overhead benchmarks).

    With `sor=SorConfig(...)` the controller learns per-chip safe operating
    regions *inside the graph*: the caller threads a functional `SorState`
    (init_sor) through its scan and calls `control_step_sor`, which pushes
    the frame into the history, refreshes the frontier estimate on the
    configured cadence, and runs the envelope-warm-started decision +
    envelope-clamped arbitration — all pure jnp."""

    def __init__(self, policy: Any, name: str | None = None,
                 rail_map: RailMap = TPU_V5E_RAIL_MAP,
                 sor: "Any | None" = None, donate: bool = False):
        if policy is None:
            raise ValueError("InGraphRailController needs a policy")
        validate_in_graph_sor(sor)
        if sor is not None:
            require_decide_for_sor(policy)
        self.policy = policy
        self.rail_map = rail_map
        self.sor = sor
        # donate=True makes the cached eager-dispatch jit donate the
        # SorState input buffers, so the O(capacity x rails x chips)
        # history ring is updated in place instead of copied every round.
        # The plane is NOT donated: telemetry frames routinely alias the
        # plane's rail arrays (`as_frame(..., state=plane)` passes them
        # through), and XLA rejects a buffer that is both donated and a
        # live second argument (`f(donate(a), a)`). Caveat: donated
        # inputs are invalidated — an eager caller must rebind to the
        # returned (plane', sor_state') and never touch the SorState it
        # passed in again (the loop idiom `plane, ss =
        # ctrl.control_step_sor(plane, frame, ss)` is already safe).
        self.donate = donate
        self.name = name or f"in-graph[{getattr(policy, 'name', 'policy')}]"
        self.last_request: RailRequest | None = None
        self.last_envelope: Any = None
        self._round_jit = None   # cached jit of control_round (eager callers)

    def control_step(self, plane: PowerPlaneState,
                     telemetry: Telemetry) -> PowerPlaneState:
        frame = as_frame(telemetry, state=plane)
        plane, request = _run_policy(
            self.policy, plane, frame, telemetry, self.rail_map, host=False)
        self.last_request = _concrete_or_none(request)
        return plane

    # -- learned safe-operating-region path -----------------------------------
    def init_sor(self, n_chips: int | None = None):
        """Fresh functional SOR state for a `control_step_sor` loop."""
        from repro.core import sor as _sor
        if self.sor is None:
            raise ValueError("construct the controller with sor=SorConfig() "
                             "before init_sor()")
        return _sor.init_state(self.sor, n_chips)

    def control_round(self, plane: PowerPlaneState, frame: TelemetryFrame,
                      sor_state, fused: bool = True):
        """ONE fused SOR control round, pure jnp: ingest the frame, refresh
        the frontier estimate on the batched `refresh_every` cadence
        (`lax.cond` — the refit graph executes only on-cadence instead of
        every round), derive the per-rail envelopes, and run the
        envelope-warm-started decide + envelope-clamped arbitration.
        Returns (plane', sor_state', request, envelopes). `fused=False`
        runs the historical per-observation-refit graph — the
        bit-equivalence oracle the fused path is pinned against."""
        from repro.core import sor as _sor
        if self.sor is None:
            raise ValueError("control_step_sor needs sor=SorConfig()")
        sor_state = _sor.observe(sor_state, frame, self.sor, fused=fused)
        env = _sor.rail_envelopes(sor_state.estimate, self.sor)
        plane, request = _run_policy(
            self.policy, plane, frame, frame, self.rail_map, host=False,
            envelope=env)
        return plane, sor_state, request, env

    def control_step_sor(self, plane: PowerPlaneState, telemetry: Telemetry,
                         sor_state):
        """One SOR-aware control round: observe -> refresh-on-cadence ->
        envelope-driven decide + arbitrate, all one fused `control_round`.
        Returns (plane', sor_state'). Pure jnp — thread `sor_state` through
        the caller's scan carry (the round inlines into the caller's trace);
        eager callers (serve engine, host-side loops) dispatch a cached
        jitted compilation of the round instead of retracing op-by-op."""
        if self.sor is None:
            raise ValueError("control_step_sor needs sor=SorConfig()")
        frame = as_frame(telemetry, state=plane)
        if _all_concrete((plane, frame, sor_state)):
            if self._round_jit is None:
                # named, so that the device trace lists it as such
                def control_round(p, f, s):
                    return self.control_round(p, f, s)

                self._round_jit = jax.jit(
                    control_round,
                    donate_argnums=(2,) if self.donate else ())
            plane, sor_state, request, env = self._round_jit(
                plane, frame, sor_state)
        else:
            plane, sor_state, request, env = self.control_round(
                plane, frame, sor_state)
        self.last_request = _concrete_or_none(request)
        self.last_envelope = _concrete_or_none(env)
        return plane, sor_state

    def stats(self) -> ControlPlaneStats:
        # decisions happen inside the compiled step; host-side cost is zero
        return ControlPlaneStats()


def sharded_control_round(controller: InGraphRailController, mesh,
                          axis_name: str = "chips"):
    """Shard-parallel spelling of `InGraphRailController.control_round` over
    a 1-D `axis_name` mesh: each shard ingests its slice of the frame into
    its resident slice of the `[capacity, n_rails, n_chips]` history ring,
    refits on the replicated `tick` cadence (`lax.cond` — every shard takes
    the same branch), derives envelopes and runs decide + arbitrate — all
    elementwise per chip, so per-shard results are bit-equal to slices of
    the single-device round. The only cross-shard traffic is the confidence
    summary (one psum + one pmin scalar); the plane/SorState never gather.

    Returns `round(plane, frame, sor_state) -> (plane', sor_state',
    conf_sum, conf_min)` where `conf_sum` is the fleet-wide sum of estimate
    confidence (divide by `confidence.size` for the mean) and `conf_min`
    its fleet-wide min. Inputs must carry a trailing `[n_chips]` axis
    divisible by the mesh size; RNG-derived frame fields must be drawn on
    global shapes *outside* the round (the `make_fleet_train_step` pattern)
    so sharded and unsharded trajectories stay bit-equal.

    Cross-chip policies (`policy.cross_chip`, e.g. `WorstChipGate`) are
    rejected up front: inside shard_map their fleet reduction would
    silently cover only the local shard."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops as _ops

    if controller.sor is None:
        raise ValueError("sharded_control_round needs a controller built "
                         "with sor=SorConfig(...) — the per-shard resident "
                         "state is the SorState")
    if getattr(controller.policy, "cross_chip", False):
        raise ValueError(
            f"policy {getattr(controller.policy, 'name', '?')!r} reduces "
            "across chips (cross_chip=True); inside the sharded control "
            "round it would only see its local shard. Run it on the "
            "unsharded path (FleetStepConfig.shard_control=False).")

    def _local(plane, frame, sor_state):
        plane, sor_state, _request, _env = controller.control_round(
            plane, frame, sor_state)
        conf = sor_state.estimate.confidence
        conf_sum = jax.lax.psum(jnp.sum(conf), axis_name)
        conf_min = jax.lax.pmin(jnp.min(conf), axis_name)
        return plane, sor_state, conf_sum, conf_min

    def round(plane, frame, sor_state):
        n_chips = sor_state.history.chip_shape[-1]
        in_specs = (_ops.chip_specs(plane, n_chips, axis_name),
                    _ops.chip_specs(frame, n_chips, axis_name),
                    _ops.chip_specs(sor_state, n_chips, axis_name))
        out_specs = (in_specs[0], in_specs[2], P(), P())
        return jax.shard_map(_local, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
            plane, frame, sor_state)

    return round


# ---------------------------------------------------------------------------
# SW-path analogue: host-side decisions, PMBus-actuated over the fleet bus
# ---------------------------------------------------------------------------

class HostDecisionController:
    """Decide-only host controller: runs the policy between steps with no
    bus actuation — for studying SW-path decision logic without paying (or
    modelling) PMBus latency. Pair with HostRailController when actuation
    cost matters."""

    def __init__(self, policy: Any, rail_map: RailMap = TPU_V5E_RAIL_MAP):
        if policy is None:
            raise ValueError("HostDecisionController needs a policy")
        self.policy = policy
        self.rail_map = rail_map
        self.name = f"host-decide[{getattr(policy, 'name', 'policy')}]"
        self.decisions = 0
        self.last_request: RailRequest | None = None

    def control_step(self, plane: PowerPlaneState,
                     telemetry: Telemetry) -> PowerPlaneState:
        self.decisions += 1
        frame = as_frame(telemetry, state=plane)
        plane, request = _run_policy(
            self.policy, plane, frame, telemetry, self.rail_map, host=True)
        self.last_request = _concrete_or_none(request)
        return plane

    def stats(self) -> ControlPlaneStats:
        return ControlPlaneStats(decisions=self.decisions)


class HostRailController:
    """Host controller driving 1..N boards through the event-scheduled
    multi-segment PMBus model (paper §III-C analogue at fleet scale).

    With `policy=None` it is pure actuation (push whatever the state asks
    for); with a policy it is decide-then-actuate. Scalar states drive board
    0; `[n_chips]` states drive one board per chip concurrently in simulated
    time.

    `decide_from` selects the observation source:
      * "telemetry" (default): decide from the frame/dict the caller passes
        (rail observations fall back to the oracle plane state — the
        pre-redesign behavior);
      * "poll": decide from this controller's own READ_VOUT polling loop —
        sampled rail voltages with their per-chip staleness (`age_s`),
        merged over the caller's non-electrical measurements (grad error,
        roofline terms). Requires `enable_polling()`; chips never sampled
        yet fall back to the plane value at age 0."""

    def __init__(
        self,
        policy: Any = None,
        *,
        n_chips: int = 1,
        path: ControlPath | str = ControlPath.SOFTWARE,
        clock_hz: int = 400_000,
        spec: ChipSpec = V5E,
        settle_band_frac: float = 0.01,
        fleet: FleetPowerManager | None = None,
        seed: int = 0,
        decide_from: str = "telemetry",
        rail_map: RailMap = TPU_V5E_RAIL_MAP,
        sor: "Any | None" = None,
        deadband_v: float = 0.0,
        poll_relax: float = 0.0,
    ):
        if decide_from not in ("telemetry", "poll"):
            raise ValueError(f"decide_from must be 'telemetry' or 'poll', "
                             f"got {decide_from!r}")
        if (decide_from == "poll" and policy is not None
                and not _has_decide(policy)):
            # a legacy update_* policy reads rail voltages from the oracle
            # state, so the polled frame would be silently ignored while
            # stats reported poll-driven decisions
            raise ValueError(
                "decide_from='poll' needs a decide(state, frame) policy; "
                f"{getattr(policy, 'name', type(policy).__name__)} only "
                "implements the legacy update_* API")
        if sor is not None:
            if policy is None:
                # pure-actuation controllers never run decide(), so the
                # learner would silently never see an observation
                raise ValueError("sor= needs a policy: an actuate-only "
                                 "HostRailController never decides, so "
                                 "nothing would ever feed the learner")
            require_decide_for_sor(policy)
        self.policy = policy
        self.spec = spec
        self.settle_band_frac = settle_band_frac
        self.decide_from = decide_from
        self.rail_map = rail_map
        self.fleet = fleet if fleet is not None else FleetPowerManager(
            n_chips, rail_map, path=path, clock_hz=clock_hz, seed=seed)
        self.name = (f"host[{getattr(policy, 'name', 'actuate-only')}]"
                     f"x{self.fleet.n_boards}")
        self.decisions = 0
        self.poll_decisions = 0
        self.last_report = None   # FleetActuationReport of the latest round
        self.last_frame: TelemetryFrame | None = None  # latest decision input
        self.last_request: RailRequest | None = None   # latest decision output
        self.last_envelope: Any = None                 # latest SOR envelope
        # learned safe-operating-region state (core/sor.py): lazily sized on
        # the first decide (scalar vs [n_chips] follows the plane)
        self.sor = sor
        self.sor_state = None
        # deadband actuation scheduling (docs/sor.md "fused control round"):
        # a lane whose arbitrated target sits within a confidence-scaled
        # deadband of its learned floor — and whose regulator already holds
        # that target — is a steady-state lane pinned by the envelope; its
        # PMBus write is skipped (counted in stats().skipped_actuations).
        # 0.0 (default) disables the scheduler: every lane writes, as before.
        self.deadband_v = deadband_v
        self.skipped_actuations = 0
        # deadband-paired poll back-pressure (> 1.0 enables, with
        # deadband_v): a board whose every *governed* lane (learned
        # envelope, nonzero confidence) is deadband-pinned this round gets
        # its READ_VOUT poll interval relaxed by this factor
        # (fleet.set_poll_relax) — steady-state boards stop paying the full
        # Table VI telemetry rate, and the relax is lifted the moment any
        # lane leaves its band. Requires deadband_v > 0 to ever trigger.
        if poll_relax and poll_relax < 1.0:
            raise ValueError(f"poll_relax must be >= 1.0 (or 0 to disable), "
                             f"got {poll_relax}")
        self.poll_relax = poll_relax

    # -- observe --------------------------------------------------------------
    def observed_frame(self, plane: PowerPlaneState,
                       telemetry: Telemetry | None = None,
                       sampled: TelemetryFrame | None = None
                       ) -> TelemetryFrame:
        """POLLED TelemetryFrame: the rail voltages this controller's polling
        loop last *sampled* (LINEAR16-quantized READ_VOUT values, with their
        fleet-clock staleness in `age_s`), merged over the caller-supplied
        non-electrical measurements. Lanes never polled fall back to the
        plane value at age 0. `sampled` optionally reuses a `poll_frame`
        the caller already took this round."""
        base = as_frame(telemetry if telemetry is not None else {})
        if sampled is None:
            sampled = self.fleet.poll_frame()
        batched = jnp.ndim(plane.v_core) >= 1

        def pick(field):
            s = getattr(sampled, field)
            want = np.asarray(s, np.float64)
            have = ~np.isnan(want)
            fallback = np.atleast_1d(np.asarray(
                jax.device_get(getattr(plane, field)), np.float64))
            fallback = np.broadcast_to(fallback, want.shape)
            v = np.where(have, want, fallback).astype(np.float32)
            return jnp.asarray(v if batched else v[0])

        age = np.asarray(sampled.age_s, np.float64)
        age = np.where(np.isnan(age), 0.0, age).astype(np.float32)
        return dataclasses.replace(
            base,
            v_core=pick("v_core"), v_hbm=pick("v_hbm"), v_io=pick("v_io"),
            age_s=jnp.asarray(age if batched else age[0]),
            provenance=Provenance.POLLED)

    # -- learn ----------------------------------------------------------------
    def _sor_observe(self, plane: PowerPlaneState, frame: TelemetryFrame,
                     sampled: TelemetryFrame | None = None) -> Any:
        """Feed the SOR learner one observation and return the current
        per-rail envelopes ({rail: sor.SafeEnvelope}). With
        `ingest="polled"` (default) the history ingests the *raw*
        `FleetPowerManager.poll_frame` samples — NaN where a lane was never
        sampled, so chips with no real READ_VOUT telemetry record nothing
        and the envelopes stay bit-exactly static (cold-start pin) — with
        the per-rail failure observables the fit needs overlaid from the
        decision frame (`sor.merge_observables`: a rail whose observable
        the caller never reported records NaN and that rail's lane simply
        stays invalid); `ingest="frames"` learns from whatever frame the
        decision consumed (EXACT oracle values included). `sampled` reuses
        a poll sweep the caller already took this round instead of sweeping
        the bus twice."""
        from repro.core import sor as _sor
        batched = jnp.ndim(plane.v_core) >= 1
        if self.sor_state is None:
            self.sor_state = _sor.init_state(
                self.sor, plane.v_core.shape[0] if batched else None)
        if self.sor.ingest == "polled":
            raw = sampled if sampled is not None else self.fleet.poll_frame()
            sample = _sor.merge_observables(raw, frame, self.sor)
            if not batched:
                sample = dataclasses.replace(
                    sample, v_core=sample.v_core[0], v_hbm=sample.v_hbm[0],
                    v_io=sample.v_io[0], age_s=sample.age_s[0])
        else:
            sample = frame
        self.sor_state = _sor.observe(self.sor_state, sample, self.sor)
        return _sor.rail_envelopes(self.sor_state.estimate, self.sor)

    def sor_summary(self) -> dict | None:
        """Host-side view of the learned safe operating regions (None until
        the first decision under sor=SorConfig)."""
        from repro.core import sor as _sor
        if self.sor is None or self.sor_state is None:
            return None
        return _sor.summary(self.sor_state.estimate, self.sor)

    # -- decide ---------------------------------------------------------------
    def decide(self, plane: PowerPlaneState,
               telemetry: Telemetry) -> PowerPlaneState:
        """Run the policy (no actuation): observation → request →
        arbitration, returning the target state the bus would be asked for."""
        if self.policy is None:
            return plane
        sampled = None
        if self.decide_from == "poll":
            sampled = self.fleet.poll_frame()   # ONE bus sweep per round
            frame = self.observed_frame(plane, telemetry, sampled=sampled)
            self.poll_decisions += 1
        else:
            frame = as_frame(telemetry, state=plane)
        self.last_frame = frame
        env = (self._sor_observe(plane, frame, sampled=sampled)
               if self.sor is not None else None)
        plane, request = _run_policy(
            self.policy, plane, frame, telemetry, self.rail_map, host=True,
            envelope=env)
        self.last_request = _concrete_or_none(request)
        self.last_envelope = _concrete_or_none(env)
        return plane

    # -- actuate --------------------------------------------------------------
    def _deadband_skips(self, want: dict[str, np.ndarray], n: int
                        ) -> tuple[dict[str, np.ndarray],
                                   dict[str, np.ndarray]]:
        """(skips, governed): per-rail [n] bool masks. `skips` marks lanes
        the deadband scheduler holds back from the bus this round: the
        target sits within `confidence * deadband_v` of the rail's learned
        floor AND the regulator already holds it (within the same band) — a
        steady-state envelope-pinned lane whose write would be a no-op
        transaction. `governed` marks lanes with a learned envelope at
        nonzero confidence — the lanes whose pinning can justify poll
        back-pressure. Rails without a learned envelope (or at zero
        confidence) never skip, so cold start actuates every lane, exactly
        as before."""
        skips = {name: np.zeros(n, bool) for name in RAIL_LANES}
        governed = {name: np.zeros(n, bool) for name in RAIL_LANES}
        if self.deadband_v <= 0.0 or self.last_envelope is None:
            return skips, governed
        from repro.core.sor import envelope_for
        for name, lane in RAIL_LANES.items():
            env = envelope_for(self.last_envelope, name)
            if env is None:
                continue
            r = self.rail_map.by_name(name)
            conf = np.broadcast_to(np.asarray(
                jax.device_get(env.confidence), np.float64), (n,))
            floor = np.broadcast_to(np.asarray(
                jax.device_get(env.floor(r.v_min)), np.float64), (n,))
            held = np.array([self.fleet.segments[i].rail_voltage(lane)
                             for i in range(n)], np.float64)
            band = conf * self.deadband_v
            governed[name] = conf > 0.0
            skips[name] = (governed[name]
                           & (np.abs(want[name] - floor) <= band)
                           & (np.abs(held - want[name]) <= band))
        return skips, governed

    def actuate(self, plane: PowerPlaneState) -> PowerPlaneState:
        """Push the state's rail voltages through PMBus on every board;
        returns the state with voltages replaced by what the regulators
        actually achieved (clamp + LINEAR16 quantization + settling).
        Lanes held back by the deadband scheduler (`deadband_v` > 0 with a
        learned envelope) are omitted from the bus round entirely and read
        back as the voltage the regulator already holds."""
        batched = jnp.ndim(plane.v_core) >= 1
        want = {name: np.atleast_1d(np.asarray(jax.device_get(
                    getattr(plane, field)), dtype=np.float64))
                for name, field in _LANE_FIELDS.items()}
        n = want["VDD_CORE"].shape[0]
        if n != self.fleet.n_boards:
            raise ValueError(
                f"state has {n} chip(s) but the fleet bus has "
                f"{self.fleet.n_boards} board(s)")
        skips, governed = self._deadband_skips(want, n)
        self.skipped_actuations += int(sum(s.sum() for s in skips.values()))
        if self.poll_relax > 1.0:
            # deadband-paired poll back-pressure: a board whose every
            # governed lane is pinned this round polls at poll_relax x the
            # requested interval; any lane leaving its band restores the
            # full rate on the board's next firing
            skp = np.stack([skips[name] for name in RAIL_LANES])
            gov = np.stack([governed[name] for name in RAIL_LANES])
            pinned_board = gov.any(axis=0) & (skp | ~gov).all(axis=0)
            lanes_pinned = skp.sum(axis=0)
            for i in range(n):
                self.fleet.set_poll_relax(
                    i, self.poll_relax if pinned_board[i] else 1.0,
                    lanes_pinned=int(lanes_pinned[i]))
        setpoints = [{RAIL_LANES[name]: float(want[name][i])
                      for name in RAIL_LANES if not skips[name][i]}
                     for i in range(n)]
        achieved, self.last_report = self.fleet.apply_setpoints(
            setpoints, settle_band_frac=self.settle_band_frac)
        # skipped lanes read back whatever the regulator holds
        got = {name: np.array(
                   [achieved[i].get(lane,
                                    self.fleet.segments[i].rail_voltage(lane))
                    for i in range(n)], dtype=np.float32)
               for name, lane in RAIL_LANES.items()}
        if not batched:
            return dataclasses.replace(
                plane,
                v_core=jnp.float32(got["VDD_CORE"][0]),
                v_hbm=jnp.float32(got["VDD_HBM"][0]),
                v_io=jnp.float32(got["VDD_IO"][0]))
        return dataclasses.replace(
            plane,
            v_core=jnp.asarray(got["VDD_CORE"]),
            v_hbm=jnp.asarray(got["VDD_HBM"]),
            v_io=jnp.asarray(got["VDD_IO"]))

    # old single-board HostPowerController spelling
    apply = actuate

    def control_step(self, plane: PowerPlaneState,
                     telemetry: Telemetry) -> PowerPlaneState:
        self.decisions += 1
        return self.actuate(self.decide(plane, telemetry))

    # -- observability --------------------------------------------------------
    @property
    def pm(self):
        """Board 0's PowerManager (single-board back-compat)."""
        return self.fleet.segments[0].pm

    @property
    def actuations(self) -> int:
        return self.fleet.lane_writes

    @property
    def actuation_seconds(self) -> float:
        return self.fleet.actuation_seconds

    def readback(self, board: int = 0) -> dict[str, float]:
        """PMBus-sampled (READ_VOUT) rail voltages of one board."""
        pm = self.fleet.segments[board].pm
        return {name: pm.get_voltage(lane)
                for name, lane in RAIL_LANES.items()}

    def enable_polling(self, interval_s: float | None = None,
                       lanes=None) -> None:
        """Start periodic READ_VOUT telemetry polling on every board's bus
        segment (paper Table VI intervals by default), interleaved with this
        controller's actuations on the fleet timeline. Polls fire as fleet
        time advances — call `self.fleet.idle(dt)` between control rounds to
        model the training time a real deployment would poll through."""
        self.fleet.start_polling(interval_s, lanes)

    def stats(self) -> ControlPlaneStats:
        return ControlPlaneStats(
            decisions=self.decisions,
            actuations=self.fleet.lane_writes,
            failed_actuations=self.fleet.failed_writes,
            actuation_seconds=self.fleet.actuation_seconds,
            serialized_seconds=self.fleet.serialized_seconds,
            polls=sum(st.polls for st in self.fleet.poll_stats.values()),
            polls_deferred=sum(st.deferred
                               for st in self.fleet.poll_stats.values()),
            poll_decisions=self.poll_decisions,
            skipped_actuations=self.skipped_actuations,
            relaxed_polls=sum(st.relaxed_polls
                              for st in self.fleet.poll_stats.values()))


class HostPowerController(HostRailController):
    """Back-compat shim: the pre-control-plane single-board actuator
    (`apply(state)`), now a thin alias over HostRailController."""

    def __init__(self, path: ControlPath | str = ControlPath.SOFTWARE,
                 clock_hz: int = 400_000, spec: ChipSpec = V5E):
        super().__init__(None, n_chips=1, path=path, clock_hz=clock_hz,
                         spec=spec)
