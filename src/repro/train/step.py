"""Train-step factory: microbatch gradient accumulation, gradient sync
(XLA-auto or error-feedback-compressed — the paper-adapted bounded-error
link), AdamW update, and the power plane woven through the step.

Two control paths, mirroring the paper (DESIGN.md §2.2):
  * in-graph controller: observation (TelemetryFrame) → policy.decide →
    arbitrate composed INTO the jitted step (HW path analogue —
    deterministic, no host round trip);
  * host controller: the trainer runs a control_plane.HostRailController
    between steps, actuating through the PMBus-simulated fleet bus (SW
    analogue — optionally deciding from its own READ_VOUT polling,
    `decide_from="poll"`). Both paths implement control_plane.RailController.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import ecollectives
from repro.core.control_plane import as_controller
from repro.core.hwspec import FleetSpec
from repro.core.power_plane import (PowerPlaneState, StepProfile,
                                    account_and_observe,
                                    account_fleet_and_observe)
from repro.kernels import ops
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class StepConfig:
    microbatches: int = 1
    grad_sync: str = "auto"          # auto | ef_int8 | ef_int8_topk
    k_fraction: float = 0.25
    policy: Any = None               # in-graph policy/RailController or None
    dp_axes: tuple[str, ...] = ("data",)  # manual axes for ef sync


@dataclasses.dataclass(frozen=True)
class FleetStepConfig:
    """Fleet-native extension of StepConfig: one jitted step drives a
    `[n_chips]` power plane whose chips carry per-chip process variation
    (`FleetSpec`), with in-graph per-chip straggler/fault injection coupled
    to each chip's voltage margin. At `FleetSpec.uniform(1)` the fleet step
    is numerically equivalent to the scalar step as long as the
    margin-coupled error feedback is inactive — uncompressed grad sync or
    `error_gain=0` (pinned by tests/test_fleet_native.py). With ef_int8*
    sync AND a nonzero `error_gain`, the fleet step intentionally models
    margin-amplified measured error that the scalar step cannot, so the
    trajectories diverge once a policy undervolts VDD_IO."""
    spec: FleetSpec
    # per-chip measured-error telemetry: how fast a chip's gradient-domain
    # error grows as it digs below its own nominal VDD_IO, scaled by the
    # chip's BER-curve offset (FleetSpec.error_sensitivity)
    error_gain: float = 12.0
    link_ber_floor: float = 0.0      # intrinsic link error floor (no compression)
    telemetry_noise: float = 0.0     # relative noise on measured error
    # per-chip stragglers: base per-step probability, amplified by the chip's
    # VDD_CORE undervolt margin — weak chips at fleet setpoints straggle first
    straggler_prob: float = 0.0
    straggler_factor: float = 4.0
    straggler_margin_gain: float = 8.0
    # margin-coupled HBM interface error rate (the VDD_HBM failure
    # observable): base rate amplified by the chip's VDD_HBM undervolt
    # margin. Base 0.0 (default) records a zero observable — inert for
    # control, but honest telemetry.
    hbm_error_base: float = 0.0
    hbm_error_gain: float = 24.0
    # fleet reductions on a sharded `chips` mesh axis: when `mesh` spans
    # more than one device, the per-chip telemetry matrix never gathers —
    # each device reduces its local shard through the Pallas/XLA
    # fleet_reduce hot path and the partials combine via pmax/pmin/psum
    # (ops.sharded_fleet_reduce). On a single-device (CPU) mesh, or with
    # mesh=None, the step falls back to the plain vmap-path fleet_reduce —
    # identical results, no shard_map.
    mesh: Any = None
    shard_axis: str = "chips"
    # shard the learned control round itself (control_plane.
    # sharded_control_round): the SorState history ring, ingest, refit,
    # envelopes, and decide/arbitrate all run per shard inside shard_map —
    # only the fleet reductions and the confidence summary scalars cross
    # shards. None (default) auto-enables when `mesh` spans more than one
    # device; True forces the shard_map path even on a 1-device mesh (the
    # bit-equality testing knob, mirroring sharded_fleet_reduce's
    # use_shard_map); False keeps the control round unsharded. Requires
    # `sor` and an elementwise (not cross_chip) policy.
    shard_control: "bool | None" = None
    # in-graph safe-operating-region learning (core/sor.py): when set, the
    # step threads a functional `sor.SorState` through its signature —
    # train_step(params, opt, plane, ef, sor_state, batch) -> (..., sor_state',
    # metrics) — so per-rail frontiers are learned DURING training, not just
    # by the host controller, and the state checkpoints next to the plane
    # (ckpt.save / ckpt.remap_sor). Requires an in-graph policy
    # (StepConfig.policy) and ingest="frames".
    sor: Any = None
    seed: int = 0


def _accumulate_grads(loss_fn, params, batch, microbatches: int):
    """Returns (mean_loss, metrics, mean_grads)."""
    if microbatches <= 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, metrics, grads

    def reshape(a):
        b = a.shape[0]
        return a.reshape((microbatches, b // microbatches) + a.shape[1:])

    mbatch = jax.tree_util.tree_map(reshape, batch)

    def body(acc, mb):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb)
        acc_loss, acc_grads = acc
        acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
        return (acc_loss + loss, acc_grads), metrics

    zero_grads = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grads_sum), metrics = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zero_grads), mbatch)
    inv = 1.0 / microbatches
    grads = jax.tree_util.tree_map(lambda g: g * inv, grads_sum)
    metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
    return loss_sum * inv, metrics, grads


def _grads_and_update(loss_fn, opt_cfg, schedule_fn, step_cfg,
                      params, opt_state, ef_resid, batch):
    """The model side of a train step, shared by the scalar and fleet step
    factories: microbatched grads, optional error-feedback compressed sync,
    AdamW update. Returns (params', opt_state', ef_resid', loss, metrics,
    opt_metrics, grad_error)."""
    loss, metrics, grads = _accumulate_grads(
        loss_fn, params, batch, step_cfg.microbatches)

    grad_error = jnp.zeros((), jnp.float32)
    if step_cfg.grad_sync.startswith("ef_int8"):
        # error-feedback compression BEFORE the cross-replica reduction
        level = (ecollectives.LEVEL_INT8_TOPK
                 if step_cfg.grad_sync == "ef_int8_topk"
                 else ecollectives.LEVEL_INT8)
        raw = grads
        grads, ef_resid = ecollectives.ef_compress(
            grads, ef_resid, level, step_cfg.k_fraction)
        grad_error = ecollectives.compression_error_norm(raw, grads)
        axis = step_cfg.dp_axes[0]
        grads = ecollectives.reduce_gradients(
            grads, axis, level=ecollectives.LEVEL_INT8
            if level >= ecollectives.LEVEL_INT8 else 0)
        loss = jax.lax.pmean(loss, axis)

    lr = schedule_fn(opt_state["step"])
    params, opt_state, opt_metrics = adamw.apply_updates(
        params, grads, opt_state, lr, opt_cfg)
    return params, opt_state, ef_resid, loss, metrics, opt_metrics, grad_error


def make_train_step(loss_fn: Callable, opt_cfg: adamw.AdamWConfig,
                    schedule_fn: Callable, profile: StepProfile,
                    step_cfg: StepConfig):
    """Returns train_step(params, opt_state, plane, ef_resid, batch) ->
    (params', opt_state', plane', ef_resid', metrics)."""
    # HW-path analogue: the in-graph controller is compiled INTO the step,
    # behind the same RailController interface the host path uses.
    controller = as_controller(step_cfg.policy)

    def train_step(params, opt_state, plane: PowerPlaneState, ef_resid, batch):
        (params, opt_state, ef_resid, loss, metrics, opt_metrics,
         grad_error) = _grads_and_update(loss_fn, opt_cfg, schedule_fn,
                                         step_cfg, params, opt_state,
                                         ef_resid, batch)

        # observation → decision → arbitration, all in-graph: the typed
        # EXACT frame is what the controller's policy sees
        plane, frame, power_metrics = account_and_observe(profile, plane)
        frame = dataclasses.replace(frame, grad_error=grad_error)
        if controller is not None:
            plane = controller.control_step(plane, frame)

        telemetry = {**power_metrics, "grad_error": grad_error}
        out_metrics = {"loss": loss, **metrics, **opt_metrics, **telemetry}
        return params, opt_state, plane, ef_resid, out_metrics

    return train_step


def make_fleet_train_step(loss_fn: Callable, opt_cfg: adamw.AdamWConfig,
                          schedule_fn: Callable, profile: StepProfile,
                          step_cfg: StepConfig, fleet_cfg: FleetStepConfig):
    """Fleet-native train step: same model/optimizer math as the scalar
    step, but the power plane is `[n_chips]` with per-chip process
    variation, per-chip margin-coupled fault/straggler injection, and fleet
    reductions (worst/mean/p95) computed in-graph through the Pallas
    `ops.fleet_reduce` hot path.

    The model itself is SPMD-replicated (every chip computes the same
    grads); what varies per chip is the *power/telemetry* world: measured
    gradient-domain error scales with the chip's BER-curve offset and its
    VDD_IO undervolt margin, stragglers fire preferentially on chips whose
    VDD_CORE margin is thinnest, and the HBM interface error rate grows with
    each chip's VDD_HBM margin. Per-step randomness derives from
    `fold_in(seed, plane.step)` so the trainer's call signature — and
    checkpoint/restart determinism — are unchanged.

    With `fleet_cfg.sor` set, the returned step instead has the signature
    train_step(params, opt_state, plane, ef_resid, sor_state, batch) ->
    (params', opt_state', plane', ef_resid', sor_state', metrics): the
    in-graph controller pushes every step's frame (per-rail voltages + the
    margin-coupled failure observables above) into the `sor.SorState`
    threaded through the carry, refreshes the per-rail frontier estimates on
    the configured cadence, and decides/arbitrates under the learned
    envelopes — learning happens DURING training, and the state persists
    through `ckpt.save` like any other group (the Trainer does this when its
    init_state carries a "sor" entry)."""
    controller = as_controller(step_cfg.policy)
    sor_cfg = fleet_cfg.sor
    if sor_cfg is not None:
        from repro.core.control_plane import with_sor
        if controller is None:
            raise ValueError("FleetStepConfig.sor needs an in-graph policy "
                             "(StepConfig.policy) to consume the learned "
                             "envelopes")
        controller = with_sor(controller, sor_cfg)

    # resolve the sharded-control-round knob once, at factory time: the mesh
    # is static, so the shard_map'd round is built here and closed over
    shard_control = fleet_cfg.shard_control
    if shard_control is None:
        shard_control = (fleet_cfg.mesh is not None
                         and fleet_cfg.mesh.devices.size > 1
                         and sor_cfg is not None)
    sharded_round = None
    if shard_control:
        from repro.core.control_plane import sharded_control_round
        if fleet_cfg.mesh is None:
            raise ValueError("FleetStepConfig.shard_control=True needs a mesh")
        if sor_cfg is None:
            raise ValueError("FleetStepConfig.shard_control shards the "
                             "learned (SOR) control round — set "
                             "FleetStepConfig.sor, or leave shard_control "
                             "off (the reduction still shards via mesh=)")
        sharded_round = sharded_control_round(
            controller, fleet_cfg.mesh, fleet_cfg.shard_axis)
    fs = fleet_cfg.spec
    n = fs.n_chips
    v_nom_core = jnp.asarray(fs.v_core_nominal, jnp.float32)
    v_nom_hbm = jnp.asarray(fs.v_hbm_nominal, jnp.float32)
    v_nom_io = jnp.asarray(fs.v_io_nominal, jnp.float32)
    sens = jnp.asarray(fs.error_sensitivity, jnp.float32)

    def _step_body(params, opt_state, plane: PowerPlaneState, ef_resid,
                   sor_state, batch):
        (params, opt_state, ef_resid, loss, metrics, opt_metrics,
         grad_error) = _grads_and_update(loss_fn, opt_cfg, schedule_fn,
                                         step_cfg, params, opt_state,
                                         ef_resid, batch)

        plane, frame, power_metrics = account_fleet_and_observe(
            profile, plane, fs)
        key = jax.random.fold_in(jax.random.PRNGKey(fleet_cfg.seed),
                                 plane.step[0])
        k_err, k_straggle = jax.random.split(key)

        # per-chip measured error: the shared compression error (plus any
        # intrinsic link floor) seen through each chip's own BER curve —
        # offset by process variation, amplified by ITS undervolt margin
        margin_io = jnp.maximum(0.0, v_nom_io - plane.v_io) / v_nom_io
        noise = 1.0 + fleet_cfg.telemetry_noise * jax.random.normal(
            k_err, (n,))
        err = ((grad_error + fleet_cfg.link_ber_floor) * sens * noise
               * (1.0 + fleet_cfg.error_gain * margin_io))

        # per-chip stragglers: thin VDD_CORE margin -> higher odds. The
        # margin-coupled *rate* is the VDD_CORE failure observable the SOR
        # learner fits (the realized 0/1 draw is far too noisy to regress).
        margin_core = jnp.maximum(0.0, v_nom_core - plane.v_core) / v_nom_core
        p_straggle = jnp.clip(
            fleet_cfg.straggler_prob
            * (1.0 + fleet_cfg.straggler_margin_gain * margin_core), 0.0, 1.0)
        straggle = jax.random.uniform(k_straggle, (n,)) < p_straggle
        t_chip = power_metrics["t_step_s"] * jnp.where(
            straggle, fleet_cfg.straggler_factor, 1.0)

        # per-chip HBM interface errors: thin VDD_HBM margin -> higher rate
        # (the VDD_HBM failure observable)
        margin_hbm = jnp.maximum(0.0, v_nom_hbm - plane.v_hbm) / v_nom_hbm
        hbm_rate = (jnp.float32(fleet_cfg.hbm_error_base) * sens
                    * (1.0 + fleet_cfg.hbm_error_gain * margin_hbm))

        # the frame is already anchored to the FleetSpec per-chip nominals;
        # overlay the per-chip measured error + straggler-stretched times +
        # the per-rail failure observables (telemetry.RAIL_OBSERVABLE_KEYS)
        frame = dataclasses.replace(
            frame, grad_error=err,
            extras={**frame.extras, "t_chip_s": t_chip,
                    "straggle_rate": p_straggle, "hbm_error_rate": hbm_rate})
        telemetry = {**power_metrics, "grad_error": err, "t_chip_s": t_chip,
                     "straggle_rate": p_straggle, "hbm_error_rate": hbm_rate,
                     "v_nom_core": v_nom_core, "v_nom_hbm": v_nom_hbm,
                     "v_nom_io": v_nom_io}
        sor_conf = None
        if sharded_round is not None:
            # per-shard resident control round: the frame slice lands in the
            # shard's own history ring, refit/envelopes/decide/arbitrate run
            # elementwise on-shard, and only the confidence summary scalars
            # cross shards (bit-equal trajectories — the RNG observables
            # above were drawn on global shapes, outside the shard_map)
            plane, sor_state, conf_sum, conf_min = sharded_round(
                plane, frame, sor_state)
            sor_conf = (conf_sum / sor_state.estimate.confidence.size,
                        conf_min)
        elif sor_cfg is not None:
            plane, sor_state = controller.control_step_sor(
                plane, frame, sor_state)
        elif controller is not None:
            plane = controller.control_step(plane, frame)

        # fleet reductions through the Pallas telemetry-reduction hot path:
        # [n_chips, n_fields] -> per-field worst/mean (+ p95 where it gates).
        # With a multi-device mesh the reduction runs sharded over the
        # chips axis (local kernel reduce + pmax/pmin/psum collectives).
        stacked = jnp.stack([power_metrics["power_w"], t_chip, err,
                             power_metrics["energy_step_j"], plane.v_io],
                            axis=1)
        if fleet_cfg.mesh is not None:
            mx, mn, sm = ops.sharded_fleet_reduce(
                stacked, mesh=fleet_cfg.mesh,
                axis_name=fleet_cfg.shard_axis,
                # a forced-on-1-device sharded control round forces the
                # reduction through shard_map too, so tests exercise the
                # whole sharded graph on any device count
                use_shard_map=True if shard_control else None)
        else:
            mx, mn, sm = ops.fleet_reduce(stacked)
        fleet_metrics = {}
        # for these, the worst chip is the max; for a voltage rail it is the
        # MIN (thinnest margin), so v_io gets min/mean instead
        for i, name in enumerate(("power_w", "t_chip_s", "grad_error",
                                  "energy_step_j")):
            fleet_metrics[f"fleet/{name}_worst"] = mx[i]
            fleet_metrics[f"fleet/{name}_mean"] = sm[i] / n
        fleet_metrics["fleet/v_io_min"] = mn[4]
        fleet_metrics["fleet/v_io_mean"] = sm[4] / n
        # a synchronous fleet steps at its slowest chip
        fleet_metrics["fleet/t_fleet_s"] = mx[1]
        # p95 tails through the kernels-layer seam (sort-bound — the [n]
        # stat vectors are the only cross-shard traffic on the sharded path)
        fleet_metrics["fleet/t_chip_p95_s"] = ops.fleet_percentile(
            t_chip, 95.0)
        fleet_metrics["fleet/grad_error_p95"] = ops.fleet_percentile(
            err, 95.0)
        fleet_metrics["fleet/straggler_frac"] = jnp.mean(
            straggle.astype(jnp.float32))

        if sor_conf is not None:
            # learned-region telemetry from the in-round collectives (one
            # psum + one pmin scalar — the SorState itself never gathers)
            fleet_metrics["fleet/sor_conf_mean"] = sor_conf[0]
            fleet_metrics["fleet/sor_conf_min"] = sor_conf[1]
        elif sor_cfg is not None:
            # learned-region telemetry: how much of the fleet trusts a fit
            fleet_metrics["fleet/sor_conf_mean"] = jnp.mean(
                sor_state.estimate.confidence)
            fleet_metrics["fleet/sor_conf_min"] = jnp.min(
                sor_state.estimate.confidence)

        # v_nom_* are static per-run FleetSpec constants — policy inputs,
        # not telemetry worth logging every step
        logged = {k: v for k, v in telemetry.items()
                  if not k.startswith("v_nom_")}
        out_metrics = {"loss": loss, **metrics, **opt_metrics, **logged,
                       **fleet_metrics}
        return params, opt_state, plane, ef_resid, sor_state, out_metrics

    if sor_cfg is not None:
        def train_step(params, opt_state, plane, ef_resid, sor_state, batch):
            return _step_body(params, opt_state, plane, ef_resid, sor_state,
                              batch)
    else:
        def train_step(params, opt_state, plane, ef_resid, batch):
            out = _step_body(params, opt_state, plane, ef_resid, None, batch)
            return out[:4] + (out[5],)

    return train_step


def jit_train_step(train_step, *, donate=True):
    """jit a train step with its carry buffers donated: params, opt state,
    plane, ef residual — and, for the 6-arg SOR step, the `SorState` too,
    so the O(capacity x rails x chips) history ring is updated in place
    instead of copied every step. Donated inputs are invalidated: callers
    must rebind to the returned state (the trainer's carry loop already
    does) and never reuse the objects they passed in."""
    if not donate:
        return jax.jit(train_step)
    try:
        import inspect
        n_args = len(inspect.signature(train_step).parameters)
    except (TypeError, ValueError):
        n_args = 5
    donate_argnums = (0, 1, 2, 3, 4) if n_args >= 6 else (0, 1, 2, 3)
    return jax.jit(train_step, donate_argnums=donate_argnums)


def shard_fleet_state(state: dict, mesh, axis_name: str = "chips") -> dict:
    """Place the per-chip groups of a trainer state dict (`plane`, `sor`)
    onto `mesh` with their trailing chip axis sharded over `axis_name`
    (ops.chip_specs layout: ring [capacity, n_rails, n] and estimate
    [n_rails, n] shard, scalars replicate). Model groups pass through
    untouched — the fleet step is SPMD-replicated over the model. Use after
    building (or restoring) the initial state, before the first sharded
    step; `ckpt.save` gathers transparently on the way back out."""
    out = dict(state)
    plane = state.get("plane")
    n_chips = None
    if plane is not None and jnp.ndim(plane.v_core) == 1:
        n_chips = plane.v_core.shape[0]
    for group in ("plane", "sor"):
        tree = state.get(group)
        if tree is None or n_chips is None:
            continue
        out[group] = ops.shard_chip_tree(tree, mesh, n_chips, axis_name)
    return out


def shard_map_ef_step(train_step, mesh, dp_axes=("data",)):
    """Wrap a train step for error-feedback compressed data parallelism:
    manual over the DP axes (so the int8 collective is ours), params/opt
    replicated, batch sharded. Used by the e2e examples and the ecollectives
    case-study benchmark (DESIGN.md §2.2)."""
    batch_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])
    rep = P()

    def mapped(params, opt_state, plane, ef_resid, batch):
        return train_step(params, opt_state, plane, ef_resid, batch)

    in_specs = (rep, rep, rep, rep, batch_spec)
    out_specs = (rep, rep, rep, rep, rep)
    return jax.shard_map(mapped, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
