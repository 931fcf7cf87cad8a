"""Trainer: the production loop — checkpoint/restart, simulated node-failure
recovery, deadline-based straggler mitigation, host-path power control, and
telemetry.

Fault-tolerance posture for 1000+ nodes (DESIGN.md §5): the *mechanisms*
(step-atomic checkpoints, elastic restore onto a different mesh, stateless
data pipeline keyed by step) are fully real and tested; node failures and
stragglers themselves are *injected* (this container is one host), driving
the same recovery code paths a real deployment would take.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.core.control_plane import RailController, as_controller
from repro.core.hwspec import FleetSpec
from repro.core.power_plane import PowerPlaneState
from repro.core.telemetry import TelemetryLog
from repro.core import ecollectives
from repro.core import sor as sor_mod
from repro.checkpoint.ckpt import CheckpointManager, remap_plane, remap_sor


class SimulatedNodeFailure(RuntimeError):
    pass


@dataclasses.dataclass
class FaultConfig:
    fail_prob: float = 0.0           # per-step probability of a node loss
    straggler_prob: float = 0.0      # per-step probability of a slow node
    straggler_factor: float = 4.0    # slow node runs this much slower
    grace: float = 1.5               # deadline = grace * median step time
    seed: int = 0


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int
    ckpt_every: int = 50             # 0 = never checkpoint
    ckpt_dir: str = "/tmp/repro_ckpt"
    async_ckpt: bool = True
    # Host-path (SW analogue) control plane: a RailController, or a bare
    # Policy (wrapped so its decision runs between steps, decide-only; pass a
    # HostRailController to also pay PMBus actuation — and decide_from="poll"
    # to close the loop on its own READ_VOUT sampling). The in-graph (HW
    # analogue) path is configured on the step (train.step.StepConfig.policy).
    controller: RailController | Any = None
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    # Fleet provenance: checkpointed alongside the plane so elastic restarts
    # onto a different fleet size remap per-chip state explicitly.
    fleet: FleetSpec | None = None
    # In-graph learned safe operating regions: the SorConfig the train step
    # was built with (train.step.FleetStepConfig.sor). When set — and
    # init_state carries a "sor" entry — the trainer threads the functional
    # SorState through the (6-arg) step, checkpoints it next to the plane,
    # remaps it across fleet sizes on elastic restore, and folds the learned
    # per-rail view into summary()["sor"].
    sor: Any = None
    # Sharded control plane (train.step.FleetStepConfig.mesh/shard_control):
    # when set, restored per-chip state (plane + SorState) is re-placed onto
    # this mesh after restore/remap — `ckpt.save` gathers transparently to
    # host arrays, restore lands on the default device, and `remap_plane`/
    # `remap_sor` run on the gathered view, so `shard_fleet_state` scatters
    # the result back before the next sharded step. Checkpoint files and
    # remap semantics are identical to the unsharded trainer.
    mesh: Any = None
    shard_axis: str = "chips"

    def __post_init__(self):
        self.controller = as_controller(self.controller, host=True)


class Trainer:
    def __init__(self, train_step: Callable, data, cfg: TrainerConfig,
                 init_state: dict[str, Any]):
        """init_state: {'params','opt','plane','ef'} pytrees."""
        self.train_step = train_step
        self.data = data
        self.cfg = cfg
        self.state = dict(init_state)
        self.ckpt = CheckpointManager(cfg.ckpt_dir, async_save=cfg.async_ckpt)
        self.log = TelemetryLog()
        self.start_step = 0
        self.restarts = 0
        self.straggler_events = 0
        self.ckpt_writes = 0
        self._rng = np.random.default_rng(cfg.faults.seed)
        self._step_times: list[float] = []
        # fail fast on SOR misconfiguration — otherwise it only surfaces as
        # an opaque step-arity TypeError on the first training step (the
        # 6-arg SOR step and the "sor" state entry must come together), or
        # as a summary() error after the whole run (rails mismatch)
        ss = self.state.get("sor")
        if (cfg.sor is None) != (ss is None):
            raise ValueError(
                "TrainerConfig.sor and init_state['sor'] must be set "
                "together: the SOR train step (FleetStepConfig.sor) takes "
                "the 6-arg signature and threads the state the trainer "
                "carries — configure both or neither")
        if ss is not None and ss.history.rails != cfg.sor.rails:
            raise ValueError(
                f"TrainerConfig.sor declares rails "
                f"{[s.rail for s in cfg.sor.rails]} but init_state['sor'] "
                f"was built with {[s.rail for s in ss.history.rails]}; "
                f"pass the same SorConfig as FleetStepConfig.sor")

    # -- checkpoint/restart ----------------------------------------------------
    def maybe_restore(self) -> bool:
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        step, restored = self.ckpt.restore(self.state, optional=("sor",))
        self.state.update(restored)
        self._remap_restored_plane()
        self.start_step = step
        return True

    def _remap_restored_plane(self) -> None:
        """Elastic fleet restore: when this run's FleetSpec differs in size
        from the checkpoint's, remap the restored `[n_old]` plane onto the
        current fleet explicitly (surviving chips keep their per-chip state,
        joiners start at their own nominal point). A restored SorState is
        remapped the same way — survivors keep their learned regions,
        joiners start at the cold-start static pin."""
        if self.cfg.fleet is None:
            return
        n_target = self.cfg.fleet.n_chips
        plane = self.state["plane"]
        if not (plane.is_fleet and plane.n_chips == n_target):
            self.state["plane"] = remap_plane(plane, self.cfg.fleet)
        ss = self.state.get("sor")
        if ss is not None and ss.history.chip_shape \
                and ss.history.chip_shape[0] != n_target:
            self.state["sor"] = remap_sor(ss, self.cfg.fleet)
        if self.cfg.mesh is not None:
            # scatter the (gathered, remapped) per-chip state back onto the
            # chips mesh so the next sharded step starts shard-resident
            from repro.train.step import shard_fleet_state
            self.state = shard_fleet_state(self.state, self.cfg.mesh,
                                           self.cfg.shard_axis)

    def _save(self, step: int):
        self.ckpt.save(step, self.state, fleet=self.cfg.fleet)
        self.ckpt_writes += 1

    # -- fault injection ---------------------------------------------------------
    def _inject_faults(self, step: int, t_step: float) -> float:
        f = self.cfg.faults
        if f.fail_prob and self._rng.random() < f.fail_prob:
            raise SimulatedNodeFailure(f"node lost at step {step}")
        if f.straggler_prob and self._rng.random() < f.straggler_prob:
            # a straggling node would stretch the step by straggler_factor;
            # deadline-based mitigation caps the damage at grace * median.
            # Median excludes the first (compile) step and uses a recent
            # window so warmup outliers don't inflate the deadline.
            recent = self._step_times[1:][-20:]
            med = float(np.median(recent)) if recent else t_step
            slow = t_step * f.straggler_factor
            mitigated = min(slow, med * f.grace)
            self.straggler_events += 1
            return mitigated
        return t_step

    # -- the loop -----------------------------------------------------------------
    def run(self) -> TelemetryLog:
        cfg = self.cfg
        step = self.start_step
        while step < cfg.total_steps:
            try:
                step = self._run_span(step)
            except SimulatedNodeFailure:
                # recovery path: reload last complete checkpoint and resume —
                # the data pipeline is stateless in step, so no drift
                self.restarts += 1
                self.ckpt.wait()
                latest = self.ckpt.latest_step()
                if latest is not None:
                    s, restored = self.ckpt.restore(self.state,
                                                    optional=("sor",))
                    self.state.update(restored)
                    self._remap_restored_plane()
                    step = s
                # else: restart from the in-memory state (step unchanged)
        self.ckpt.wait()
        return self.log

    def _run_span(self, step: int) -> int:
        """Steps until `total_steps`. Host spans name each stage for the
        profiler: `train.step` (stat `step`) holds `train.batch`,
        `train.dispatch`, `train.wait`, `train.control` (host-path
        controller only), `train.telemetry` and `train.ckpt` (when a save
        is due)."""
        cfg = self.cfg
        span = jax.profiler.TraceAnnotation
        while step < cfg.total_steps:
            with span("train.step", step=step):
                with span("train.batch"):
                    batch = self.data.jax_batch(step)
                t0 = time.perf_counter()
                with span("train.dispatch"):
                    if "sor" in self.state:
                        # in-graph SOR step: the functional SorState rides
                        # the trainer state like any other carry (and
                        # checkpoints)
                        params, opt, plane, ef, sor_state, metrics = \
                            self.train_step(
                                self.state["params"], self.state["opt"],
                                self.state["plane"], self.state["ef"],
                                self.state["sor"], batch)
                    else:
                        sor_state = None
                        params, opt, plane, ef, metrics = self.train_step(
                            self.state["params"], self.state["opt"],
                            self.state["plane"], self.state["ef"], batch)
                with span("train.wait"):
                    jax.block_until_ready(metrics["loss"])
                wall = time.perf_counter() - t0
                wall = self._inject_faults(step, wall)
                self._step_times.append(wall)

                self.state.update(params=params, opt=opt, plane=plane, ef=ef)
                if sor_state is not None:
                    self.state["sor"] = sor_state

                # host-path control (SW analogue): one control_step through
                # the unified rail control plane (decide + PMBus-actuate)
                if cfg.controller is not None:
                    with span("train.control"):
                        self.state["plane"] = cfg.controller.control_step(
                            plane, metrics)
                        metrics = self._with_sor_metrics(metrics)

                with span("train.telemetry"):
                    self.log.append_from(step, metrics["loss"], metrics,
                                         self.state["plane"])
                step += 1
                if cfg.ckpt_every and (step % cfg.ckpt_every == 0
                                       or step == cfg.total_steps):
                    with span("train.ckpt"):
                        self._save(step)
        return step

    def _with_sor_metrics(self, metrics: dict[str, Any]) -> dict[str, Any]:
        """Fold the controller's learned safe-operating-region view into the
        step telemetry (`sor/...` scalar keys) so the TelemetryLog records
        how the fleet's learned envelope evolves over training."""
        summarize = getattr(self.cfg.controller, "sor_summary", None)
        s = summarize() if callable(summarize) else None
        if not s:
            return metrics
        return {**metrics,
                **{f"sor/{k}": float(v) for k, v in s.items()
                   if np.isfinite(v)}}

    # -- reporting -------------------------------------------------------------
    def summary(self) -> dict[str, Any]:
        t = self.log.totals()
        ctrl = (self.cfg.controller.stats() if self.cfg.controller is not None
                else None)
        out = {
            **t,
            "restarts": self.restarts,
            "straggler_events": self.straggler_events,
            "ckpt_writes": self.ckpt_writes,
            "host_actuations": ctrl.actuations if ctrl else 0,
            "host_actuation_s": ctrl.actuation_seconds if ctrl else 0.0,
            # writes the deadband scheduler held back from the bus (steady-
            # state lanes pinned at a learned floor) — saved transactions
            "host_skipped_actuations": ctrl.skipped_actuations if ctrl else 0,
            "mean_wall_step_s": float(np.mean(self._step_times))
            if self._step_times else 0.0,
        }
        if self.log.records:
            last = self.log.records[-1]
            out["n_chips"] = last.n_chips
            if last.fleet:   # fleet run: surface the gating worst-chip view
                out["fleet_last"] = dict(last.fleet)
        summarize = getattr(self.cfg.controller, "sor_summary", None)
        sor = summarize() if callable(summarize) else None
        if sor is None and self.cfg.sor is not None \
                and self.state.get("sor") is not None:
            # in-graph learner: summarize the state threaded through the step
            sor = sor_mod.summary(self.state["sor"].estimate, self.cfg.sor)
        if sor:              # learned safe-operating-region state, if any
            out["sor"] = sor
        return out


def initial_plane_and_ef(params, fleet: FleetSpec | None = None
                         ) -> tuple[PowerPlaneState, Any]:
    """Initial (plane, error-feedback residuals). With a `FleetSpec`, the
    plane is `[n_chips]` with every chip at its own process-varied nominal
    point (pair with train.step.make_fleet_train_step)."""
    plane = (PowerPlaneState.from_fleet(fleet) if fleet is not None
             else PowerPlaneState.nominal())
    return plane, ecollectives.zeros_like_residuals(params)
