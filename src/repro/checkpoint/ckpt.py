"""Step-atomic checkpointing with elastic restore.

Layout: <dir>/step_<N>/
    manifest.msgpack   — leaf paths, shapes, dtypes, step, mesh metadata,
                         and (fleet runs) the FleetSpec provenance
    arrays.npz         — one entry per leaf (path-keyed); fleet runs add a
                         `fleet_spec::` group with the per-chip nominals
    .complete          — commit marker written LAST (atomicity: a partially
                         written checkpoint is never visible to restore)

Elastic restore: arrays are saved as full (unsharded) host arrays with their
*logical* role recorded via path names; restore re-shards onto whatever mesh
is active via parallel.sharding.param_pspecs — a 2x16x16 checkpoint restores
onto 16x16 (or 1 device) unchanged. Background (async) save is supported for
step-overlap; `wait()` joins the writer.

Fleet elasticity: `save(..., fleet=FleetSpec)` records the fleet's seed and
per-chip process-variation arrays next to the plane state; restoring onto a
*different* fleet size goes through `remap_plane` — surviving chips keep
their per-chip operating point/energy, new chips start at their own nominal
— so the remapping is explicit, never a silent broadcast/truncation.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any

import jax
import msgpack
import numpy as np

from repro.core.hwspec import FleetSpec

_DTYPE_FIX = {"bfloat16": "bfloat16"}

# FleetSpec per-chip arrays persisted under the `fleet_spec::` npz group
_FLEET_FIELDS = ("v_core_nominal", "v_hbm_nominal", "v_io_nominal",
                 "leakage_scale", "error_sensitivity")


def remap_plane(plane, target: FleetSpec):
    """Explicitly remap a restored plane onto a `target` fleet of a possibly
    different size: chips 0..min(n_old, n_new)-1 keep their restored per-chip
    state (operating point, accumulated energy, step counter); chips beyond
    the restored fleet start at their *own* process-varied nominal point with
    zero energy. A scalar plane is treated as a 1-chip fleet. Returns the
    plane unchanged when the sizes already match."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from repro.core.power_plane import PowerPlaneState

    n_old = plane.n_chips
    n_new = target.n_chips
    if plane.is_fleet and n_old == n_new:
        return plane
    fresh = PowerPlaneState.from_fleet(target)
    k = min(n_old, n_new)

    def take(old, new):
        old = jnp.atleast_1d(jnp.asarray(old))
        return new.at[:k].set(old[:k].astype(new.dtype))

    # joining chips adopt the fleet's step counter (a synchronous fleet
    # steps together; per-step RNG derives from plane.step)
    step = jnp.full((n_new,),
                    jnp.max(jnp.atleast_1d(plane.step)), jnp.int32)
    return _dc.replace(
        fresh,
        v_core=take(plane.v_core, fresh.v_core),
        v_hbm=take(plane.v_hbm, fresh.v_hbm),
        v_io=take(plane.v_io, fresh.v_io),
        comp_level=take(plane.comp_level, fresh.comp_level),
        energy_j=take(plane.energy_j, fresh.energy_j),
        step=take(plane.step, step),
    )


def remap_sor(sor_state, target):
    """Explicitly remap a restored `sor.SorState` onto a `target` fleet
    (a FleetSpec or an int chip count) of a possibly different size — the
    learned-region counterpart of `remap_plane`: chips 0..min(n_old,
    n_new)-1 keep their learned telemetry window and fitted frontier;
    joining chips start empty, which is ZERO confidence — the cold-start
    pin — so a joiner runs at static envelopes until its own telemetry
    accrues. Returns the state unchanged when the sizes already match."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    n_new = target.n_chips if hasattr(target, "n_chips") else int(target)
    hist = sor_state.history
    chip = hist.chip_shape
    if not chip:
        raise ValueError("remap_sor needs a fleet-shaped ([n_chips]) "
                         "SorState; a scalar learner has nothing to remap")
    n_old = chip[0]
    if n_old == n_new:
        return sor_state
    k = min(n_old, n_new)

    def take(a):
        a = jnp.asarray(a)
        z = jnp.zeros(a.shape[:-1] + (n_new,), a.dtype)
        return z.at[..., :k].set(a[..., :k])

    return _dc.replace(
        sor_state,
        history=_dc.replace(
            hist, v=take(hist.v), obs=take(hist.obs),
            age_s=take(hist.age_s), polled=take(hist.polled),
            valid=take(hist.valid)),
        estimate=jax.tree_util.tree_map(take, sor_state.estimate))


def _path_key(k) -> str:
    """One path entry -> stable string: DictKey.key, GetAttrKey.name
    (registered dataclasses like PowerPlaneState), SequenceKey.idx. Falling
    through to None would collapse distinct leaves onto one npz entry."""
    for attr in ("key", "name", "idx"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def _flatten(tree) -> dict[str, np.ndarray]:
    flat = {}

    def go(path, leaf):
        flat["/".join(_path_key(k) for k in path)] = np.asarray(
            jax.device_get(leaf))

    jax.tree_util.tree_map_with_path(go, tree)
    return flat


def _unflatten_into(tree_like, flat: dict[str, np.ndarray]):
    def go(path, leaf):
        return flat["/".join(_path_key(k) for k in path)]

    return jax.tree_util.tree_map_with_path(go, tree_like)


@dataclasses.dataclass
class CheckpointManager:
    directory: str
    keep: int = 3
    async_save: bool = False
    _thread: threading.Thread | None = None   # the directory appears on save

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state: dict[str, Any],
             fleet: FleetSpec | None = None) -> str:
        """state: dict of pytrees, e.g. {'params': ..., 'opt': ..., 'plane': ...}.
        `fleet` additionally records the FleetSpec (seed + per-chip nominal
        arrays) the plane was seeded from, so an elastic restart onto a
        different fleet size can remap per-chip state explicitly."""
        self.wait()
        path = os.path.join(self.directory, f"step_{step:08d}")

        host = {name: _flatten(tree) for name, tree in state.items()}
        bf16_mask = {name: {k: str(v.dtype) for k, v in flat.items()}
                     for name, flat in host.items()}
        # learned-region groups (sor.SorState) record their full rail
        # layout — names AND observable keys/bounds — so a restore under a
        # different SorConfig.rails cannot silently misassign one rail's
        # learned frontier to another, or relabel a frontier cut at one
        # bound as an envelope for a different one
        sor_rails = {name: {"rails": [dataclasses.asdict(s)
                                      for s in tree.history.rails],
                            "capacity": int(tree.history.capacity)}
                     for name, tree in state.items()
                     if hasattr(getattr(tree, "history", None), "rails")}
        fleet_arrays = ({f: np.asarray(getattr(fleet, f))
                         for f in _FLEET_FIELDS} if fleet is not None else None)
        fleet_meta = ({"n_chips": fleet.n_chips, "seed": fleet.seed,
                       "base": dataclasses.asdict(fleet.base)}
                      if fleet is not None else None)

        def write():
            os.makedirs(path, exist_ok=True)
            arrays = {}
            manifest = {"step": step, "groups": {}, "time": time.time()}
            if sor_rails:
                manifest["sor_rails"] = sor_rails
            if fleet_meta is not None:
                manifest["fleet"] = fleet_meta
                for f, v in fleet_arrays.items():
                    arrays[f"fleet_spec::{f}"] = v
            for name, flat in host.items():
                manifest["groups"][name] = {
                    k: {"shape": list(v.shape), "dtype": bf16_mask[name][k]}
                    for k, v in flat.items()}
                for k, v in flat.items():
                    # npz has no bf16: store as uint16 view, dtype in manifest
                    if v.dtype == jax.numpy.bfloat16:
                        v = v.view(np.uint16)
                    arrays[f"{name}::{k}"] = v
            np.savez(os.path.join(path, "arrays.npz"), **arrays)
            with open(os.path.join(path, "manifest.msgpack"), "wb") as f:
                f.write(msgpack.packb(manifest))
            with open(os.path.join(path, ".complete"), "w") as f:
                f.write("ok")
            self._gc()

        if self.async_save:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        else:
            write()
        return path

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = self.list_steps()
        for s in steps[:-self.keep]:
            p = os.path.join(self.directory, f"step_{s:08d}")
            for fn in os.listdir(p):
                os.unlink(os.path.join(p, fn))
            os.rmdir(p)

    # -- restore --------------------------------------------------------------
    def list_steps(self) -> list[int]:
        out = []
        if not os.path.isdir(self.directory):
            return out
        for d in os.listdir(self.directory):
            if d.startswith("step_") and os.path.exists(
                    os.path.join(self.directory, d, ".complete")):
                out.append(int(d.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore_fleet(self, step: int | None = None) -> FleetSpec | None:
        """The FleetSpec a checkpoint was written under (None for scalar /
        pre-fleet checkpoints): seed + the exact per-chip nominal arrays, so
        a restart can compare it to its own fleet and `remap_plane`
        explicitly when the sizes differ."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        path = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(path, "manifest.msgpack"), "rb") as f:
            manifest = msgpack.unpackb(f.read())
        meta = manifest.get("fleet")
        if meta is None:
            return None
        from repro.core.hwspec import V5E, ChipSpec
        base = (ChipSpec(**meta["base"]) if meta.get("base") else V5E)
        with np.load(os.path.join(path, "arrays.npz")) as z:
            arrs = {f: z[f"fleet_spec::{f}"] for f in _FLEET_FIELDS}
        return FleetSpec(base=base, seed=int(meta["seed"]), **arrs)

    def restore(self, state_like: dict[str, Any], step: int | None = None,
                shardings: dict[str, Any] | None = None,
                optional: tuple = ()) -> tuple[int, dict]:
        """Restore into the structure of `state_like`. If `shardings` maps
        group name -> NamedSharding pytree, leaves are device_put sharded
        (elastic restore onto a different mesh). A group the checkpoint
        never recorded raises KeyError — unless named in `optional`, in
        which case it is skipped (absent from the returned dict): that is
        how a SOR-enabled trainer restores a pre-SOR checkpoint and keeps
        its in-memory cold start, without a missing REQUIRED group (renamed
        key, truncated manifest) silently restarting from fresh state."""
        import jax.numpy as jnp
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        path = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(path, "manifest.msgpack"), "rb") as f:
            manifest = msgpack.unpackb(f.read())
        with np.load(os.path.join(path, "arrays.npz")) as z:
            out = {}
            for name, tree in state_like.items():
                if name not in manifest["groups"]:
                    if name in optional:
                        continue
                    raise KeyError(
                        f"checkpoint step_{step:08d} has no state group "
                        f"{name!r} (has {sorted(manifest['groups'])}); "
                        f"pass optional=({name!r},) if the caller can "
                        f"genuinely proceed without it")
                saved = manifest.get("sor_rails", {}).get(name)
                if saved is not None:
                    hist = getattr(tree, "history", None)
                    want = {"rails": [dataclasses.asdict(s) for s in
                                      getattr(hist, "rails", ())],
                            "capacity": int(getattr(hist, "capacity", 0))}
                    if saved != want:
                        # substituting the arrays would index one rail's
                        # learned frontier as another's, relabel a frontier
                        # cut at a different bound, or hand a window of the
                        # wrong depth to the ring arithmetic — refuse loudly
                        raise ValueError(
                            f"checkpoint group {name!r} was learned under "
                            f"rails/capacity {saved} but this run's "
                            f"SorConfig declares {want}; restore with the "
                            f"config the state was learned under (or drop "
                            f"the group)")
                flat = {}
                for k, meta in manifest["groups"][name].items():
                    v = z[f"{name}::{k}"]
                    if meta["dtype"] == "bfloat16":
                        v = v.view(jnp.bfloat16)
                    flat[k] = v
                restored = _unflatten_into(tree, flat)
                if shardings and name in shardings:
                    restored = jax.tree_util.tree_map(
                        lambda a, s: jax.device_put(jnp.asarray(a), s),
                        restored, shardings[name])
                else:
                    restored = jax.tree_util.tree_map(jnp.asarray, restored)
                out[name] = restored
        return manifest["step"], out
