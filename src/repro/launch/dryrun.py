import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run (deliverable e): lower + compile every assigned
(architecture x input shape) cell against the production meshes and record
memory/cost/collective analysis for the roofline (deliverable g).

The two lines above MUST stay first: jax locks the device count at first
init, and only the dry-run wants 512 placeholder CPU devices.

Usage:
    python -m repro.launch.dryrun --arch grok1_314b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out reports/
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ARCH_IDS, SHAPES, ModelConfig, ShapeConfig,
                                cells, get_config)
from repro.core.power_plane import StepProfile
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import registry
from repro.optim import adamw
from repro.optim.schedule import cosine
from repro.parallel import sharding as shd
from repro.train.step import StepConfig, make_train_step

# Per-arch microbatch counts for train_4k (activation-memory control; the
# constraint is microbatches <= global_batch / dp_size). §Perf iteration:
# FSDP all-gathers scale with the microbatch count, so these sit at the
# smallest value whose activations still fit 16 GB/chip.
MICROBATCHES = {
    "mistral_large_123b": 8, "grok1_314b": 2, "granite_20b": 4,
    "qwen2p5_14b": 4, "qwen3_moe_30b_a3b": 2, "rwkv6_7b": 4,
    "zamba2_1p2b": 2, "minicpm_2b": 2, "internvl2_2b": 2, "whisper_base": 1,
}
# configurations stated as one chip's share of a deployment, not sized for
# the production meshes: the dry run leaves them out
CHIP_SHARE_ONLY = ("moonlight_16b_a3b",)
# >=100B-param models use int8 optimizer moments (DESIGN.md §5)
INT8_OPT = {"mistral_large_123b", "grok1_314b"}

# §Perf iteration (sharding recipe per arch): sub-3B models pay more in TP
# activation all-reduces than they save, so they run wide-FSDP (params
# sharded over data x model, no TP; batch over data x model when divisible).
SHARDING_PROFILES = {
    "zamba2_1p2b": "fsdp_wide", "minicpm_2b": "fsdp_wide",
    "internvl2_2b": "fsdp_wide", "whisper_base": "fsdp_wide",
    # E=128 divides model=16 -> true expert parallelism (EP): experts over
    # 'model', full-width F per expert (F/16=48 was MXU-hostile)
    "qwen3_moe_30b_a3b": "moe_ep",
}


def _profile_settings(arch: str, mesh, shape: ShapeConfig):
    """Returns (rule_overrides, fsdp_axes, batch_axis_candidates, microbatches).

    fsdp_wide applies ONLY to training: inference batches (32/128/1) don't
    divide data x model, which would idle the model axis and turn FSDP
    gathers into per-token traffic (§Perf iteration 3: measured regression).
    Wide-FSDP training also forces microbatches=1 so each microbatch still
    divides the 256-way batch split (a 128-row microbatch on 256 devices
    compiles to 2x padded work — §Perf iteration 3a)."""
    base_dp = dp_axes(mesh)
    mb = MICROBATCHES.get(arch, 2) if shape.name == "train_4k" else 1
    if (SHARDING_PROFILES.get(arch) == "fsdp_wide"
            and shape.kind == "train"
            and shape.global_batch % _mesh_size(mesh, ("data", "model")) == 0):
        overrides = {"heads": None, "kv_heads": None, "ff": None,
                     "vocab": None, "ssm_heads": None, "experts": None}
        wide = tuple(mesh.axis_names)
        cands = [c for c in (wide, ("data", "model"))
                 if shape.global_batch % _mesh_size(mesh, c) == 0]
        return overrides, ("data", "model"), cands + [base_dp, None], 1
    if SHARDING_PROFILES.get(arch) == "moe_ep":
        return {"experts": "model", "ff": None}, "data", [base_dp, None], mb
    return {}, "data", [base_dp, None], mb


def analytic_profile(cfg: ModelConfig, shape: ShapeConfig, n_chips: int
                     ) -> StepProfile:
    """Coarse 6ND-based profile for the in-graph power plane (the precise
    numbers come back out of this dry-run; the plane only needs scale)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        flops = 6.0 * n_active * shape.tokens / n_chips
        grad_bytes = 2.0 * 2 * cfg.param_count() / n_chips
    elif shape.kind == "prefill":
        flops = 2.0 * n_active * shape.tokens / n_chips
        grad_bytes = 0.0
    else:
        flops = 2.0 * n_active * shape.global_batch / n_chips
        grad_bytes = 0.0
    hbm = 2.0 * cfg.param_count() / n_chips + 0.05 * flops / 100.0
    ici = grad_bytes
    return StepProfile(flops, hbm, ici, grad_bytes)


# ---------------------------------------------------------------------------
# Spec/shard construction per cell
# ---------------------------------------------------------------------------

def batch_pspecs(batch_tree, batch_axes):
    def one(path, leaf):
        keys = ".".join(str(getattr(k, "key", getattr(k, "idx", "")))
                        for k in path)
        if leaf.ndim == 0:
            return P()
        if "cross_kv" in keys:   # [L, B, S, H, Dh]: stacked layer dim leads
            return P(None, batch_axes, None, "model", None)
        return P(*((batch_axes,) + (None,) * (leaf.ndim - 1)))

    return jax.tree_util.tree_map_with_path(one, batch_tree)


def build_cell(arch: str, shape_name: str, mesh):
    """Returns (step_fn, abstract_args, in_shardings) for one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    api = registry.build(cfg)
    rule_overrides, fsdp_axes, batch_candidates, mb = _profile_settings(
        arch, mesh, shape)
    # first batch-axis candidate the global batch divides (long_500k: none)
    batch_axes = next(
        (c for c in batch_candidates
         if c is None or shape.global_batch % _mesh_size(mesh, c) == 0), None)

    moe_ep = SHARDING_PROFILES.get(arch) == "moe_ep"
    abstract_params = registry.abstract_params(cfg)
    pspecs = shd.param_pspecs(abstract_params, mesh, fsdp=fsdp_axes,
                              moe_ep=moe_ep)
    psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)

    overrides = {"batch": batch_axes, **rule_overrides}
    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig(
            state_dtype="int8" if arch in INT8_OPT else "float32")
        abstract_opt = jax.eval_shape(
            lambda p: adamw.init_state(p, opt_cfg), abstract_params)
        ospecs = shd.param_pspecs(abstract_opt, mesh, fsdp=fsdp_axes,
                                  moe_ep=moe_ep)
        osh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), ospecs)

        profile = analytic_profile(cfg, shape, mesh.devices.size)
        step_cfg = StepConfig(microbatches=mb, grad_sync="auto")
        sched = lambda s: cosine(s, peak_lr=3e-4, warmup_steps=2000,
                                 total_steps=100_000)
        base_step = make_train_step(
            lambda p, b: api.loss_fn(p, b), opt_cfg, sched, profile, step_cfg)

        from repro.core.power_plane import PowerPlaneState
        abstract_plane = jax.eval_shape(PowerPlaneState.nominal)
        plane_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), abstract_plane)

        batch = registry.input_specs(cfg, shape)
        bspecs = batch_pspecs(batch, batch_axes)
        bsh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), bspecs)

        def step(params, opt, plane, batch):
            with shd.mesh_context(mesh, overrides):
                return base_step(params, opt, plane, {}, batch)

        args = (abstract_params, abstract_opt, abstract_plane, batch)
        shardings = (psh, osh, plane_sh, bsh)
        donate = (0, 1, 2)
        return step, args, shardings, donate

    if shape.kind == "prefill":
        batch = registry.input_specs(cfg, shape)
        bspecs = batch_pspecs(batch, batch_axes)
        bsh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), bspecs)

        if cfg.family == "encdec":
            from repro.models import encdec

            def step(params, batch):
                with shd.mesh_context(mesh, overrides):
                    enc = encdec.encode(params, batch["frames"], cfg)
                    logits = encdec.decode_train(params, enc, batch["tokens"], cfg)
                    return logits[:, -1:], encdec.cross_kv(params, enc, cfg)
        else:
            def step(params, batch):
                with shd.mesh_context(mesh, overrides):
                    return api.prefill_fn(params, batch["tokens"], shape.seq_len)

        return step, (abstract_params, batch), (psh, bsh), ()

    # decode — §Perf note (blocked iteration, see EXPERIMENTS.md §Perf):
    # sharding the residual embed dim over 'data' would make FSDP weight
    # shards contract locally instead of moving expert weights to tokens,
    # but it collides with batch sharding on the same axis under automatic
    # SPMD (PartitionSpec('data', ..., 'data') is illegal). A manual
    # shard_map decode layer with a 2-D weight-stationary layout is the
    # production fix; left as documented future work.
    abstract_cache = registry.abstract_decode_cache(cfg, shape)
    cspecs = shd.cache_pspecs(abstract_cache, mesh, batch_axes=batch_axes)
    csh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), cspecs)
    batch = registry.input_specs(cfg, shape)
    bspecs = batch_pspecs(batch, batch_axes)
    bsh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), bspecs)

    def step(params, cache, batch):
        with shd.mesh_context(mesh, overrides):
            return api.decode_fn(params, cache, batch)

    return step, (abstract_params, abstract_cache, batch), (psh, csh, bsh), (1,)


def _mesh_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    return max(n, 1)


# ---------------------------------------------------------------------------
# Collective-byte extraction from post-SPMD HLO
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n]*",
    re.M)

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
                       r"\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def _group_size(line: str) -> int:
    m = _GROUP_IOTA_RE.search(line)       # iota format: [ngroups,group_size]
    if m:
        return max(1, int(m.group(2)))
    m = _GROUP_RE.search(line)            # explicit: {{0,1,...},{...}}
    if m:
        return max(1, m.group(1).count(",") + 1)
    return 2


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device wire bytes of every collective in post-optimization HLO.

    Output-shape bytes are converted to ring-algorithm wire traffic per
    participant (P = replica-group size):
      all-gather         out*(P-1)/P     (out = full gathered buffer)
      all-reduce         2*out*(P-1)/P   (reduce-scatter + all-gather phases)
      reduce-scatter     out*(P-1)       (out = the local shard)
      all-to-all         out*(P-1)/P
      collective-permute out
    """
    out: dict[str, float] = {}
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes_blob, kind, line = m.group(1), m.group(2), m.group(0)
        total = 0
        for sm in _SHAPE_RE.finditer(shapes_blob):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * _BYTES[dt]
        p = _group_size(line)
        factor = {"all-gather": (p - 1) / p,
                  "all-reduce": 2 * (p - 1) / p,
                  "reduce-scatter": float(p - 1),
                  "all-to-all": (p - 1) / p,
                  "collective-permute": 1.0}[kind]
        out[kind] = out.get(kind, 0) + total * factor
        counts[kind] = counts.get(kind, 0) + 1
    out["total"] = sum(v for k, v in out.items() if k != "total")
    out["op_counts"] = counts
    return out


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str,
             save_hlo_dir: str | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    step, args, shardings, donate = build_cell(arch, shape_name, mesh)
    jitted = jax.jit(step, in_shardings=shardings,
                     donate_argnums=donate or ())
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        # older jax returns one dict per device program
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    if save_hlo_dir:
        os.makedirs(save_hlo_dir, exist_ok=True)
        with open(os.path.join(save_hlo_dir,
                               f"{arch}.{shape_name}.{mesh_kind}.hlo"), "w") as f:
            f.write(hlo)

    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "utilization_ops": {k: v for k, v in cost.items()
                            if k.startswith("utilization")},
        "memory": mem_info,
        "collective_bytes": coll,
        "ok": True,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=[a for a in ARCH_IDS
                                       if a not in CHIP_SHARE_ONLY])
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="reports")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    if args.all:
        todo = [(a, s) for a, s, runnable in cells()
                if runnable and a not in CHIP_SHARE_ONLY]
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        todo = [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for mesh_kind in meshes:
        for arch, shape_name in todo:
            tag = f"{arch} x {shape_name} x {mesh_kind}"
            try:
                r = run_cell(arch, shape_name, mesh_kind,
                             save_hlo_dir=os.path.join(args.out, "hlo")
                             if args.save_hlo else None)
                print(f"[OK] {tag}: flops={r['flops']:.3e} "
                      f"coll={r['collective_bytes']['total']:.3e}B "
                      f"compile={r['compile_s']}s", flush=True)
            except Exception as e:
                traceback.print_exc()
                r = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                     "ok": False, "error": f"{type(e).__name__}: {e}"}
                print(f"[FAIL] {tag}: {r['error']}", flush=True)
            results.append(r)
            path = os.path.join(args.out, f"dryrun_{'_'.join(meshes)}.json")
            with open(path, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells passed -> {path}")
    if n_ok != len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
