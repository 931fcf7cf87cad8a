#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, through the normal entry points.

    python chip_smoke.py                # one chip: kernels, serve, train
    python chip_smoke.py --four-chips   # four chips: the sharded control
                                        # round and the mesh'd fleet step

One chip runs three phases, each of which passes or fails the run:

* kernels: every Pallas kernel of `kernels/ops.py` on the chip at the
  widths of the model that uses it, against `kernels/ref.py`;
* serve: minicpm_2b at its published CONFIG through `launch/serve.py`'s
  engine and `ServeEngine.generate`, with the fused learned control round
  after every token. The compiled prefill, decode and control-round
  programs must hold their kernels, the first decode step's logits must
  match a no-cache forward of the same sequence, and the rails must move;
* train: zamba2_1p2b at its published widths through `launch/train.py`'s
  trainer with the policy compiled into the step, depth cut to what one
  chip holds; the compiled step must hold its kernels and the loss must be
  finite and fall.

`--four-chips` runs only the sharded learned control round over a 4-device
`chips` mesh, against the unsharded round run on each device's slice of
the fleet, and the mesh'd fleet train step with `shard_control=True`
against the unsharded step.

Weights and data come from `--seed`. The script refuses to run (non-zero
exit, before any work) off a TPU and under `REPRO_PALLAS=off|interpret`.
Its last line of output is one JSON object naming the device. No number it
prints is a benchmark metric.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SERVE_ARGS = ["--arch", "minicpm_2b", "--control-path", "in-graph", "--sor",
              "--batch", "4", "--prompt-len", "512", "--max-new", "32"]
# 34 of zamba2_1p2b's 38 layers: the largest even depth (remat pairs) whose
# step keeps 1 GiB of the chip's 15.75 GiB free at 4 x 512 tokens
# (`compiled.memory_analysis().peak_memory_in_bytes`: 14.58 GiB at 34,
# 15.62 GiB at 38)
TRAIN_ARGS = ["--arch", "zamba2_1p2b", "--n-layers", "34", "--seq", "512",
              "--batch", "4", "--steps", "4", "--ckpt-every", "0",
              "--control-path", "in-graph"]
FLEET_CHIPS = 1024                # the four-chip fleet: 256 chips per device


def refuse(why: str) -> None:
    print(f"chip_smoke: refused: {why}", file=sys.stderr)
    sys.exit(2)


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in f32 on the host."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def kernels_in(jitted, *args) -> tuple[set, float, "object"]:
    """(Pallas kernel names, compile seconds, compiled) of `jitted` at
    `args`."""
    from repro.kernels.ops import pallas_kernels
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return (pallas_kernels(compiled.as_text()), time.perf_counter() - t0,
            compiled)


def check(ok, what) -> None:
    """Fail the phase unless `ok` (an `assert` that `python -O` keeps)."""
    if not ok:
        raise AssertionError(what)


def require_kernels(program: str, found: set, want: set) -> None:
    print(f"  {program}: tpu_custom_call kernels {sorted(found)}")
    check(want <= found, f"{program} lacks kernels {sorted(want - found)}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_kernels(seed: int) -> None:
    """Each kernel against its reference at the widths of its model."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops, ref
    from repro.models.lm import mamba_spec

    key = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    bf16, f32 = jnp.bfloat16, jnp.float32
    normal = lambda shape, dtype=f32: jax.random.normal(next(key), shape,
                                                        dtype)
    exact = jax.default_matmul_precision("highest")
    checks = []           # (name, error, tolerance)

    # attention at minicpm_2b: 48 padded query heads of 64, 512 tokens
    cfg = get_config("minicpm_2b")
    plan = cfg.head_plan()
    hq, hkv, group, dh = (plan.n_q_pad, plan.n_kv_pad, plan.group,
                          cfg.head_dim_)
    T = 512
    q, k, v, g = (normal((1, T, h, dh), bf16) for h in (hq, hkv, hkv, hq))

    def attn_loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(f32) * g)

    kern = lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                               group=group)
    oracle = lambda q, k, v: ref.mha_reference(
        q.astype(f32), k.astype(f32), v.astype(f32), causal=True,
        group=group)
    grads = lambda fn: jax.jit(jax.grad(attn_loss(fn), argnums=(0, 1, 2)))
    o1, d1 = kern(q, k, v), grads(kern)(q, k, v)
    with exact:
        o2, d2 = jax.jit(oracle)(q, k, v), grads(oracle)(q, k, v)
    checks.append(("flash_attention fwd", rel_err(o1, o2), 2e-2))
    for name, a, b in zip(("dq", "dk", "dv"), d1, d2):
        checks.append((f"flash_attention bwd {name}", rel_err(a, b), 3e-2))

    # decode attention against a 640-slot minicpm_2b cache, ragged lengths
    S = 640
    qd = normal((4, 1, hq, dh), bf16)
    kc, vc = normal((4, S, hkv, dh), bf16), normal((4, S, hkv, dh), bf16)
    lengths = jnp.array([S, 513, 300, 1], jnp.int32)
    o1 = ops.decode_attention(qd, kc, vc, lengths, group=group)
    with exact:
        o2 = jax.jit(lambda *a: ref.mha_reference(
            *(x.astype(f32) for x in a[:3]), causal=False, group=group,
            lengths=a[3]))(qd, kc, vc, lengths)
    checks.append(("decode_attention", rel_err(o1, o2), 2e-2))

    # Mamba2 SSD scan at zamba2_1p2b: 64 heads of 64, state 64, 512 tokens
    spec = mamba_spec(get_config("zamba2_1p2b"))
    H, P, G, N = spec.n_heads, spec.head_dim, spec.n_groups, spec.d_state
    x = normal((1, T, H, P), bf16)
    dt = jax.nn.softplus(normal((1, T, H)) - 2.0)
    A = -jnp.exp(normal((H,)))
    Bm, Cm = normal((1, T, G, N), bf16), normal((1, T, G, N), bf16)
    D = normal((H,))
    y1, s1 = ops.mamba2_scan(x, dt, A, Bm, Cm, D)
    with exact:
        y2, s2 = jax.jit(ref.mamba2_scan_reference)(x, dt, A, Bm, Cm, D)
    checks.append(("mamba2_ssd y", rel_err(y1, y2), 3e-2))
    checks.append(("mamba2_ssd state", rel_err(s1, s2), 3e-2))

    # RWKV6 scan at rwkv6_7b: 64 heads of 64, 256 tokens
    rcfg = get_config("rwkv6_7b")
    H, dh6 = rcfg.n_heads, rcfg.d_model // rcfg.n_heads
    r, kk, vv = (normal((1, 256, H, dh6), bf16) for _ in range(3))
    w = -jnp.exp(normal((1, 256, H, dh6)) - 1.0)
    u = normal((H, dh6))
    y1, s1 = ops.rwkv6_scan(r, kk, vv, w, u)
    with exact:
        y2, s2 = jax.jit(ref.rwkv6_scan_reference)(r, kk, vv, w, u)
    checks.append(("rwkv6_scan y", rel_err(y1, y2), 3e-2))
    checks.append(("rwkv6_scan state", rel_err(s1, s2), 3e-2))

    # int8 codec over a minicpm_2b MLP weight
    wq = normal((cfg.d_model, cfg.d_ff))
    (c1, sc1), (c2, sc2) = ops.quantize_int8(wq), jax.jit(
        ref.quantize_int8_reference)(wq)
    code_err = float(jnp.max(jnp.abs(c1.astype(f32) - c2.astype(f32))))
    checks.append(("quantize_int8 codes (abs)", code_err, 1.0))
    checks.append(("quantize_int8 scales", rel_err(sc1, sc2), 1e-6))

    # fleet telemetry and the SOR fit over 3 rails x 1024 chips
    fleet = jax.random.uniform(next(key), (FLEET_CHIPS, 5))
    for name, a, b in zip(("max", "min", "sum"), ops.fleet_reduce(fleet),
                          jax.jit(ref.fleet_reduce_reference)(fleet)):
        checks.append((f"fleet_reduce {name}", rel_err(a, b), 1e-5))
    lanes = 3 * FLEET_CHIPS
    vx = 0.60 + 0.35 * jax.random.uniform(next(key), (32, lanes))
    vy = -2.0 - 6.0 * (vx - 0.6) + 0.05 * normal((32, lanes))
    vw = jax.random.uniform(next(key), (32, lanes))
    bound = jnp.full((lanes,), jnp.log10(5e-3), f32)
    guard = jnp.full((lanes,), 0.01, f32)
    for i, (a, b) in enumerate(zip(ops.sor_accumulate(vx, vy, vw),
                                   jax.jit(ref.sor_accumulate_reference)(
                                       vx, vy, vw))):
        checks.append((f"sor_accumulate sum{i}", rel_err(a, b), 1e-5))
    fit_kw = dict(min_slope=0.5, min_spread_v=2e-3, conf_samples=8.0)
    fit_names = ("intercept", "slope", "v_frontier", "confidence", "n_eff",
                 "floor")
    for name, a, b in zip(fit_names,
                          ops.sor_fit(vx, vy, vw, bound, guard, **fit_kw),
                          jax.jit(lambda *a: ref.sor_fit_reference(
                              *a, **fit_kw))(vx, vy, vw, bound, guard)):
        checks.append((f"sor_fit {name}", rel_err(a, b), 1e-3))

    bad = []
    for name, err, tol in checks:
        ok = err <= tol
        print(f"  {name}: max error {err!r} (limit {tol})"
              f"{'' if ok else '  FAILED'}")
        if not ok:
            bad.append(name)
    if bad:
        raise AssertionError(f"kernels off their references: {bad}")


def phase_serve(seed: int) -> None:
    """minicpm_2b at its published CONFIG through the serve launcher's
    engine: prefill, cached decode and one fused control round a token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.power_plane import account_and_observe
    from repro.launch import serve
    from repro.models.lm import forward_logits

    args = serve.parse_args(SERVE_ARGS + ["--seed", str(seed)])
    t0 = time.perf_counter()
    cfg, engine, n = serve.build_engine(args)
    jax.block_until_ready(engine.params)
    print(f"  {cfg.name}: {n} params, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}; weights in "
          f"{time.perf_counter() - t0:.1f} s")
    prompts = serve.prompts_for(args, cfg)
    toks = jnp.asarray(prompts)
    B, Tp = prompts.shape

    # the programs generate() runs, compiled ahead of it
    pre_k, pre_s, _ = kernels_in(engine._prefill, engine.params, toks)
    _, cache_shape, _ = jax.eval_shape(engine._prefill, engine.params, toks)
    step_in = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
               "cur_index": jax.ShapeDtypeStruct((), jnp.int32)}
    dec_k, dec_s, _ = kernels_in(engine._decode, engine.params, cache_shape,
                                 step_in)
    print(f"  set-up: compile prefill {pre_s:.1f} s, decode {dec_s:.1f} s")
    require_kernels("prefill", pre_k, {"flash_attention_fwd"})
    require_kernels("decode", dec_k, {"decode_attention"})

    rails = ("v_core", "v_hbm", "v_io")
    v0 = {r: float(getattr(engine.plane, r)) for r in rails}
    t0 = time.perf_counter()
    out = engine.generate(prompts, max_new_tokens=args.max_new)
    gen_s = time.perf_counter() - t0
    v1 = {r: float(getattr(engine.plane, r)) for r in rails}
    print(f"  generate: {out.shape} tokens from a {B} x {Tp} prompt in "
          f"{gen_s:.1f} s wall (host clock, not a benchmark metric)")
    print(f"  rails before {v0} after {v1}")
    # the fused control round generate() ran after every token
    ctrl = engine.controller
    _, frame, _ = account_and_observe(engine.decode_profile, engine.plane)
    rnd_k, _, _ = kernels_in(ctrl._round_jit, engine.plane, frame,
                             engine._sor_state)
    require_kernels("control round", rnd_k, {"sor_fit"})
    check(out.shape == (B, args.max_new), out.shape)
    check(((out >= 0) & (out < cfg.vocab_size)).all(), "token off vocab")
    check(any(v1[r] != v0[r] for r in rails), "the policy moved no rail")

    # first decode step against a no-cache forward of the same sequence
    logits_p, cache, _ = engine._prefill(engine.params, toks)
    V = cfg.vocab_size
    t1 = jnp.argmax(logits_p[:, -1, :V], axis=-1).astype(jnp.int32)[:, None]
    logits_d, _ = engine._decode(engine.params, cache,
                                 {"tokens": t1, "cur_index": jnp.int32(Tp)})
    full = jax.jit(lambda p, t: forward_logits(p, t, cfg))(
        engine.params, jnp.concatenate([toks, t1], axis=1))
    dec, ref_last = logits_d[:, -1, :V], full[:, -1, :V]
    err = rel_err(dec, ref_last)
    agree = float(np.mean(np.asarray(jnp.argmax(dec, -1) ==
                                     jnp.argmax(ref_last, -1))))
    print(f"  decode-step logits vs no-cache forward: max error {err!r} of "
          f"max |logit| (limit 5e-2), top-1 agreement {agree}")
    check(np.array_equal(out[:, :1], np.asarray(t1)),
          "generate's first token is not the prefill's argmax")
    check(err <= 5e-2, f"decode logits off the no-cache forward: {err}")
    summ = engine.summary()
    print(f"  engine: prefill_tokens {summ.get('prefill_tokens')} "
          f"decode_tokens {summ.get('decode_tokens')}")


def phase_train(seed: int) -> None:
    """zamba2_1p2b through the train launcher's trainer, the policy in the
    step."""
    import jax
    import numpy as np
    from repro.launch import train

    args = train.parse_args(TRAIN_ARGS + ["--seed", str(seed)])
    cfg, trainer, n, reduced = train.build_trainer(args)
    print(f"  reduced: {reduced or 'none'}")
    print(f"  {cfg.name}: {n} params, d_model {cfg.d_model}, ssm_state "
          f"{cfg.ssm_state}, {args.batch} x {args.seq} tokens a step")
    st = trainer.state
    found, secs, compiled = kernels_in(
        trainer.train_step, st["params"], st["opt"], st["plane"], st["ef"],
        trainer.data.jax_batch(0))
    peak = compiled.memory_analysis().peak_memory_in_bytes / 2 ** 30
    print(f"  set-up: compile train step {secs:.1f} s, peak {peak:.2f} GiB")
    require_kernels("train step", found,
                    {"mamba2_ssd", "flash_attention_fwd",
                     "flash_attention_bwd_dq", "flash_attention_bwd_dkv"})
    del compiled
    t0 = time.perf_counter()
    log = trainer.run()
    wall = time.perf_counter() - t0
    losses = [float(r.loss) for r in log.records]
    print(f"  losses {losses} in {wall:.1f} s wall (host clock, not a "
          f"benchmark metric)")
    plane = trainer.state["plane"]
    print(f"  rails after: v_core {float(plane.v_core)} v_hbm "
          f"{float(plane.v_hbm)} v_io {float(plane.v_io)}")
    check(len(losses) == args.steps, losses)
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _fleet_world(seed: int):
    from repro.core import sor
    from repro.core.control_plane import InGraphRailController
    from repro.core.hwspec import FleetSpec
    from repro.core.policy import MultiRailClosedLoop

    cfg = sor.SorConfig(capacity=16, refresh_every=4, decay=0.96,
                        guard_v=0.004, max_extension_v=0.12,
                        ingest="frames", rails=sor.ALL_RAIL_OBSERVABLES)
    fs = FleetSpec.sample(FLEET_CHIPS, seed=seed)
    ctrl = InGraphRailController(MultiRailClosedLoop(), sor=cfg)
    return cfg, fs, ctrl


def _frame(plane, i: int, seed: int, sl=slice(None)):
    import jax
    import jax.numpy as jnp
    from repro.core.telemetry import as_frame
    k = jax.random.fold_in(jax.random.PRNGKey(seed), i)
    err = 1e-4 * (1.0 + jax.random.uniform(k, (FLEET_CHIPS,)))[sl]
    m = err.shape[0]
    return as_frame({"grad_error": err, "t_chip_s": jnp.full((m,), 1e-3),
                     "straggle_rate": jnp.full((m,), 1e-3),
                     "hbm_error_rate": jnp.full((m,), 1e-4)}, state=plane)


def _on_devices(tree, devices) -> None:
    """Every chip-axis leaf of `tree` is spread over exactly `devices`."""
    import jax
    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(tree):
        if getattr(leaf, "ndim", 0) and leaf.shape[-1] == FLEET_CHIPS:
            check(leaf.sharding.device_set == want, leaf.sharding)
            shards = leaf.addressable_shards
            check(len(shards) == len(want), len(shards))
            check(all(s.data.shape[-1] == FLEET_CHIPS // len(want)
                      for s in shards), [s.data.shape for s in shards])


def phase_sharded_round(seed: int, devices) -> None:
    """The sharded learned round against the unsharded round run on each
    device's slice of the fleet: bit-equal."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.control_plane import sharded_control_round
    from repro.core.power_plane import PowerPlaneState
    from repro.kernels import ops

    cfg, fs, ctrl = _fleet_world(seed)
    plane, ss = PowerPlaneState.from_fleet(fs), ctrl.init_sor(FLEET_CHIPS)
    mesh = Mesh(np.array(devices), ("chips",))
    rnd = jax.jit(sharded_control_round(ctrl, mesh))
    p1 = ops.shard_chip_tree(plane, mesh, FLEET_CHIPS)
    s1 = ops.shard_chip_tree(ss, mesh, FLEET_CHIPS)
    found, secs, _ = kernels_in(rnd, p1, _frame(p1, 0, seed), s1)
    print(f"  set-up: compile sharded round {secs:.1f} s")
    require_kernels("sharded round", found, {"sor_fit"})
    rounds = 8
    for i in range(rounds):
        p1, s1, conf_sum, conf_min = rnd(p1, _frame(p1, i, seed), s1)
    _on_devices((p1, s1), devices)
    print(f"  {FLEET_CHIPS} chips over {len(devices)} devices, {rounds} "
          f"rounds: confidence sum {float(conf_sum)!r} min "
          f"{float(conf_min)!r}; state on all {len(devices)} devices")

    k = FLEET_CHIPS // len(devices)
    slice_of = lambda tree, sl: jax.tree_util.tree_map(
        lambda a: a[..., sl] if np.ndim(a) and np.shape(a)[-1] ==
        FLEET_CHIPS else a, tree)
    rj = jax.jit(ctrl.control_round)
    parts = []
    for d, dev in enumerate(devices):
        sl = slice(d * k, (d + 1) * k)
        pb, sb = jax.device_put((slice_of(plane, sl), slice_of(ss, sl)), dev)
        for i in range(rounds):
            pb, sb, _, _ = rj(pb, _frame(pb, i, seed, sl), sb)
        parts.append((pb, sb))
    fields = {"plane.v_core": lambda p, s: p.v_core,
              "plane.v_hbm": lambda p, s: p.v_hbm,
              "plane.v_io": lambda p, s: p.v_io,
              "history.v": lambda p, s: s.history.v,
              "estimate.v_frontier": lambda p, s: s.estimate.v_frontier,
              "estimate.confidence": lambda p, s: s.estimate.confidence}
    unequal = []
    for name, get in fields.items():
        whole = np.asarray(get(p1, s1))
        pieces = np.concatenate([np.asarray(get(p, s)) for p, s in parts],
                                axis=-1)
        diff = float(np.max(np.abs(whole - pieces)))
        print(f"  sharded vs per-slice {name}: max |diff| {diff!r}")
        if not np.array_equal(whole, pieces):
            unequal.append(name)
    if unequal:
        raise AssertionError(f"sharded round differs from the per-slice "
                             f"rounds in {unequal}")


def phase_sharded_step(seed: int, devices) -> None:
    """The fleet train step with the SOR round shard-parallel on the mesh,
    against the same step unsharded on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import sor
    from repro.core.policy import MultiRailClosedLoop
    from repro.core.power_plane import StepProfile
    from repro.kernels import ops
    from repro.optim import adamw
    from repro.train.step import (FleetStepConfig, StepConfig,
                                  jit_train_step, make_fleet_train_step)
    from repro.train.trainer import initial_plane_and_ef

    cfg, fs, _ = _fleet_world(seed)
    mesh = Mesh(np.array(devices), ("chips",))
    opt_cfg = adamw.AdamWConfig(grad_clip_norm=1.0)
    batches = [jnp.full((8, 4), 0.1 * (i + 1), jnp.float32)
               for i in range(4)]

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2), {}

    def run(mesh_arg, shard_control):
        step = jit_train_step(make_fleet_train_step(
            loss_fn, opt_cfg, lambda s: 1e-3,
            StepProfile(2e12, 8e9, 4e9, 3e9),
            StepConfig(policy=MultiRailClosedLoop()),
            FleetStepConfig(spec=fs, hbm_error_base=1e-4,
                            straggler_prob=0.05, mesh=mesh_arg,
                            shard_control=shard_control, sor=cfg)),
            donate=False)
        p = {"w": jnp.ones((4,), jnp.float32)}
        opt = adamw.init_state(p, opt_cfg)
        plane, ef = initial_plane_and_ef(p, fleet=fs)
        ss = sor.init_state(cfg, fs.n_chips)
        if shard_control:
            plane = ops.shard_chip_tree(plane, mesh_arg, fs.n_chips)
            ss = ops.shard_chip_tree(ss, mesh_arg, fs.n_chips)
            found, secs, _ = kernels_in(step, p, opt, plane, ef, ss,
                                        batches[0])
            print(f"  set-up: compile mesh'd fleet step {secs:.1f} s")
            require_kernels("mesh'd fleet step", found, {"sor_fit"})
        for b in batches:
            p, opt, plane, ef, ss, metrics = step(p, opt, plane, ef, ss, b)
        return plane, ss, metrics

    plane_s, ss_s, m_s = run(mesh, True)
    _on_devices((plane_s, ss_s), devices)
    with jax.default_device(devices[0]):
        plane_u, ss_u, m_u = run(None, None)
    dv = float(np.max(np.abs(np.asarray(plane_s.v_io) -
                             np.asarray(plane_u.v_io))))
    dl = abs(float(m_s["loss"]) - float(m_u["loss"]))
    print(f"  mesh'd vs unsharded fleet step, {len(batches)} steps: max "
          f"|v_io diff| {dv!r} (limit 5e-4), |loss diff| {dl!r}; "
          f"sor_conf_mean {float(m_s['fleet/sor_conf_mean'])!r} vs "
          f"{float(m_u['fleet/sor_conf_mean'])!r}; state on all "
          f"{len(devices)} devices")
    check(dv <= 5e-4, dv)
    check(dl <= 1e-5 * max(abs(float(m_u["loss"])), 1.0), dl)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded control plane on 4 chips")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_PALLAS") in ("off", "interpret"):
        refuse(f"REPRO_PALLAS={os.environ['REPRO_PALLAS']} keeps the Pallas "
               f"kernels off the chip")
    if not (ROOT / "src" / "repro").is_dir():
        refuse(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    cache = compile_cache.enable()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        refuse(f"JAX found no TPU (platform {dev.platform!r})")
    from repro.kernels import ops
    if ops._pallas_mode() != "native":
        refuse(f"kernels would run in mode {ops._pallas_mode()!r}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        refuse(f"needs {need} chips, JAX found {len(devices)}")
    print(f"chip_smoke: {len(devices)} x {dev.device_kind} ({dev.platform}), "
          f"jax {jax.__version__}, seed {args.seed}, compile cache {cache}")

    if args.four_chips:
        phases = [("sharded control round",
                   lambda: phase_sharded_round(args.seed, devices[:4])),
                  ("mesh'd fleet train step",
                   lambda: phase_sharded_step(args.seed, devices[:4]))]
    else:
        phases = [("kernels", lambda: phase_kernels(args.seed)),
                  ("serve minicpm_2b", lambda: phase_serve(args.seed)),
                  ("train zamba2_1p2b", lambda: phase_train(args.seed))]
    failed = []
    for name, run in phases:
        print(f"phase {name}:", flush=True)
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        gc.collect()
        print(f"phase {name}: {'FAILED' if name in failed else 'ok'} in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
