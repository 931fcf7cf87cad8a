"""Production training launcher.

    python -m repro.launch.train --arch minicpm_2b --tiny --steps 100
    python -m repro.launch.train --arch zamba2_1p2b --n-layers 24 \
        --seq 512 --batch 4 --steps 4 --ckpt-every 0   (published widths,
                                                       one chip's depth)
    python -m repro.launch.train --arch grok1_314b --dry-run   (lower only)

Without `--tiny` the published CONFIG trains; `--n-layers` cuts its depth
(widths unchanged) to what one device holds. On a CPU use --tiny (reduced
same-family config) or --dry-run (AOT compile check via launch/dryrun.py,
which runs in a child process and is never for a chip)."""

from __future__ import annotations

import argparse
import dataclasses
import shutil

import jax

from repro import compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.core.control_plane import HostRailController
from repro.core.policy import POLICIES
from repro.core.power_plane import PowerPlaneState, StepProfile
from repro.data.pipeline import DataConfig, SyntheticLM, stub_frontend_inputs
from repro.models import registry
from repro.optim import adamw
from repro.optim.schedule import wsd
from repro.train.step import StepConfig, jit_train_step, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--dry-run", action="store_true",
                    help="AOT lower+compile on the production mesh instead")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="keep this many of the config's layers, widths "
                         "unchanged (0 = all)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--policy", choices=list(POLICIES), default="phase-aware")
    ap.add_argument("--control-path", choices=("in-graph", "host"),
                    default="in-graph",
                    help="in-graph = HW-path analogue (policy compiled into "
                         "the step); host = SW-path analogue (policy between "
                         "steps, actuated through simulated PMBus)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="steps between checkpoints (default max(10, "
                         "steps/5); 0 = never)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the data")
    return ap.parse_args(argv)


def build_trainer(args):
    """The configured model with random weights from `--seed`, its jitted
    train step and a `Trainer` over seeded synthetic data. Returns
    (cfg, trainer, n_params, reduced) — `reduced` names the depth cut, or
    is None when the config runs whole."""
    cfg = get_config(args.arch, tiny=args.tiny)
    reduced = None
    if args.n_layers and args.n_layers != cfg.n_layers:
        reduced = f"n_layers {args.n_layers} of {cfg.n_layers}"
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    api = registry.build(cfg, remat="none" if args.tiny else "full")
    params = api.init(jax.random.PRNGKey(args.seed))
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))

    opt_cfg = adamw.AdamWConfig()
    opt = adamw.init_state(params, opt_cfg)
    # the step reads error-feedback residuals only under grad_sync=ef_int8*,
    # which this launcher never sets: carry none (f32 zeros the size of the
    # model would cost 4.4 GiB at zamba2_1p2b's full depth)
    plane, ef = PowerPlaneState.nominal(), None
    tokens = args.batch * args.seq
    profile = StepProfile(6.0 * n * tokens, 14.0 * n, 4.0 * n, 4.0 * n)
    # warm up over a tenth of the run, at most 10 steps
    sched = lambda s: wsd(s, peak_lr=3e-4,
                          warmup_steps=min(10, args.steps // 10),
                          stable_steps=int(args.steps * 0.7),
                          decay_steps=int(args.steps * 0.2))
    policy = POLICIES[args.policy]
    in_graph = args.control_path == "in-graph"
    step = jit_train_step(make_train_step(
        lambda p, b: api.loss_fn(p, b), opt_cfg, sched, profile,
        StepConfig(policy=policy if in_graph else None)))

    class _Data(SyntheticLM):
        def jax_batch(self, s, extra=None):
            return super().jax_batch(s, stub_frontend_inputs(
                cfg, cfg.family, args.batch))

    data = _Data(DataConfig(cfg.vocab_size, args.seq, args.batch,
                            seed=args.seed))
    ckpt_every = (max(10, args.steps // 5) if args.ckpt_every is None
                  else args.ckpt_every)
    if ckpt_every and not args.resume:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    controller = None if in_graph else HostRailController(policy)
    trainer = Trainer(step, data, TrainerConfig(
        total_steps=args.steps, ckpt_every=ckpt_every,
        ckpt_dir=args.ckpt_dir, controller=controller),
        {"params": params, "opt": opt, "plane": plane, "ef": ef})
    return cfg, trainer, n, reduced


def main(argv=None):
    args = parse_args(argv)
    if args.dry_run:
        import subprocess
        import sys
        raise SystemExit(subprocess.call(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", args.arch,
             "--shape", "train_4k", "--mesh", "both"]))

    compile_cache.enable()
    cfg, trainer, n, reduced = build_trainer(args)
    if reduced:
        print(f"reduced: {reduced}")
    print(f"{cfg.name}: {n/1e6:.1f}M params (tiny={args.tiny})")
    if args.resume and trainer.maybe_restore():
        print(f"resumed from step {trainer.start_step}")
    log = trainer.run()
    rec = list(log.records)
    print(f"loss {rec[0].loss:.4f} -> {rec[-1].loss:.4f}; "
          f"summary: {trainer.summary()}")


if __name__ == "__main__":
    main()
