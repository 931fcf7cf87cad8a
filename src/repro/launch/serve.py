"""Serving launcher: batched prefill+decode with the power plane.

    python -m repro.launch.serve --arch qwen2p5_14b --tiny --max-new 32
    python -m repro.launch.serve --arch minicpm_2b --control-path in-graph \
        --sor --prompt-len 512                    (published widths, one chip)

Without `--tiny` the published CONFIG runs.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.core.control_plane import HostRailController, InGraphRailController
from repro.core.hwspec import FleetSpec
from repro.core.policy import POLICIES, WorstChipGate
from repro.core.power_plane import StepProfile
from repro.core.sor import SorConfig
from repro.models import registry
from repro.serve.engine import ServeEngine

CACHE_ALIGN = 128   # decode-kernel tile: a cache of this multiple never pads


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--policy", choices=list(POLICIES), default="phase-aware")
    ap.add_argument("--control-path", choices=("in-graph", "host"),
                    default="in-graph")
    ap.add_argument("--fleet-chips", type=int, default=0,
                    help="serve on an [n_chips] fleet plane with per-chip "
                         "process variation (0 = scalar single-chip)")
    ap.add_argument("--fleet-seed", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--sor", action="store_true",
                    help="in-graph path: learn safe operating regions in "
                         "the fused control round run after every token "
                         "(core/sor.py)")
    ap.add_argument("--router", choices=("none", "headroom", "roundrobin"),
                    default="none",
                    help="route a seeded bursty traffic trace over the "
                         "fleet by per-rail voltage headroom (or the "
                         "round-robin baseline) instead of running "
                         "generate(); needs --fleet-chips")
    ap.add_argument("--trace-requests", type=int, default=48,
                    help="requests in the bursty trace (--router only)")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--tick-path", choices=("auto", "fused", "loop"),
                    default="auto",
                    help="serve tick device path (--router only): 'fused' "
                         "forces the one-dispatch jitted tick, 'loop' the "
                         "historical per-tick host loop, 'auto' picks fused "
                         "for in-graph controllers (docs/serve.md)")
    ap.add_argument("--fast-forward", action="store_true",
                    help="skip idle tick gaps (empty queue, no resident "
                         "work) by jumping simulated time to the next "
                         "arrival — fused tick path only")
    ap.add_argument("--batch-cap", type=int, default=0,
                    help="continuous batching: each chip decodes a "
                         "token-level batch over up to BATCH_CAP resident "
                         "lanes at the shared-roofline per-lane rate "
                         "(0 = historical full-rate-per-slot model; "
                         "--router only — the cap becomes the router's "
                         "lane capacity)")
    ap.add_argument("--migrate-after-ticks", type=int, default=0,
                    help="in-flight migration: evacuate a chip's resident "
                         "decode lanes after its pinned/over-bound flag "
                         "held this many consecutive ticks (0 = off; "
                         "needs --router headroom — round-robin has no "
                         "migration planner)")
    args = ap.parse_args(argv)
    if args.batch_cap < 0:
        ap.error(f"--batch-cap must be >= 0, got {args.batch_cap}")
    if args.migrate_after_ticks < 0:
        ap.error(f"--migrate-after-ticks must be >= 0, got "
                 f"{args.migrate_after_ticks}")
    if args.batch_cap and args.router == "none":
        ap.error("--batch-cap batches a router's lanes; pass --router "
                 "headroom (or roundrobin)")
    if args.migrate_after_ticks and args.router != "headroom":
        ap.error("--migrate-after-ticks needs the headroom router's "
                 "migration planner; pass --router headroom")
    if args.sor and args.control_path != "in-graph":
        ap.error("--sor learns in the in-graph control round; pass "
                 "--control-path in-graph")
    return args


def build_engine(args):
    """The configured model with random weights from `--seed`, behind a
    `ServeEngine`. Returns (cfg, engine, n_params)."""
    cfg = get_config(args.arch, tiny=args.tiny)
    if cfg.family == "encdec":
        raise SystemExit("whisper serving uses cross-attention prefill; see "
                         "tests/test_models_smoke.py::test_arch_decode_step_smoke")
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(args.seed))
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))

    policy = POLICIES[args.policy]
    fleet = (FleetSpec.sample(args.fleet_chips, seed=args.fleet_seed)
             if args.fleet_chips else None)
    if fleet is not None:
        # fleet serving: gate every chip's decision on the worst chip
        policy = WorstChipGate(policy)
    sor = SorConfig(ingest="frames") if args.sor else None
    controller = (InGraphRailController(policy, sor=sor)
                  if args.control_path == "in-graph"
                  else HostRailController(policy,
                                          n_chips=max(args.fleet_chips, 1)))
    router = None
    if args.router != "none":
        if fleet is None:
            raise SystemExit("--router places work across a fleet; pass "
                             "--fleet-chips N")
        from repro.serve.router import HeadroomRouter, RoundRobinRouter
        # the launcher world has no error telemetry, so every chip walks
        # to its policy floor and reads as pinned — a drain-pinned router
        # would (correctly) shed the whole trace. Keep pinned chips
        # eligible here; benchmarks/serve_router.py and the tests
        # exercise the drain semantics against a frontier-error world.
        # --batch-cap sets the lane capacity (lanes ARE the router's
        # slots); without it the historical --batch slot count stands
        lanes = args.batch_cap or args.batch
        router = (HeadroomRouter(capacity=lanes, drain_pinned=False)
                  if args.router == "headroom"
                  else RoundRobinRouter(capacity=lanes))
    max_len = -(-(args.prompt_len + args.max_new) // CACHE_ALIGN) * CACHE_ALIGN
    engine = ServeEngine(
        cfg, params, max_len=max_len,
        batch_size=args.batch,
        prefill_profile=StepProfile(2.0 * n * args.batch * args.prompt_len,
                                    2.0 * n, 0.0),
        decode_profile=StepProfile(2.0 * n * args.batch, 2.0 * n, 0.0),
        controller=controller, fleet=fleet, router=router,
        batch_cap=args.batch_cap or None)
    return cfg, engine, n


def prompts_for(args, cfg) -> np.ndarray:
    """Seeded random prompts, [batch, prompt_len] int32."""
    return np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    cfg, engine, n = build_engine(args)
    router = engine.router
    if router is not None:
        # routed serving: place a seeded bursty trace by per-rail headroom
        # (docs/serve.md) and report the per-request SLO ledger
        from repro.serve.traffic import bursty_trace
        trace = bursty_trace(args.trace_requests, seed=args.trace_seed)
        # a tiny model's roofline step is microseconds — pin a serving-scale
        # tick so the seconds-scale trace spans hundreds of ticks, not 1e6;
        # bound the run to the trace span plus drain slack so a saturated
        # fleet reports unplaced work instead of spinning 20k ticks
        tick_s = 0.02
        span = trace.requests[-1].t_arrival_s if trace.requests else 0.0
        fused = {"auto": None, "fused": True, "loop": False}[args.tick_path]
        ledger = engine.serve_trace(trace, tick_s=tick_s,
                                    max_ticks=int(span / tick_s) + 400,
                                    fused=fused,
                                    fast_forward=args.fast_forward,
                                    migrate_after_ticks=(
                                        args.migrate_after_ticks or None))
        print(f"{cfg.name} ({n/1e6:.1f}M): routed {len(trace)} requests "
              f"over {engine.n_chips} chips ({args.router})")
        print("trace:", engine.last_trace)
        print("slo:", ledger.summary())
        print("summary:", engine.summary())
        return
    out = engine.generate(prompts_for(args, cfg), max_new_tokens=args.max_new)
    print(f"{cfg.name} ({n/1e6:.1f}M): generated {out.shape} tokens")
    print("summary:", engine.summary())


if __name__ == "__main__":
    main()
