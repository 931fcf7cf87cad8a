"""Benchmark harness (deliverable d): one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV and writes reports/bench_results.json.

``--only SUBSTR`` runs just the modules whose name contains SUBSTR.
``--json-out PATH`` additionally writes structured perf records, grouped by
each row's ``bench`` tag: the fleet-frontier learned-vs-static comparison
(rail-power saving %, per-rail floors, phase-split wall time) goes to PATH
itself — ``reports/BENCH_fleet_frontier.json`` by convention — and every
other tagged group (e.g. ``controller_overhead``'s fused-vs-unfused round)
to ``BENCH_<bench>.json`` next to it, so the bench trajectory accumulates
across PRs.
"""

from __future__ import annotations

import argparse
import datetime
import importlib
import json
import os
import subprocess
import sys
import time
import traceback

MODULES = [
    "benchmarks.transition_latency",    # Fig 7
    "benchmarks.measurement_interval",  # Table VI / Fig 8
    "benchmarks.settling_detection",    # Fig 9 / §V-D
    "benchmarks.controller_overhead",   # Tables VII-IX
    "benchmarks.ber_sweep",             # Fig 12
    "benchmarks.tx_rx_sensitivity",     # Fig 13 / Table XI
    "benchmarks.link_speed",            # Fig 14
    "benchmarks.latency_impact",        # Fig 15
    "benchmarks.power_reduction",       # Fig 16 / Table XII
    "benchmarks.ecollectives_frontier",  # beyond-paper (DESIGN.md §2.2)
    "benchmarks.fleet_frontier",        # beyond-paper: fleet size x policy
    # learned-vs-static safe-operating-region comparison (docs/sor.md):
    # per-chip recovered headroom below the shared static envelope
    "benchmarks.fleet_frontier:run_learned",
    # sharded-control-plane weak scaling (docs/fleet.md): learned µs/step
    # vs shard count, gated on the ratio to the single-device anchor
    # (runs on however many devices are visible; multi-device needs
    # XLA_FLAGS=--xla_force_host_platform_device_count=N at process start)
    "benchmarks.fleet_frontier:run_weak_scaling",
    # headroom-aware serving router vs round-robin (docs/serve.md): gated
    # on the roundrobin/headroom tokens-per-joule and headroom/roundrobin
    # p99 ratios
    "benchmarks.serve_router",
    # fused one-dispatch serve tick vs the per-tick host loop at fleet
    # scale (docs/serve.md "serving at fleet scale"): gated on the
    # loop/fused tick-rate ratio and the fused per-chip µs/tick scaling
    "benchmarks.serve_scale",
    # continuous batching vs one-request-per-slot, and in-flight migration
    # vs drain-pinned-only (docs/serve.md "continuous batching &
    # migration"): gated on the unbatched/batched tokens-per-joule,
    # batched/unbatched p99, and migrate/drain degraded-chip-ticks ratios
    "benchmarks.serve_batching",
    "benchmarks.roofline_table",        # deliverable (g)
]


def _git_commit() -> "str | None":
    """The commit the records were produced at (None outside a checkout
    or without git on PATH) — provenance for the cross-PR trajectory."""
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except (OSError, subprocess.SubprocessError):
        return None


def _append_trajectory(out_dir: str, stamp: dict,
                       by_bench: "dict[str, list]") -> str:
    """Append ONE cumulative row per --json-out run to
    `<out_dir>/BENCH_trajectory.jsonl`: the commit/time stamp plus each
    bench's gated within-run ratios (`check_bench_regression.gate_metrics`
    — the same numbers CI gates, so the trajectory is comparable across
    machines). The BENCH_*.json files are overwritten per run; this file
    only grows, which is what makes the cross-PR story tellable."""
    from benchmarks.check_bench_regression import gate_metrics
    row_out = {**stamp, "benches": {
        bench: {rec["name"]: gate_metrics(rec) for rec in records}
        for bench, records in by_bench.items()}}
    path = os.path.join(out_dir, "BENCH_trajectory.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(row_out, sort_keys=True) + "\n")
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only modules whose name contains SUBSTR")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="write the fleet_frontier structured perf record "
                         "(e.g. reports/BENCH_fleet_frontier.json)")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()

    modules = [m for m in MODULES if args.only is None or args.only in m]
    if not modules:
        sys.exit(f"no benchmark module matches {args.only!r}")
    all_rows = []
    failures = 0
    t0 = time.perf_counter()
    for name in modules:
        try:
            # "module" runs module.run(); "module:fn" runs module.fn()
            mod_name, _, fn_name = name.partition(":")
            mod = importlib.import_module(mod_name)
            rows = getattr(mod, fn_name or "run")()
            all_rows.extend(rows)
        except Exception:
            failures += 1
            traceback.print_exc()
            all_rows.append({"name": f"{name}.FAILED", "us_per_call": 0.0,
                             "derived": "see traceback"})
    wall_s = time.perf_counter() - t0
    print("\nname,us_per_call,derived")
    for r in all_rows:
        print(f"{r['name']},{r['us_per_call']},\"{r['derived']}\"")
    os.makedirs("reports", exist_ok=True)
    if args.only is None:
        # only a full run may overwrite the canonical results file — a
        # filtered run would clobber it with a subset
        with open("reports/bench_results.json", "w") as f:
            json.dump(all_rows, f, indent=1)
        print(f"\n{len(all_rows)} rows, {failures} module failures "
              f"-> reports/bench_results.json")
    else:
        print(f"\n{len(all_rows)} rows, {failures} module failures "
              f"(--only run: reports/bench_results.json left untouched)")

    if args.json_out:
        # structured perf records: every row that carries a machine-
        # readable `record` — the across-PR bench trajectory entries.
        # Rows are grouped by their `bench` tag (untagged rows are the
        # fleet_frontier learned-vs-static comparison, the original
        # emitter): the fleet_frontier group writes to --json-out itself
        # (e.g. reports/BENCH_fleet_frontier.json), every other group to
        # BENCH_<bench>.json next to it. Per-bench timing lives in each
        # record; run_wall_time_s covers whatever module set THIS
        # invocation ran (named, so runs with different --only selections
        # are not compared as if commensurate).
        by_bench: dict[str, list] = {}
        for r in all_rows:
            if "record" in r:
                by_bench.setdefault(r.get("bench", "fleet_frontier"),
                                    []).append(
                    {"name": r["name"], "us_per_call": r["us_per_call"],
                     **r["record"]})
        if by_bench:
            out_dir = os.path.dirname(args.json_out) or "."
            os.makedirs(out_dir, exist_ok=True)
            # commit/PR provenance: every record file carries the commit
            # it was produced at, and each --json-out run appends one row
            # to the cumulative cross-PR trajectory next to it
            stamp = {"commit": _git_commit(),
                     "generated_utc": datetime.datetime.now(
                         datetime.timezone.utc).isoformat(
                             timespec="seconds"),
                     "modules_run": modules}
            for bench, records in by_bench.items():
                path = (args.json_out if bench == "fleet_frontier"
                        else os.path.join(out_dir, f"BENCH_{bench}.json"))
                out = {"bench": bench, **stamp,
                       "run_wall_time_s": round(wall_s, 3),
                       "failures": failures, "records": records}
                with open(path, "w") as f:
                    json.dump(out, f, indent=1)
                print(f"perf record ({len(records)} entries) -> {path}")
            tpath = _append_trajectory(out_dir, stamp, by_bench)
            print(f"trajectory row appended -> {tpath}")
        else:
            # a selection that ran no record-emitting module must not
            # clobber the accumulated trajectory entry with an empty file
            print(f"no perf records produced; {args.json_out} left "
                  f"untouched")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
