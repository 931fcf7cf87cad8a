#!/usr/bin/env python3
"""Runs one benchmark cell once on the chip.

    python3 bench/run.py --workload minicpm_2b.decode --seed 7 \
        --seconds 30 --trace 0

Loads the cell named in BENCHMARK.json (its configuration, traffic mix and
limits, each found by name under bench/), makes the weights and inputs from
the seed, warms up every shape the window uses (all of it `setup_s`),
measures for `--seconds`, then checks a sample of what the window produced
against a plain f32 reference. With `--trace 1` the window runs under the
profiler and the cell's per-layer metrics are read from the device trace;
otherwise the end-to-end metrics are reported.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, (with --trace 1) breakdown, and last the numbers
compared with their limits, which also close standard error. The run
refuses (exit 2, no result) off a TPU, on fewer chips than the cell asks
for, on a device kind bench/peaks.json does not know, under
REPRO_PALLAS=off|interpret, and without the program under src/.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def refuse(why: str) -> None:
    print(f"bench: refused: {why}", file=sys.stderr)
    sys.exit(2)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


def check_environment() -> None:
    if os.environ.get("REPRO_PALLAS") in ("off", "interpret"):
        refuse(f"REPRO_PALLAS={os.environ['REPRO_PALLAS']} keeps the Pallas "
               f"kernels off the chip")
    if not (ROOT / "src" / "repro").is_dir():
        refuse(f"no program under {ROOT / 'src'}")


def enable_compile_cache() -> str:
    """JAX's persistent cache at the one path the program also uses:
    $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_info(chips: int) -> tuple[dict, dict]:
    """({platform, kind, count}, peaks) of the chips JAX found; refuses
    what the cell cannot run on."""
    import jax
    from bench import spec
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        refuse(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        refuse(f"the cell needs {chips} chips, JAX found {len(devices)}")
    try:
        peaks = spec.peaks(dev.device_kind)
    except spec.SpecError as e:
        refuse(str(e))
    from repro.kernels import ops
    if ops._pallas_mode() != "native":
        refuse(f"kernels would run in mode {ops._pallas_mode()!r}")
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devices)}, peaks)


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def traced_window(driver, seconds: float, clock, peaks: dict, cell: dict):
    """Runs the window under the profiler; returns (elapsed, per-layer
    metrics, device additions, breakdown)."""
    from bench import spec, trace
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with trace.capture(tmp):
            elapsed = driver.window(seconds, clock)
        tr = trace.load(trace.find(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    lo, hi = trace.window(tr)
    busy = trace.busy_ns(tr, lo, hi)
    ctx = {"trace": tr, "lo": lo, "hi": hi, "busy_ns": busy,
           "window_ns": hi - lo, "work": driver.trace_work(), "peaks": peaks}
    metrics = {}
    for m in cell["per_layer"]:
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9}
    breakdown = {"device_ops": trace.device_ops(tr, lo, hi),
                 "idle_gaps": trace.idle_gaps(tr, lo, hi)}
    return elapsed, metrics, extra, breakdown


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, clock,
             device: dict, peaks: dict) -> dict:
    """Set-up, the window and the check of one run; returns the result
    line's object. Every run of the harness comes through here."""
    import importlib
    kind = cell["traffic"]["driver"]
    driver = importlib.import_module(f"bench.drivers.{kind}").DRIVER(
        cell, seed)
    driver.setup()
    setup_s = clock.now()
    out = {}
    if traced:
        seconds = min(seconds, cell["traffic"]["trace_seconds"])
        elapsed, metrics, extra, breakdown = traced_window(
            driver, seconds, clock, peaks, cell)
        device = {**device, **extra}
        out["breakdown"] = breakdown
    else:
        elapsed = driver.window(seconds, clock)
        e2e = {"setup_s": setup_s, **driver.end_to_end(elapsed)}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    times = driver.durations()
    print(f"bench: window of {len(times)} calls: first {times[0]:.4f} s, "
          f"median {statistics.median(times):.4f} s, longest "
          f"{max(times):.4f} s, elapsed {elapsed:.4f} s", file=sys.stderr)
    device = {**device, "memory_peak_bytes":
              memory_peak_bytes(cell["workload"]["chips"])}
    attempted, failed = driver.attempted(), driver.failed()
    driver.free()
    t_check = clock.now()
    numbers = driver.check()
    print(f"bench: set-up {setup_s:.4f} s, reference check "
          f"{clock.now() - t_check:.4f} s", file=sys.stderr)
    check = {k: {"value": numbers[k], "limit": lim}
             for k, lim in cell["limits"].items()}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in check.values())
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **out, "check": check}


def main(argv=None) -> int:
    args = parse_args(argv)
    check_environment()
    # the checkout's root, not bench/, so bench's modules keep their package
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    try:
        bench = spec.benchmark(ROOT)
        cell = spec.cell(bench, args.workload, ROOT)
    except (OSError, KeyError, spec.SpecError) as e:
        refuse(str(e))
    enable_compile_cache()
    device, peaks = device_info(cell["workload"]["chips"])
    from bench.drivers.common import Clock
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      Clock(T_START), device, peaks)
    for k, c in result["check"].items():
        print(f"check: {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stderr.flush()
    # the result is out: skip the interpreter's and the TPU runtime's
    # teardown, which kept decode runs alive for about two minutes more
    os._exit(rc)
