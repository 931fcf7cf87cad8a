#!/usr/bin/env python3
"""Readings from which a cell's limits are set, on the chip, in one process.

    python3 bench/calibrate.py --workload zamba2_1p2b.train \
        --seeds 1-12 --controls 3 --seconds 5

For each seed: set-up, a short window at the cell's own load, and the
numbers the run compares (the lower readings). For the first `--controls`
seeds also the control (the reference in fp8 put in the program's place)
and, for training, the fault of a step that takes half the batch (the
reference over half the rows put in the program's place): the upper
readings. One JSON line per reading; the harness's own runs never call
this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--readings", default=None,
                    help="which controls and faults to read, comma-separated "
                         "(default: fp8, and half_batch for training)")
    ap.add_argument("--leaves", action="store_true",
                    help="also print each reading's norms leaf by leaf")
    args = ap.parse_args(argv)
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    import importlib

    from bench import spec
    from bench.drivers.common import Clock
    from bench.run import device_info, enable_compile_cache
    cell = spec.cell(spec.benchmark(ROOT), args.workload, ROOT)
    enable_compile_cache()
    device_info(cell["workload"]["chips"])
    kind = cell["traffic"]["driver"]
    driver_cls = importlib.import_module(f"bench.drivers.{kind}").DRIVER
    controls = (args.readings.split(",") if args.readings else
                ["fp8"] + (["half_batch"] if kind == "train" else []))
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        d = driver_cls(cell, seed)
        d.setup()
        d.window(args.seconds, Clock())
        d.free()
        rows = [("program", d.check())]
        if i < args.controls:
            rows += [(c, d.check(c)) for c in controls]
        for what, numbers in rows:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": what, **numbers}), flush=True)
        if args.leaves:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": "leaves",
                              **getattr(d, "readings", {})}), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        del d
    return 0


if __name__ == "__main__":
    sys.exit(main())
