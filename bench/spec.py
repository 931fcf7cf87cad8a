"""Finds a cell's configuration, traffic mix, limits and metric readers by
the names in `BENCHMARK.json`, so that a cell, a configuration or a metric
is added with files and entries alone."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class SpecError(ValueError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The cell `name` with its configuration, traffic, limits and the
    metrics it reports: {"workload", "config", "traffic", "limits",
    "end_to_end", "per_layer"}."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names no known config "
                        f"{w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = load_json(HERE / "limits" / f"{name}.json")

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {"workload": w, "config": config, "traffic": traffic,
            "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def reader(metric_name: str):
    """The `read(ctx)` function of `metrics/<metric_name>.py`."""
    path = HERE / "metrics" / f"{metric_name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path.relative_to(ROOT)} for per-layer "
                        f"metric {metric_name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
