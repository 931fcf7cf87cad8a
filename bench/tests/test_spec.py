"""Cells, configurations, traffic, limits and metric readers are found by
the names in BENCHMARK.json; the harness refuses what it cannot run."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = spec.benchmark(ROOT)


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_by_name(w):
    c = spec.cell(BENCH, w["name"], ROOT)
    assert c["traffic"]["driver"] in ("serve", "train")
    assert c["config"]["name"] == w["config"]
    e2e = [m["name"] for m in c["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2, e2e
    assert c["per_layer"], w["name"]
    for m in c["per_layer"]:
        assert m["moves"] in e2e, (m["name"], m["moves"])
    assert c["limits"] and all(v > 0 for v in c["limits"].values())


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(m):
    assert callable(spec.reader(m["name"]))


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.cell(BENCH, "no_such.cell", ROOT)
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_configs_are_used_and_their_files_lie_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"]


def _run(cwd, *extra, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**os.environ, **(env or {})})


def test_refuses_off_a_tpu():
    r = _run(ROOT, env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 2, r.stderr
    assert "refused" in r.stderr and r.stdout == ""


def test_refuses_with_pallas_off():
    r = _run(ROOT, env={"REPRO_PALLAS": "interpret"})
    assert r.returncode == 2 and r.stdout == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 2 and r.stdout == ""
