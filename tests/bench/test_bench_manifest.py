"""The benchmark's manifest (BENCHMARK.json) and the files it names.

None of these tests touches a chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}


def _names():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[kind]:
            yield entry["name"]
    for w in MANIFEST["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in MANIFEST["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_unit_and_reader(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()


def test_names_are_unique():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[kind]]
        assert len(names) == len(set(names)), kind
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_cells_report_the_metric_they_move(metric):
    moved = {m["name"]: m for m in MANIFEST["end_to_end"]}[metric["moves"]]
    for cell in metric.get("workloads", list(CELLS)):
        assert cell in CELLS
        assert cell in moved.get("workloads", [cell]), (metric["name"], cell)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_agree(cell):
    w = CELLS[cell]
    work = json.loads((ROOT / "bench" / "workloads" / f"{cell}.json")
                      .read_text())
    assert (work["config"], work["traffic"]) == (w["config"], w["traffic"])
    config = {c["name"]: c for c in MANIFEST["configs"]}[w["config"]]
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert w["chips"] in (1, 4)
    reported = [m["name"] for m in MANIFEST["end_to_end"]
                if cell in m.get("workloads", [cell])]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(cell in m.get("workloads", [cell])
               for m in MANIFEST["per_layer"])


def test_bounds_and_run_seconds():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= MANIFEST["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpc10k.live",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    proc = _run(ROOT, {})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "correct" not in proc.stdout


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MANIFEST["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    # past the device check the harness imports the program from src/,
    # which such a directory does not hold
    proc = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path[:0] = ['src', 'bench']; "
         "import drive"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert _run(tmp_path, {}).returncode != 0
