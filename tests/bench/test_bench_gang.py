"""The GPU-fleet configuration, helios_saturn_2k, and its cell,
saturn2k.gang_stream20: its traffic model (bench/traffic/helios.py), its
parts as the harness finds them, its entries in BENCHMARK.json, and a
small copy of its cell run through the harness on the CPU.  None of these
tests touches a chip."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import drive  # noqa: E402
import run  # noqa: E402
from reference import gang_ref  # noqa: E402
from traffic import generator, helios  # noqa: E402

CELL = "saturn2k.gang_stream20"
MANIFEST = run.load_manifest()
_, CONFIG, WORK = run.cell_spec(MANIFEST, CELL)
SEEDS = (3, 2**31 + 977)
#: the per-layer metrics that read the cell
LAYERS = {"setup.compile_s", "scan.ms_per_tick.gang",
          "evict.victims_per_tick.gang"}


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_columns(seed):
    a = helios.generate(CONFIG, WORK, seed, 300)
    b = helios.generate(CONFIG, WORK, seed, 300)
    c = helios.generate(CONFIG, WORK, seed + 1, 300)
    assert set(a) == set(generator.COLUMNS)
    for k in generator.COLUMNS:
        assert np.array_equal(a[k], b[k]), k
        # another seed reorders the same jobs
        assert np.array_equal(np.sort(a[k]), np.sort(c[k])), k
    assert not np.array_equal(a["cpus"][:200], c["cpus"][:200])
    assert (np.diff(a["submit"]) >= 0).all() and a["submit"].max() < 300
    standing = int(round(WORK["standing_fraction"] * CONFIG["capacity"]))
    assert (a["submit"][:standing] == 0).all()


def test_sizes_are_powers_of_two_with_the_fitted_mix():
    cols = helios.draw_jobs(CONFIG, 200_000, generator.rng_for(5))
    gpus = cols["cpus"]
    assert ((gpus & (gpus - 1)) == 0).all()
    mix = CONFIG["jobs"]["gpus"]
    for g, p in mix.items():
        assert abs((gpus == int(g)).mean() - p) < 0.005, g
    # jobs of 8 GPUs or more hold most of the GPU time
    held = gpus * cols["work"]
    assert held[gpus >= 8].sum() > 0.5 * held.sum()
    assert cols["work"].min() >= 1
    assert cols["work"].max() <= CONFIG["jobs"]["runtime_max_ticks"]


def test_tenant_mix_follows_the_shares():
    cols = helios.draw_jobs(CONFIG, 200_000, generator.rng_for(6))
    share = np.bincount(cols["user"], minlength=CONFIG["tenants"])
    want = np.asarray(CONFIG["shares"]) / 100.0
    assert np.abs(share / share.sum() - want).max() < 0.005


def test_arrivals_offer_the_stated_load():
    horizon = 200_000
    ticks = helios.arrival_ticks(CONFIG, horizon, generator.rng_for(7))
    rate = helios.arrival_rate(CONFIG)
    assert abs(ticks.size / (rate * horizon) - 1) < 0.01
    offered = rate * helios.mean_gpu_ticks(CONFIG["jobs"])
    assert offered == pytest.approx(
        CONFIG["jobs"]["offered_load"] * CONFIG["cpu_total"])
    cols = helios.draw_jobs(CONFIG, 400_000, generator.rng_for(8))
    drawn = (cols["cpus"] * cols["work"]).mean()
    # the ceiling adds under a tick a job
    assert 0.97 < drawn / helios.mean_gpu_ticks(CONFIG["jobs"]) < 1.03


def test_running_set_fits_the_nodes():
    run_set = helios.draw_jobs(CONFIG, 4 * CONFIG["cpu_total"],
                               generator.rng_for(9), running=True)
    g = CONFIG["scheduler"]["node_size"]
    assert 0.9 * CONFIG["cpu_total"] < run_set["cpus"].sum() \
        < CONFIG["cpu_total"]
    sizes = run_set["cpus"]
    assert ((sizes <= g) | (sizes % g == 0)).all()


def test_configuration_resolves_to_its_own_parts():
    parts = drive.parts_of(CONFIG)
    assert parts.generate is helios.generate
    assert parts.reference is gang_ref
    assert parts.cfg.node_size == 8 and parts.cfg.n_nodes == 262
    assert parts.cfg.cr_tiers.n_tiers == 1
    assert [u.percent for u in parts.users] == CONFIG["shares"]
    assert gang_ref.COMPARED[-1] == "node"


def test_manifest_registers_a_valid_cell():
    """The cell's entries name its files, its workload file agrees with
    its entry, and every metric it reports moves one the cell reports,
    from a reader that exists."""
    [config] = [c for c in MANIFEST["configs"]
                if c["name"] == CONFIG["name"]]
    [cell] = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert config["name"] == CONFIG["name"] == cell["config"]
    assert config["source"] == CONFIG["source"]
    assert config["file"] == f"bench/configs/{CONFIG['name']}.json"
    assert config["reduced"] == CONFIG["reduced"] == []
    assert (WORK["name"], WORK["config"], WORK["traffic"]) == (
        CELL, cell["config"], cell["traffic"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert WORK["warm_rounds"] * WORK["segment_len"] > CONFIG["quantum"]
    e2e = {m["name"] for m in run.metrics_for(MANIFEST, CELL, False)}
    assert e2e == {"setup_s", "ticks_per_s"}
    layers = run.metrics_for(MANIFEST, CELL, True)
    assert {m["name"] for m in layers} == LAYERS
    for m in layers:
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("seed", SEEDS)
def test_small_copy_runs_correct(seed):
    config = dict(CONFIG, capacity=400)
    work = dict(WORK, segment_len=4, rounds_per_s=12, trace_seconds=0.5)
    res = run.run_cell({"name": CELL, "chips": 1}, config, work,
                       run.metrics_for(MANIFEST, CELL, False), seed, 1.0,
                       False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ticks_per_s"}
