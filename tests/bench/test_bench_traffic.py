"""The benchmark's traffic generator (bench/traffic/generator.py)."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from traffic.generator import (  # noqa: E402
    COLUMNS, POPULATION, arrival_ticks, draw_jobs, generate, rng_for)

CONFIGS = {p.stem: json.loads(p.read_text())
           for p in (ROOT / "bench" / "configs").glob("*.json")}
WORK = {p.stem: json.loads(p.read_text())
        for p in (ROOT / "bench" / "workloads").glob("*.json")}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("cell", sorted(WORK))
def test_same_seed_same_traffic(cell):
    work = WORK[cell]
    config = CONFIGS[work["config"]]
    a = generate(config, work, BIG_SEED, 500)
    b = generate(config, work, BIG_SEED, 500)
    c = generate(config, work, BIG_SEED + 1, 500)
    assert set(a) == set(COLUMNS)
    for k in COLUMNS:
        assert np.array_equal(a[k], b[k]), k
    # another seed sends the same jobs at the same ticks, reordered: the
    # schedule changes, the amount of work does not
    assert not np.array_equal(a["work"][:100], c["work"][:100])
    assert np.array_equal(a["submit"], c["submit"])
    for k in COLUMNS:
        assert np.array_equal(np.sort(a[k]), np.sort(c[k])), k
    assert (np.diff(a["submit"]) >= 0).all() and a["submit"].max() < 500


@pytest.mark.parametrize("cell", sorted(WORK))
def test_standing_queue_starts_from_a_running_machine(cell):
    work = WORK[cell]
    config = CONFIGS[work["config"]]
    cols = generate(config, work, 7, 10)
    standing = int(round(work["standing_fraction"] * config["capacity"]))
    assert (cols["submit"][:standing] == 0).all()
    tenants = np.unique(cols["user"][:standing])
    assert tenants.tolist() == list(range(config["tenants"]))
    # the queue opens with the running set: it fills most of the machine
    # and no more, and its residual runtimes are length-biased
    run = draw_jobs(config, 4 * config["cpu_total"], rng_for(POPULATION),
                    running=True)
    assert np.array_equal(np.sort(cols["cpus"][:run["cpus"].size]),
                          np.sort(run["cpus"]))
    assert 0.5 * config["cpu_total"] < run["cpus"].sum() < config["cpu_total"]
    assert cols["cpus"].max() <= 2 ** config["model"]["uhi"]
    assert cols["work"].min() >= 1 and (cols["jclass"] == 2).all()
    assert np.array_equal(cols["state_mib"],
                          cols["cpus"] * config["state_mib_per_cpu"])


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_job_shapes_follow_the_model(config):
    """Sizes and runtimes have the model's marginals and its correlation:
    the serial share, mostly powers of two, longer runs for larger jobs."""
    cfg = CONFIGS[config]
    m = cfg["model"]
    cols = draw_jobs(cfg, 200_000, rng_for(3))
    size, work = cols["cpus"], cols["work"]
    assert abs((size == 1).mean() - m["serial_prob"]) < 0.01
    parallel = size[size > 1]
    pow2 = (parallel & (parallel - 1)) == 0
    assert pow2.mean() > m["pow2_prob"]
    assert np.median(work[size >= 32]) > 2 * np.median(work[size == 1])


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_arrivals_follow_the_model(config):
    """Mean inter-arrival time of the model, E[exp(Gamma(k, theta))] =
    (1 - theta)**-k seconds, in ticks of ``tick_s``."""
    cfg = CONFIGS[config]
    m = cfg["model"]
    horizon = 400_000
    ticks = arrival_ticks(cfg, horizon, rng_for(5))
    expected = horizon * cfg["tick_s"] * (1 - m["barr"]) ** m["aarr"]
    assert abs(ticks.size / expected - 1) < 0.1
    assert (np.diff(ticks) >= 0).all() and ticks.max() < horizon
