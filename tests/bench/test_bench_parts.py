"""A configuration's parts (bench/parts.py, `drive.parts_of`): the two
accepted configurations resolve to what the harness always ran, and a
configuration made only of new files runs through the harness with no
file of it edited.  None of these tests touches a chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import drive  # noqa: E402
import run  # noqa: E402
from parts import shares_of  # noqa: E402
from reference import sched_ref  # noqa: E402
from reference.runs import references  # noqa: E402
from traffic import generator  # noqa: E402
from repro.core.crcost import CRCostModel, TieredCRCostModel  # noqa: E402
from repro.core.types import SchedulerConfig, User  # noqa: E402

MANIFEST = run.load_manifest()
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in MANIFEST["configs"]}
#: the first cell of each configuration: a `simulate_stream` cell
WORK = {}
for _w in MANIFEST["workloads"]:
    WORK.setdefault(_w["config"], run.cell_spec(MANIFEST, _w["name"])[2])
SEEDS = (1, 2**31 + 977)


def accepted_scheduler_config(config):
    """`SchedulerConfig` as the harness built it before configurations
    could name their own settings."""
    kw = {}
    if config.get("cr_tiers"):
        tiers = config["cr_tiers"]
        kw["cr_tiers"] = TieredCRCostModel(
            tiers=tuple(CRCostModel(**{k: v for k, v in t.items()
                                       if k != "capacity_mib"})
                        for t in tiers),
            capacity_mib=tuple(int(t["capacity_mib"]) for t in tiers))
    return SchedulerConfig(cpu_total=int(config["cpu_total"]),
                           quantum=int(config["quantum"]),
                           cr_overhead=int(config["cr_overhead"]), **kw)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_accepted_configs_resolve_as_before(name, seed):
    config = CONFIGS[name]
    work = WORK[name]
    parts = drive.parts_of(config)
    got = parts.generate(config, work, seed, 400)
    want = generator.generate(config, work, seed, 400)
    assert list(got) == list(want)
    for k in generator.COLUMNS:
        assert np.array_equal(got[k], want[k]), k
    assert parts.reference is sched_ref
    assert parts.cfg == accepted_scheduler_config(config)
    n = config["tenants"]
    assert parts.users == [User(f"u{i}", 100.0 / n) for i in range(n)]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_accepted_configs_give_the_same_reference(name, seed):
    config = dict(CONFIGS[name], capacity=300)
    work = dict(WORK[name], rounds_per_s=4)
    if work["entry"] == "stream":
        work.update(segment_len=2, warm_rounds=2)
    [(ref, stats)] = references(config, work, seed, 1.0)
    horizon = 2 * (1 + 2 + 4)
    plain = sched_ref.RefSim(generator.generate(config, work, seed, horizon),
                             config, work["policy"],
                             quantum=int(config["quantum"]),
                             depth=int(config["pass_depth"]))
    assert plain.ent == [int(100.0 / config["tenants"] / 100.0
                             * config["cpu_total"])] * config["tenants"]
    assert stats == plain.run_stream(horizon, 300, 2)
    assert plain.busy == ref.busy
    a, b = ref.table(), plain.table()
    assert list(a) == list(b) == ["jid", *sched_ref.COMPARED]
    assert sched_ref.mismatches(a, b) == 0


def test_scheduler_block_reaches_the_config():
    config = dict(CONFIGS["hpc_centre_10k"],
                  scheduler={"victim_filter_over_entitlement": True,
                             "kernel_backend": "pallas_interpret"})
    cfg = drive.parts_of(config).cfg
    assert cfg.victim_filter_over_entitlement
    assert cfg.kernel_backend == "pallas_interpret"
    assert cfg.quantum == config["quantum"]


def test_unknown_scheduler_key_raises():
    config = dict(CONFIGS["hpc_centre_10k"], scheduler={"node_gpus": 8})
    with pytest.raises(ValueError, match="node_gpus"):
        drive.parts_of(config)


@pytest.mark.parametrize("shares", [
    [40, 20, 20, 10],                 # one tenant short
    [40, 20, 20, 10, 20],             # sums to 110
    [60, 20, 20, 10, -10],            # a negative share
    "20,20,20,20,20",                 # not a list
])
def test_bad_shares_raise(shares):
    config = dict(CONFIGS["hpc_centre_10k"], tenants=5, shares=shares)
    with pytest.raises(ValueError, match="shares"):
        drive.parts_of(config)
    with pytest.raises(ValueError, match="shares"):
        sched_ref.RefSim(generator.generate(config, WORK["hpc_centre_10k"],
                                            1, 10),
                         config, "omfs", quantum=1, depth=1)


def test_shares_set_the_entitlements():
    config = dict(CONFIGS["hpc_centre_10k"], tenants=5,
                  shares=[40, 20, 20, 10, 10])
    users = drive.parts_of(config).users
    assert [u.percent for u in users] == shares_of(config)
    ref = sched_ref.RefSim(generator.generate(config, WORK["hpc_centre_10k"],
                                              1, 10),
                           config, "omfs", quantum=1, depth=1)
    assert ref.ent == [u.entitled_cpus(128) for u in users] \
        == [51, 25, 25, 12, 12]


# -- a configuration made only of new files ---------------------------------

STUB_TRAFFIC = '''"""GPU-fleet traffic for a test: power-of-two sizes, log-normal
runtimes, a standing queue at tick 0, then uniform arrivals."""
import numpy as np


def generate(config, mix, seed, horizon, stream=0):
    rng = np.random.default_rng([int(seed) % (1 << 64), stream])
    standing = int(round(mix["standing_fraction"] * config["capacity"]))
    arrivals = int(rng.poisson(config["arrivals_per_tick"] * horizon))
    n = standing + arrivals
    gpus = 2 ** rng.integers(0, 4, n)
    return {"user": rng.integers(0, config["tenants"], n),
            "cpus": gpus,
            "work": np.ceil(rng.lognormal(2.0, 1.0, n)).astype(np.int64),
            "priority": np.zeros(n, np.int64),
            "jclass": np.full(n, 2, np.int64),
            "submit": np.concatenate([np.zeros(standing, np.int64),
                                      np.sort(rng.integers(0, horizon,
                                                           arrivals))]),
            "state_mib": gpus * config["state_mib_per_cpu"]}
'''

STUB_REFERENCE = '''"""A reference for a test that compares columns of its own."""
import numpy as np

from reference import sched_ref

COMPARED = ("user", "cpus", "submit", "state", "first_start", "finish",
            "n_preempt")


class RefSim(sched_ref.RefSim):
    def table(self):
        full = super().table()
        return {k: full[k] for k in ("jid",) + COMPARED}


def mismatches(program, reference):
    assert set(program) == set(reference) == {"jid", *COMPARED}
    common, pi, ri = np.intersect1d(program["jid"], reference["jid"],
                                    return_indices=True)
    only = len(program["jid"]) + len(reference["jid"]) - 2 * common.size
    return only * len(COMPARED) + sum(
        int((np.asarray(program[k])[pi] != reference[k][ri]).sum())
        for k in COMPARED)
'''

STUB_CONFIG = {
    "name": "gpu_fleet_stub", "source": "a test's own deployment",
    "traffic_model": "gpu_stub", "reference": "node_stub",
    "cpu_total": 64, "tenants": 5, "shares": [40, 20, 20, 10, 10],
    "quantum": 4, "pass_depth": 50, "tick_s": 60, "capacity": 120,
    "cr_overhead": 1, "state_mib_per_cpu": 1024, "arrivals_per_tick": 0.5,
    "scheduler": {"drop_killed": True, "kernel_backend": "lax"},
    "reduced": []}

STUB_WORK = {
    "name": "stub.fleet", "config": "gpu_fleet_stub", "traffic": "fleet_mix",
    "entry": "stream", "policy": "omfs", "segment_len": 2,
    "standing_fraction": 0.9, "rounds_per_s": 6, "trace_seconds": 0.5,
    "warm_rounds": 2, "trace_start_s": 0.0}

DRIVE_STUB = f'''
import json, sys, time
sys.path[:0] = ["bench", {str(ROOT / "src")!r}]
import drive, run
manifest = run.load_manifest()
cell, config, work = run.cell_spec(manifest, "stub.fleet")
parts = drive.parts_of(config)
res = run.run_cell(cell, config, work,
                   run.metrics_for(manifest, "stub.fleet", False),
                   {2**31 + 977}, 1.0, False, time.perf_counter())
res["parts"] = {{"generate": parts.generate.__module__,
                 "reference": parts.reference.__name__,
                 "percents": [u.percent for u in parts.users],
                 "kernel_backend": parts.cfg.kernel_backend}}
print(json.dumps(res))
'''


def test_configuration_of_new_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    new = {"bench/traffic/gpu_stub.py": STUB_TRAFFIC,
           "bench/reference/node_stub.py": STUB_REFERENCE,
           "bench/configs/gpu_fleet_stub.json": json.dumps(STUB_CONFIG),
           "bench/workloads/stub.fleet.json": json.dumps(STUB_WORK)}
    for rel, text in new.items():
        assert not (tmp_path / rel).exists()
        (tmp_path / rel).write_text(text)
    manifest = dict(MANIFEST)
    manifest["configs"] = MANIFEST["configs"] + [{
        "name": "gpu_fleet_stub", "source": "a test's own deployment",
        "file": "bench/configs/gpu_fleet_stub.json", "reduced": [],
        "why": "a configuration of new files"}]
    manifest["workloads"] = MANIFEST["workloads"] + [{
        "name": "stub.fleet", "config": "gpu_fleet_stub",
        "traffic": "fleet_mix", "chips": 1, "why": "a cell of new files"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    proc = subprocess.run(
        [sys.executable, "-c", DRIVE_STUB], cwd=tmp_path, timeout=600,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.splitlines()[-1])
    assert res["parts"] == {"generate": "traffic.gpu_stub",
                            "reference": "reference.node_stub",
                            "percents": [40.0, 20.0, 20.0, 10.0, 10.0],
                            "kernel_backend": "lax"}
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    # the harness took the configuration without an edit to its files
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {Path(p) for p in new}

