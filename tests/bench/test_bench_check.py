"""The benchmark's check (bench/run.py's ``correct``) at a size a test run
can hold, on the CPU: sound runs come out correct, the control comes out
not correct, and so does a run whose timed path is broken underneath.

The device check is skipped (`run.run_cell`); everything after it runs as
on the chip, with the cells' job tables cut to 300 rows and short
windows."""
import json
import sys
import threading
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import run  # noqa: E402
from drive import Tracer  # noqa: E402
from control import control_readings  # noqa: E402
from repro.core import engine, omfs_jax, policies_jax  # noqa: E402

MANIFEST = run.load_manifest()
#: the benchmark's cells, and the defined cells it does not run yet
CELLS = sorted(p.stem for p in (ROOT / "bench" / "workloads").glob("*.json"))
SEED = 2**31 + 977


def spec(cell):
    if cell in {w["name"] for w in MANIFEST["workloads"]}:
        return run.cell_spec(MANIFEST, cell)
    work = json.loads((ROOT / "bench" / "workloads" / f"{cell}.json")
                      .read_text())
    config = json.loads((ROOT / "bench" / "configs" / f"{work['config']}.json")
                        .read_text())
    return {"name": cell, "chips": 4}, config, work


def tiny(cell):
    c, config, work = spec(cell)
    config = dict(config, capacity=300)
    work = dict(work, trace_seconds=0.5)
    if work["entry"] == "stream":
        seg = max(1, work["segment_len"] // 10)
        work.update(segment_len=seg, rounds_per_s=max(2, 60 // seg))
    else:
        work.update(horizon=30, rounds_per_s=2)
    return c, config, work


def drive(cell, seed=SEED):
    c, config, work = tiny(cell)
    return run.run_cell(c, config, work, run.metrics_for(MANIFEST, cell, False),
                        seed, 1.0, False, time.perf_counter())


@pytest.fixture
def fresh_programs():
    """Compiled runners are cached per process; a broken path must be
    traced anew, and must not outlive its test."""
    def clear():
        engine._jitted_segment_runner.cache_clear()
        engine._jitted_batch_runner.cache_clear()
    clear()
    yield
    clear()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, fresh_programs):
    res = drive(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    _, config, work = tiny(cell)
    got = control_readings(config, work, SEED, 1.0)
    assert got["table_mismatches"] > 0, got


def _unchanged_step(monkeypatch, cell):
    monkeypatch.setattr(engine, "tick_jax",
                        lambda cfg, ent, tbl, t, pass_fn, knobs=None: tbl)


def _half_left_out(monkeypatch, cell):
    if tiny(cell)[2]["entry"] == "batch":
        # the odd cells of the sweep are not run: the even cells' knobs
        # stand in for them
        real = omfs_jax.Knobs

        def knobs(quantum, depth):
            odd = jnp.arange(quantum.shape[0]) % 2 == 1
            return real(quantum=jnp.where(odd, jnp.roll(quantum, 1), quantum),
                        depth=jnp.where(odd, jnp.roll(depth, 1), depth))
        monkeypatch.setattr(omfs_jax, "Knobs", knobs)
    else:
        # half of each boundary's arrivals never reach the table
        real = omfs_jax.table_from_jobs

        def half(jobs, *a, **k):
            jobs = sorted(jobs, key=lambda j: j.id)
            return real(jobs[:(len(jobs) + 1) // 2], *a, **k)
        monkeypatch.setattr(omfs_jax, "table_from_jobs", half)


def _altered_answer(monkeypatch, cell):
    real = omfs_jax.admit_job

    def admit(tbl, idx, t, ok):
        out = real(tbl, idx, t, ok)
        first = ok & (tbl.first_start[idx] < 0)
        return out._replace(first_start=out.first_start.at[idx].add(
            jnp.where(first, 1, 0)))
    monkeypatch.setattr(omfs_jax, "admit_job", admit)
    monkeypatch.setattr(policies_jax, "admit_job", admit)


FAULTS = {"unchanged_step": _unchanged_step,
          "half_left_out": _half_left_out,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch,
                                          fresh_programs):
    FAULTS[fault](monkeypatch, cell)
    res = drive(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["hpc10k.live", "hpc10k.sweep4"])
def test_trace_begins_by_the_last_round(cell, tmp_path):
    """A window that ends before ``trace_start_s`` still leaves a trace:
    the tracer begins as the window's last round starts."""
    c, config, work = tiny(cell)
    work["trace_start_s"] = 3600.0
    res = run.run_cell(c, config, work, run.metrics_for(MANIFEST, cell, True),
                       SEED, 1.0, True, time.perf_counter(),
                       trace_dir=str(tmp_path))
    assert res["correct"], res["checks"]
    assert res["info"]["window_s"] < work["trace_start_s"]
    assert list(tmp_path.rglob("*.xplane.pb"))


def test_begin_never_waits_on_a_stop():
    """The last round's `drive.Tracer.begin` returns at once while `stop`
    holds the lock to write a trace that has run."""
    tracer = Tracer("unused", 1.0)
    tracer.state = "running"
    with tracer.lock:
        last_round = threading.Thread(target=tracer.begin)
        last_round.start()
        last_round.join(timeout=10)
        assert not last_round.is_alive()
    assert tracer.state == "running" and not tracer.timers
