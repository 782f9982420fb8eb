"""Smoke run of the fair-share scheduler's device path on a TPU.

Drives the scheduler through the entry points its users call —
``engine.simulate``, ``engine.simulate_batch`` and
``engine.simulate_stream`` — at the size of an HPC centre's backlog, and
checks what comes out.  One process owns the chip(s) throughout.

    python chip_smoke.py            # one chip: the six phases below
    python chip_smoke.py --chips 4  # only the sweep sharded over four chips

Phases (one chip):

1. device   — the default backend must be a TPU; there is no CPU fallback.
2. reference — JAX backend vs the Python reference at J=400 (4 tenants,
   256 CPUs, OMFS evicting): equal schedule signatures.
3. tick_scan — J=100,000 rows, 16,384 CPUs, two-tier C/R costs, quantum
   10, pass_depth 64, 500 ticks; ``omfs`` and ``backfill_cr`` on the lax
   path.  busy <= cpu_total on every tick, and evictions and spills occur.
4. kernel   — ``kernel_backend="pallas"`` at J=10,000 and at the kernel's
   largest table: the compiled ``sched_select`` kernel (a
   ``tpu_custom_call`` in the program) gives tables bit-identical to lax,
   and the bare kernel matches its reference on random columns.
5. sweep    — ``simulate_batch`` over 4 policies x 2 seeds at J=10,000;
   every cell equals sequential ``simulate``.
6. stream   — ``simulate_stream`` at capacity 10,000 with events recorded:
   no event dropped, and the schedule equals the monolithic run.

Each phase prints one line: its name, J, ticks, compile seconds and run
seconds.  The seconds are smoke wall times (compile = the XLA compile
events JAX reports; run = the rest of the phase, tracing and host set-up
included, ending in ``block_until_ready``), not benchmark rows.  The last
line is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed.  Any failure raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.bench_sched_scale import _tiered_cfg, _workload  # noqa: E402
from repro.core import engine, omfs_jax  # noqa: E402
from repro.core.types import SchedulerConfig  # noqa: E402
from repro.core.workload import arrival_stream  # noqa: E402
from repro.kernels.sched_select.ops import (  # noqa: E402
    MAX_JOBS, plan_evictions_fused, plan_evictions_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs import ProfileTimers  # noqa: E402

#: the deployment: an HPC centre's backlog (see the module docstring)
FULL_JOBS = 100_000
CPU_TOTAL = 16_384
PASS_DEPTH = 64
#: the deployment's 2,000-tick horizon, cut to keep the whole smoke run
#: under 20 minutes: on one v5e chip 500 ticks at J=100,000 took 50 s
#: (omfs) and 159 s (backfill_cr) besides ~43 s of compile each, so 2,000
#: ticks of both would take ~14 minutes on their own
HORIZON = 500
#: the sweep's policies (x 2 seeds = 8 cells)
SWEEP_POLICIES = ("omfs", "omfs_cheap_victim", "backfill_cr", "fcfs")

#: JAX's event for one XLA (and Mosaic) compile of a top-level program;
#: tracing and lowering nest across jits, so they stay in run_s
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: JAX's event for a program read back from the persistent compile cache
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


@contextmanager
def phase(name: str, n_jobs: int, ticks: int):
    """Time one phase and print its line; ``info`` collects extra fields.

    Compile seconds and persistent-cache hits come from JAX's own
    monitoring events, listened to for the length of the phase."""
    info: dict = {}
    compile_s, hits = [0.0], [0]

    def on_duration(event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            compile_s[0] += duration

    def on_event(event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    try:
        yield info
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    wall = time.perf_counter() - t0
    extra = " ".join(f"{k}={v}" for k, v in info.items())
    print(f"phase={name} J={n_jobs} ticks={ticks} "
          f"compile_s={compile_s[0]:.3f} run_s={wall - compile_s[0]:.3f} "
          f"(smoke wall times) cache_hits={hits[0]} {extra}", flush=True)


def check_device(want: int) -> dict:
    """Phase 1: the chip(s) JAX sees; exits non-zero without a TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX's default backend is "
                 f"{devs[0].platform!r}; this script has no CPU fallback")
    if len(devs) < want:
        sys.exit(f"chip_smoke: {want} TPU chips wanted, JAX sees {len(devs)}")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"phase=device platform={dev['platform']} kind={dev['kind']!r} "
          f"count={dev['count']}", flush=True)
    return dev


def _ready(res):
    jax.block_until_ready(res.table)
    return res


def _same(a, b) -> bool:
    return (omfs_jax.tables_equal(a.table, b.table)
            and np.array_equal(a.busy_series(), b.busy_series()))


def run_reference(n_jobs: int = 400, cpu_total: int = 256,
                  horizon: int = 200, n_users: int = 4) -> None:
    """Phase 2: JAX backend == Python reference (schedule signatures), at
    a size the reference finishes in seconds and where OMFS evicts."""
    users, jobs = _workload(n_jobs, cpu_total, n_users=n_users)
    cfg = _tiered_cfg(cpu_total)
    with phase("reference", n_jobs, horizon) as info:
        dev = _ready(engine.simulate(users, jobs, cfg, horizon, "omfs",
                                     backend="jax"))
        ref = engine.simulate(users, jobs, cfg, horizon, "omfs",
                              backend="python")
        _check(dev.signature() == ref.signature(),
               "JAX backend disagrees with the Python reference")
        _check(np.array_equal(dev.busy_series(), ref.busy_series()),
               "JAX busy series disagrees with the Python reference")
        info["preemptions"] = dev.summary()["preemptions"]
        _check(info["preemptions"] > 0, "the reference run never evicted")


def run_tick_scan(n_jobs: int = FULL_JOBS, cpu_total: int = CPU_TOTAL,
                  horizon: int = HORIZON, pass_depth: int = PASS_DEPTH,
                  policies=("omfs", "backfill_cr")) -> None:
    """Phase 3: the full-size tick scan, lax path."""
    users, jobs = _workload(n_jobs, cpu_total)
    cfg = _tiered_cfg(cpu_total)
    for policy in policies:
        with phase(f"tick_scan/{policy}", n_jobs, horizon) as info:
            res = _ready(engine.simulate(users, jobs, cfg, horizon, policy,
                                         backend="jax",
                                         pass_depth=pass_depth))
            busy = res.busy_series()
            s = res.summary()
            _check(busy.shape == (horizon,), f"busy series {busy.shape}")
            _check((busy <= cpu_total).all() and (busy >= 0).all(),
                   f"{policy}: busy exceeds cpu_total={cpu_total}")
            _check(s["preemptions"] > 0, f"{policy}: no eviction happened")
            _check(s["spills"] > 0, f"{policy}: no checkpoint spilled")
            info.update(utilization=round(s["utilization"], 6),
                        goodput=round(s["goodput"], 6),
                        preemptions=s["preemptions"], spills=s["spills"],
                        done=s["done"])


def _random_columns(rng, j: int, n_tiers: int):
    lat = rng.integers(0, 60, (j, n_tiers)).astype(np.int32)
    return (rng.integers(0, 5, j).astype(np.int32),
            rng.integers(-1, 400, j).astype(np.int32),
            rng.permutation(j).astype(np.int32), lat[:, 0],
            rng.random(j) < 0.5, rng.integers(1, 64, j).astype(np.int32),
            rng.integers(0, 4096, j).astype(np.int32), rng.random(j) < 0.7,
            lat)


def run_kernel(sizes=(10_000, MAX_JOBS), cpu_total: int = CPU_TOTAL,
               horizon: int = 100, pass_depth: int = PASS_DEPTH,
               interpret: bool = False,
               kernel_sizes=(100, 4096)) -> None:
    """Phase 4: the compiled ``sched_select`` kernel.  Bare, every static
    variant against its reference at a one-row and a multi-row tile; in the
    engine, bit-identical to lax at each of ``sizes``."""
    backend = "pallas_interpret" if interpret else "pallas"
    with phase("kernel/bare", max(kernel_sizes), 0) as info:
        rng = np.random.default_rng(0)
        for j in kernel_sizes:
            cols = _random_columns(rng, j, 2)
            for cheap in (False, True):
                for tiered, bounded in ((False, False), (True, False),
                                        (True, True)):
                    cap = np.asarray([1 << 16 if bounded else -1, -1],
                                     np.int32)
                    sc = (0, int(cols[5].sum()) // 3,
                          np.asarray([1000, 0], np.int32), cap)
                    kw = dict(cheap=cheap, tiered=tiered, bounded=bounded)
                    got = plan_evictions_fused(*cols, *sc, interpret=interpret,
                                               **kw)
                    want = plan_evictions_ref(*cols, *sc, **kw)
                    for name, g, w in zip(("planned", "enough", "tier"),
                                          got, want):
                        _check(np.array_equal(np.asarray(g), np.asarray(w)),
                               f"kernel {name} differs at J={j} {kw}")
        info["variants"] = 6 * len(kernel_sizes)
    for n_jobs in sizes:
        users, jobs = _workload(n_jobs, cpu_total)
        lax_cfg = _tiered_cfg(cpu_total)
        pal_cfg = _tiered_cfg(cpu_total, backend)
        with phase(f"kernel/{backend}", n_jobs, horizon) as info:
            # the program simulate() runs must hold the compiled kernel
            pass_fn = engine.POLICIES["omfs"].jax_factory(pass_depth)
            tbl, ent = omfs_jax.table_from_jobs(jobs, users, cpu_total,
                                                pal_cfg)
            text = engine._jitted_runner(pal_cfg, pass_fn, horizon).lower(
                tbl, ent).as_text()
            _check(("tpu_custom_call" in text) != interpret,
                   "compiled program does not hold the sched_select kernel")
            pal = _ready(engine.simulate(users, jobs, pal_cfg, horizon,
                                         "omfs", backend="jax",
                                         pass_depth=pass_depth))
            lax = _ready(engine.simulate(users, jobs, lax_cfg, horizon,
                                         "omfs", backend="jax",
                                         pass_depth=pass_depth))
            _check(_same(pal, lax), f"pallas != lax at J={n_jobs}")
            s = pal.summary()
            _check(s["preemptions"] > 0, "the kernel never planned a victim")
            info.update(preemptions=s["preemptions"], spills=s["spills"],
                        tpu_custom_call="tpu_custom_call" in text)


def _sweep_cells(n_jobs: int, cpu_total: int, pass_depth: int,
                 policies=SWEEP_POLICIES, seeds=(1, 2)):
    cells = []
    for seed in seeds:
        users, jobs = _workload(n_jobs, cpu_total, seed=seed)
        cells += [engine.BatchCell(users=users, jobs=jobs, policy=p,
                                   pass_depth=pass_depth) for p in policies]
    return cells


def _flat_cfg(cpu_total: int) -> SchedulerConfig:
    # the sweep's vmap runs every branch of every policy at every queue
    # position, so it uses the flat C/R cost (no per-victim placement scan)
    return SchedulerConfig(cpu_total=cpu_total, quantum=10, cr_overhead=2)


def run_sweep(n_jobs: int = 10_000, cpu_total: int = CPU_TOTAL,
              horizon: int = 20, pass_depth: int = PASS_DEPTH) -> None:
    """Phase 5: one batched program over 8 cells == sequential runs."""
    cells = _sweep_cells(n_jobs, cpu_total, pass_depth)
    cfg = _flat_cfg(cpu_total)
    with phase("sweep", n_jobs, horizon) as info:
        batch = engine.simulate_batch(cells, cfg, horizon, devices=1)
        for r in batch:
            _ready(r)
        info["cells"] = len(cells)
    with phase("sweep/sequential", n_jobs, horizon) as info:
        for c, b in zip(cells, batch):
            seq = _ready(engine.simulate(c.users, c.jobs, cfg, horizon,
                                         c.policy, backend="jax",
                                         pass_depth=pass_depth))
            _check(_same(seq, b), f"batch cell {c.policy} != simulate")
        info["preemptions"] = sum(b.summary()["preemptions"] for b in batch)


def run_sharded_sweep(n_dev: int = 4, n_jobs: int = 10_000,
                      cpu_total: int = CPU_TOTAL, horizon: int = 20,
                      pass_depth: int = PASS_DEPTH) -> None:
    """``--chips 4``: the sweep sharded over ``n_dev`` devices equals the
    one-device sweep cell by cell, and its tables lay on ``n_dev`` devices."""
    cells = _sweep_cells(n_jobs, cpu_total, pass_depth)
    cfg = _flat_cfg(cpu_total)
    with phase(f"sweep/devices={n_dev}", n_jobs, horizon) as info:
        sharded = [_ready(r) for r in engine.simulate_batch(
            cells, cfg, horizon, devices=n_dev)]
        spread = {len(r.table.cpus.sharding.device_set) for r in sharded}
        _check(spread == {n_dev}, f"batch tables spread over {spread} devices")
        info.update(cells=len(cells), devices=n_dev)
    with phase("sweep/devices=1", n_jobs, horizon) as info:
        single = [_ready(r) for r in engine.simulate_batch(
            cells, cfg, horizon, devices=1)]
        for c, a, b in zip(cells, sharded, single):
            _check(_same(a, b), f"sharded cell {c.policy} != one device")
        info["cells"] = len(cells)


def run_stream(capacity: int = 10_000, cpu_total: int = CPU_TOTAL,
               horizon: int = 400, segment_len: int = 100,
               pass_depth: int = PASS_DEPTH) -> None:
    """Phase 6: the streaming engine with events, against the monolithic
    run over the same arrivals."""
    users, jobs = _workload(capacity, cpu_total)
    jobs = [j for j in jobs if j.submit_time < horizon]
    cfg = _tiered_cfg(cpu_total)
    prof = ProfileTimers()
    with phase("stream", capacity, horizon) as info:
        res = engine.simulate_stream(
            users, arrival_stream(jobs), cfg, horizon, "omfs",
            capacity=capacity, segment_len=segment_len,
            pass_depth=pass_depth, record_events=True, profile=prof)
        _ready(res)
        _check(res.events_dropped_total() == 0, "event ring dropped events")
        _check(res.stream_stats["deferrals"] == 0,
               "stream deferred arrivals: capacity too small")
        mono = _ready(engine.simulate(users, jobs, cfg, horizon, "omfs",
                                      backend="jax", pass_depth=pass_depth))
        _check(res.signature() == mono.signature(),
               "stream schedule != monolithic schedule")
        snap = prof.snapshot()
        for section in ("compile", "dispatch", "compaction"):
            s = snap.get(section, {"total_s": 0.0, "calls": 0})
            info[f"stream_{section}_s"] = f"{s['total_s']:.3f}"
            info[f"stream_{section}_calls"] = s["calls"]
        info.update(segments=res.stream_stats["segments"],
                    events=len(res.events))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sweep sharded over four chips")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = check_device(args.chips)
    if args.chips == 4:
        run_sharded_sweep(4)
    else:
        run_reference()
        run_tick_scan()
        run_kernel()
        run_sweep()
        run_stream()
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
