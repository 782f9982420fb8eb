"""Fleet-scale scheduler throughput: Python reference vs vectorized JAX,
and — the PR-2 headline — the reference O(J)-per-admission JAX pass vs the
incremental-aggregate pass (`core.omfs_jax.make_omfs_pass(incremental=True)`,
DESIGN.md §Incremental aggregates).

The JAX simulator is what makes 1000+-node / 100k-job what-if studies cheap —
this benchmark measures ticks/second at increasing job counts, with the
SLURM-style ``pass_depth`` bound for the O(J^2) pass, and asserts the
optimized pass produces bit-identical schedule signatures to the reference.

``--smoke`` runs one tiny case (CI keeps the hot path importable + correct).
"""
from __future__ import annotations

import argparse
import time

import jax

from benchmarks.common import emit, write_rows
from repro.core import omfs_jax
from repro.core.crcost import UNBOUNDED, CRCostModel, TieredCRCostModel
from repro.core.simulator import simulate
from repro.core.types import SchedulerConfig
from repro.core.workload import WorkloadSpec, make_jobs, make_users


def _workload(n_jobs: int, cpu_total: int, n_users: int = 16,
              arrival_rate: float = 0.5, seed: int = 1):
    """A workload that actually *reaches* ``n_jobs`` table rows: the spec
    horizon scales with the target so the arrival process generates enough
    jobs (jobs past the simulated horizon still cost O(J) table work, which
    is exactly the scale knob under test)."""
    gen_horizon = max(200, int(1.5 * n_jobs / (n_users * arrival_rate)))
    spec = WorkloadSpec(n_users=n_users, horizon=gen_horizon,
                        cpu_total=cpu_total, seed=seed,
                        arrival_rate=arrival_rate, mean_work=60)
    users = make_users(spec)
    jobs = make_jobs(spec, users)[:n_jobs]
    assert len(jobs) == n_jobs, f"workload too small: {len(jobs)} < {n_jobs}"
    return users, jobs


def _time_jax(users, jobs, cfg, horizon, pass_depth, incremental, reps=5):
    # warm up with the same shapes so compilation stays out of the timing;
    # best-of-`reps` so the CI regression gate compares stable numbers
    _, busy = omfs_jax.simulate_jax(users, jobs, cfg, horizon, pass_depth,
                                    incremental=incremental)
    jax.block_until_ready(busy)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        tbl, busy = omfs_jax.simulate_jax(users, jobs, cfg, horizon,
                                          pass_depth,
                                          incremental=incremental)
        jax.block_until_ready(busy)
        best = min(best, time.perf_counter() - t0)
    return tbl, busy, best


def run_case(n_jobs: int, cpu_total: int, pass_depth, horizon: int) -> None:
    users, jobs = _workload(n_jobs, cpu_total)
    cfg = SchedulerConfig(cpu_total=cpu_total, quantum=10)

    if n_jobs <= 400:  # Python reference gets slow fast
        t_py = float("inf")
        for _ in range(5):   # best-of-5: this row anchors the CI gate
            t0 = time.perf_counter()
            simulate(users, [j.clone() for j in jobs], cfg, horizon)
            t_py = min(t_py, time.perf_counter() - t0)
        emit(f"sched_scale/python_{n_jobs}jobs_ticks_per_s",
             horizon / t_py, f"cpus={cpu_total}")

    tbl_ref, _, t_ref = _time_jax(users, jobs, cfg, horizon, pass_depth, False)
    emit(f"sched_scale/jax_ref_{n_jobs}jobs_ticks_per_s", horizon / t_ref,
         f"cpus={cpu_total};pass_depth={pass_depth}")

    tbl_inc, busy, t_inc = _time_jax(users, jobs, cfg, horizon, pass_depth, True)
    emit(f"sched_scale/jax_inc_{n_jobs}jobs_ticks_per_s", horizon / t_inc,
         f"cpus={cpu_total};pass_depth={pass_depth};"
         f"util={float(busy.mean())/cpu_total:.3f}")

    assert omfs_jax.tables_equal(tbl_ref, tbl_inc), \
        f"incremental pass changed the schedule at J={n_jobs}"
    emit(f"sched_scale/incremental_speedup_{n_jobs}jobs", t_ref / t_inc,
         "x vs reference pass (identical signatures)")

    # size-aware C/R cost model enabled: same incremental pass, the jobs'
    # heterogeneous state sizes now charge save/restore penalties.  The
    # acceptance bar is <= 10% tick-throughput regression (the costs are
    # precomputed table columns + O(1) scatters, not per-tick O(J) work).
    cfg_cost = SchedulerConfig(
        cpu_total=cpu_total, quantum=10,
        cr_cost=CRCostModel(save_mib_per_tick=4096, restore_mib_per_tick=8192,
                            save_base=1, restore_base=1))
    _, _, t_cost = _time_jax(users, jobs, cfg_cost, horizon, pass_depth, True)
    emit(f"sched_scale/jax_costmodel_{n_jobs}jobs_ticks_per_s",
         horizon / t_cost,
         f"rel_to_free={t_inc / t_cost:.3f};"
         f"(>=0.9 keeps the cost model inside the perf budget)")

    # tiered eviction placement enabled: the per-victim placement lax.scan
    # runs ONLY on the eviction branch, so tick throughput must stay close
    # to the flat cost model's.
    cfg_tiered = _tiered_cfg(cpu_total)
    _, _, t_tier = _time_jax(users, jobs, cfg_tiered, horizon, pass_depth, True)
    emit(f"sched_scale/jax_tiered_{n_jobs}jobs_ticks_per_s",
         horizon / t_tier,
         f"rel_to_costmodel={t_cost / t_tier:.3f};"
         f"(placement scan confined to the eviction branch)")


def _tiered_cfg(cpu_total: int, backend: str = "lax") -> SchedulerConfig:
    """Tiered C/R config for the backend A/B: tiers exercise the FULL fused
    surface (victim keys + masked sort + cumsum cutoff + greedy placement),
    not just the flat-cost subset."""
    return SchedulerConfig(
        cpu_total=cpu_total, quantum=10,
        kernel_backend=backend,
        cr_tiers=TieredCRCostModel(
            tiers=(CRCostModel(save_mib_per_tick=4096,
                               restore_mib_per_tick=8192,
                               save_base=1, restore_base=1),
                   CRCostModel(save_mib_per_tick=512,
                               restore_mib_per_tick=1024,
                               save_base=2, restore_base=2)),
            capacity_mib=(16 << 10, UNBOUNDED)))


def _lattice_cfg(cpu_total: int) -> SchedulerConfig:
    """T=4 HBM/DRAM/NVMe/object hierarchy with the measured delta
    coefficients (182/256, `crcost.measured_delta_num`) — the [J, T]
    lattice's stress case: four save/restore columns ride the victim sort
    and the greedy placement walks four capacity lanes."""
    from repro.core.crcost import measured_delta_num
    d = measured_delta_num()
    return SchedulerConfig(
        cpu_total=cpu_total, quantum=10,
        cr_tiers=TieredCRCostModel(
            tiers=(CRCostModel(save_mib_per_tick=8192,
                               restore_mib_per_tick=16384,
                               delta_num=d, delta_den=256),
                   CRCostModel(save_mib_per_tick=4096,
                               restore_mib_per_tick=8192, save_base=1,
                               delta_num=d, delta_den=256),
                   CRCostModel(save_mib_per_tick=512,
                               restore_mib_per_tick=1024, save_base=1,
                               restore_base=1, delta_num=d, delta_den=256),
                   CRCostModel(save_mib_per_tick=64,
                               restore_mib_per_tick=128, save_base=2,
                               restore_base=2, delta_num=d, delta_den=256)),
            capacity_mib=(4 << 10, 16 << 10, 64 << 10, UNBOUNDED)))


def lattice_case(n_jobs: int, cpu_total: int, pass_depth,
                 horizon: int) -> None:
    """[J, T] cost-lattice throughput gate (ISSUE 10): a T=4 delta-aware
    hierarchy must hold tick throughput within 10% of the T=2 two-column
    model at fleet scale — the extra tiers are more int32 lanes on the
    existing sort/scan, never extra passes."""
    users, jobs = _workload(n_jobs, cpu_total)
    _, _, t_two = _time_jax(users, jobs, _tiered_cfg(cpu_total), horizon,
                            pass_depth, True)
    _, _, t_lat = _time_jax(users, jobs, _lattice_cfg(cpu_total), horizon,
                            pass_depth, True)
    rel = t_two / t_lat
    emit(f"sched_scale/jax_lattice_{n_jobs}jobs_ticks_per_s",
         horizon / t_lat,
         f"rel_to_two_column={rel:.3f};tiers=4;delta=182/256;"
         "(>=0.9 at J>=10k keeps the lattice inside the perf budget)")
    if n_jobs >= 10_000:
        assert rel >= 0.9, (
            f"T=4 lattice throughput {rel:.1%} of the two-column model at "
            f"J={n_jobs} — the lattice broke the <=10% overhead budget")


def backend_case(n_jobs: int, cpu_total: int, pass_depth, horizon: int,
                 reps: int = 3) -> None:
    """The tentpole A/B: eviction machinery served by the ``lax`` path
    (hoisted lexsort + cumsum + placement `lax.scan`) vs the fused
    `kernels.sched_select` Pallas kernel, same incremental pass, same
    tiered cost model, asserted bit-identical.

    Off the TPU the kernel runs as ``"pallas_interpret"`` (the kernel body
    runs as XLA ops), so the pallas rows there measure *dispatch +
    interpret* overhead, not the TPU win, and say so in their detail.
    Both rows are still `_ticks_per_s`-gated: a regression in either
    dispatch path (or an accidental retrace) shows up as a throughput
    drop."""
    users, jobs = _workload(n_jobs, cpu_total)
    on_tpu = jax.default_backend() == "tpu"
    cfg_lax = _tiered_cfg(cpu_total, "lax")
    cfg_pal = _tiered_cfg(cpu_total, "pallas" if on_tpu else "pallas_interpret")

    tbl_lax, _, t_lax = _time_jax(users, jobs, cfg_lax, horizon, pass_depth,
                                  True, reps)
    emit(f"sched_scale/sched_kernel_lax_{n_jobs}jobs_ticks_per_s",
         horizon / t_lax, f"cpus={cpu_total};pass_depth={pass_depth}")

    tbl_pal, _, t_pal = _time_jax(users, jobs, cfg_pal, horizon, pass_depth,
                                  True, reps)
    emit(f"sched_scale/sched_kernel_pallas_{n_jobs}jobs_ticks_per_s",
         horizon / t_pal,
         f"cpus={cpu_total};pass_depth={pass_depth};"
         f"interpret={not on_tpu}")

    assert omfs_jax.tables_equal(tbl_lax, tbl_pal), \
        f"pallas backend changed the schedule at J={n_jobs}"
    # informational, NOT gated (interpret-mode ratios are meaningless on
    # CPU; on TPU this becomes the headline number)
    emit(f"sched_scale/pallas_vs_lax_ratio_{n_jobs}jobs", t_lax / t_pal,
         "x lax (identical tables; interpret mode => expect < 1 on CPU)")


def sched_roofline_entry(n_jobs: int = 262_144) -> None:
    """Roofline statement of the expected TPU win for the fused kernel.

    Per *eviction tick* at J jobs the lax path pays (a) an HBM-resident
    variadic lexsort — ~log2(J)*(log2(J)+1)/2 bitonic stages over ~5 int32
    operands — and (b) a J-step sequential `lax.scan` for greedy placement,
    whose per-step loop latency dominates everything at fleet scale.  The
    fused kernel reads 8 int32 columns from HBM once, keeps every
    intermediate in VMEM, and bounds the placement loop by the planned
    count.  Numbers below use nominal v4-ish rates (HBM 1.2 TB/s, VMEM
    ~20x that, ~1us/sequential-step); the value is the expected
    per-eviction-tick speedup, emitted as an ungated roofline row."""
    hbm_bps, vmem_bps, step_s = 1.2e12, 2.2e13, 1e-6
    jp = 1 << max(7, (n_jobs - 1).bit_length())
    log2j = jp.bit_length() - 1
    stages = log2j * (log2j + 1) // 2
    # lax: bitonic sort traffic in HBM (5 operands, read+write per stage)
    # plus the J-step placement scan
    lax_sort_s = stages * 5 * 2 * 4 * jp / hbm_bps
    lax_scan_s = n_jobs * step_s
    t_lax = lax_sort_s + lax_scan_s
    # pallas: one HBM round trip (8 cols in, 3 out) + the same stage count
    # of VMEM-resident traffic (~6 live operands)
    pallas_io_s = (8 + 3) * 4 * jp / hbm_bps
    pallas_vmem_s = stages * 6 * 2 * 4 * jp / vmem_bps
    t_pallas = pallas_io_s + pallas_vmem_s
    emit(f"sched_scale/roofline_sched_select_{n_jobs}jobs_expected_speedup",
         t_lax / t_pallas,
         f"lax~{t_lax*1e3:.1f}ms(sort {lax_sort_s*1e3:.2f}+scan "
         f"{lax_scan_s*1e3:.1f})/evict-tick vs pallas~{t_pallas*1e6:.0f}us;"
         f"VMEM-bound at ~{6 * 4 * jp >> 20}MiB live")


def instrumented_case(n_jobs: int, cpu_total: int, horizon: int) -> None:
    """Event-ring overhead gate (repro.obs): tick throughput with
    ``record_events=True`` — in-scan capture + host-side ring decode — must
    stay within 10% of the uninstrumented run at fleet scale (J = 10k, the
    acceptance bar: capture is ~30 elementwise ops + one scatter on [8*J],
    amortized to noise once a tick costs tens of ms).  Smaller runs emit
    the row for the trajectory without the hard assert — there the sub-ms
    jitted tick is comparable to the fixed capture/decode cost and the
    ratio measures host speed, not the ring."""
    import json as _json
    import os as _os

    from repro.core import engine
    from repro.obs import registry_from_result

    users, jobs = _workload(n_jobs, cpu_total)
    cfg = SchedulerConfig(cpu_total=cpu_total, quantum=10)

    def timed(record):
        t0 = time.perf_counter()
        res = engine.simulate(users, jobs, cfg, horizon, backend="jax",
                              record_events=record)
        jax.block_until_ready(res.busy)
        return res, time.perf_counter() - t0

    timed(False), timed(True)                         # warm both programs
    t_plain = t_inst = float("inf")
    res = None
    # interleave plain/instrumented reps: the ratio then compares
    # neighboring measurements, so host-speed drift across the bench run
    # (thermal, co-tenants) cancels instead of masquerading as overhead
    for _ in range(5):
        _, tp = timed(False)
        res, ti = timed(True)
        t_plain = min(t_plain, tp)
        t_inst = min(t_inst, ti)
    rel = t_plain / t_inst
    dropped = res.events_dropped_total()
    emit(f"sched_scale/jax_instrumented_{n_jobs}jobs_ticks_per_s",
         horizon / t_inst,
         f"rel_to_plain={rel:.3f};events={len(res.events)};"
         f"dropped={dropped}")
    # DROPPED is never silent: its own row, even (especially) when zero
    emit(f"sched_scale/instrumented_events_dropped_{n_jobs}jobs",
         float(dropped), "lossless ring => must stay 0")
    assert dropped == 0, \
        f"lossless ring dropped {dropped} events at J={n_jobs}"
    if n_jobs >= 10_000:
        assert rel >= 0.9, (
            f"instrumented throughput {rel:.1%} of plain at J={n_jobs} — "
            "the event ring broke the <=10% overhead budget")

    # metrics-registry JSON snapshot rides along with the bench artifacts
    # (METRICS_*, not BENCH_*: compare_bench globs BENCH_*.json for rows)
    outdir = _os.environ.get("BENCH_OUTDIR", ".")
    _os.makedirs(outdir, exist_ok=True)
    snap = _os.path.join(outdir, "METRICS_sched_scale.json")
    with open(snap, "w") as f:
        _json.dump(registry_from_result(res, users=users).to_json(), f,
                   indent=1)
    print(f"wrote {snap}")


def profiling_case(horizon: int, capacity: int, segment_len: int) -> None:
    """Streaming-engine profiling hooks: wall time split into compile
    (fresh segment-runner builds), dispatch (jitted segment execution) and
    host-side compaction (the stream boundary).  Timings are machine noise,
    not gated rows — they land in the bench JSON and the step summary so a
    compile-time or boundary blow-up is visible per-PR."""
    from repro.core import engine
    from repro.core.workload import endless_arrivals
    from repro.obs import ProfileTimers

    spec = WorkloadSpec(n_users=8, horizon=horizon, cpu_total=64, seed=3,
                        arrival_rate=0.4, mean_work=40)
    users = make_users(spec)
    cfg = SchedulerConfig(cpu_total=64, quantum=10)
    prof = ProfileTimers()
    res = engine.simulate_stream(users, endless_arrivals(spec, users), cfg,
                                 horizon, "omfs", capacity=capacity,
                                 segment_len=segment_len,
                                 record_events=True, profile=prof)
    snap = prof.snapshot()
    for section in ("compile", "dispatch", "compaction"):
        s = snap.get(section, {"total_s": 0.0, "calls": 0})
        emit(f"sched_scale/stream_profile_{section}_s", s["total_s"],
             f"calls={s['calls']};capacity={capacity};"
             f"segment_len={segment_len}")
    emit("sched_scale/stream_events_dropped",
         float(res.events_dropped_total()),
         f"events={len(res.events)} (lossless ring => must stay 0)")
    assert res.events_dropped_total() == 0


def _obs_step_summary() -> None:
    """Surface the telemetry rows (ring drops + profiling split) in the CI
    step summary — ring overflow must never be silent (repro.obs)."""
    import os as _os

    path = _os.environ.get("GITHUB_STEP_SUMMARY")
    if not path:
        return
    from benchmarks.common import ROWS

    picks = [(n, v, d) for n, v, d in ROWS
             if "instrumented" in n or "stream_profile" in n
             or "events_dropped" in n]
    if not picks:
        return
    lines = ["## Scheduler telemetry (repro.obs)", "",
             "| row | value | detail |", "|---|---|---|"]
    lines += [f"| `{n}` | {v:.6g} | {d} |" for n, v, d in picks]
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n\n")


def donation_case(n_jobs: int, cpu_total: int, horizon: int) -> None:
    """Peak-memory gate for the donated table buffers (ISSUE 7 satellite).

    The jitted runners declare ``donate_argnums=(0,)``: XLA reuses the
    input table's buffers for the output, so a sweep's working set is ONE
    table, not input+output.  Two asserts make that a regression gate
    rather than a hope: the donated input must actually be deleted, and
    the total live-array footprint after the run must not have grown by a
    second table copy (slack: the busy series plus one column)."""
    import resource

    from repro.core import engine

    users, jobs = _workload(n_jobs, cpu_total)
    cfg = SchedulerConfig(cpu_total=cpu_total, quantum=10)
    run = engine._jitted_runner(cfg, omfs_jax.make_omfs_pass(64), horizon)
    tbl, ent = omfs_jax.table_from_jobs(jobs, users, cfg.cpu_total, cfg)
    table_bytes = sum(getattr(tbl, f).nbytes for f in tbl._fields)

    donated = engine._copy_table(tbl)      # keep `tbl` alive as the yardstick
    jax.block_until_ready(donated.cpus)
    before = sum(a.nbytes for a in jax.live_arrays())
    out, busy = run(donated, ent)
    jax.block_until_ready(busy)
    after = sum(a.nbytes for a in jax.live_arrays())

    assert donated.cpus.is_deleted(), \
        "input table was NOT donated — the runner holds two table copies"
    grew = after - before
    slack = busy.nbytes + tbl.cpus.nbytes
    assert grew <= slack, (
        f"live arrays grew {grew}B > {slack}B slack for a {table_bytes}B "
        "table — donation regressed (output no longer reuses the input "
        "buffers)")
    rss_mib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
    emit(f"sched_scale/donation_extra_copies_{n_jobs}jobs",
         grew / table_bytes,
         f"x table ({table_bytes}B); input deleted=True; "
         f"rss={rss_mib}MiB (informational)")
    del out, busy


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny case for CI (seconds, still asserts "
                         "signature equality)")
    ap.add_argument("--full", action="store_true",
                    help="include the J=100k case and the J=64k "
                         "backend A/B")
    args = ap.parse_args()

    if args.smoke:
        # 200 ticks: long enough that the timed region dominates timer and
        # dispatch noise — the bench-regression gate needs stable rows
        cases = ((64, 128, None, 200),)
        backend_cases = [(64, 128, None, 200, 3)]
    else:
        cases = [(100, 256, None, 200), (400, 1024, 64, 200),
                 (2000, 4096, 64, 200), (10_000, 8192, 64, 100)]
        backend_cases = [(10_000, 8192, 64, 40, 3)]
        if args.full:
            cases.append((100_000, 16384, 32, 50))
            # lax-vs-pallas at the kernel's largest table (ops.MAX_JOBS);
            # interpret mode makes the pallas side slow on CPU, so the
            # horizon is short
            backend_cases += [(65_536, 16384, 32, 16, 2)]

    for n_jobs, cpu_total, pass_depth, horizon in cases:
        run_case(n_jobs, cpu_total, pass_depth, horizon)
    for n_jobs, cpu_total, pass_depth, horizon, reps in backend_cases:
        backend_case(n_jobs, cpu_total, pass_depth, horizon, reps)
    lattice_case(*((64, 128, None, 200) if args.smoke
                   else (10_000, 8192, 64, 100)))
    sched_roofline_entry()
    donation_case(*((64, 128, 50) if args.smoke else (2000, 4096, 50)))
    if args.smoke:
        instrumented_case(64, 128, 200)
        profiling_case(horizon=60, capacity=32, segment_len=20)
    else:
        instrumented_case(10_000, 8192, 100)
        profiling_case(horizon=400, capacity=256, segment_len=50)
    _obs_step_summary()
    write_rows("sched_scale")


if __name__ == "__main__":
    main()
