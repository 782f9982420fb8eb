"""Property tests for the fused victim-select/placement kernel family
(`kernels.sched_select`) and its `SchedulerConfig.kernel_backend` dispatch:
the pallas path must be bit-identical to the lax path — planned victims,
placement tiers, spill counts, events — for every registered policy, under
random tiered C/R costs, at J ∈ {64, 10k}, and through every engine entry
point (`simulate`, `simulate_matrix`, `simulate_batch`, `simulate_stream`).
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine, omfs_jax
from repro.core.crcost import UNBOUNDED, CRCostModel, TieredCRCostModel
from repro.core.types import SchedulerConfig
from repro.core.workload import WorkloadSpec, arrival_stream, make_jobs, make_users
from repro.kernels.sched_select.ops import plan_evictions_fused
from repro.kernels.sched_select.ref import plan_evictions_ref

POLICY_NAMES = sorted(engine.POLICIES)


def _pallas(cfg: SchedulerConfig) -> SchedulerConfig:
    return dataclasses.replace(cfg, kernel_backend="pallas_interpret")


def _workload(seed, n_users=3, cpu_total=32, n_jobs=35, horizon=100):
    spec = WorkloadSpec(n_users=n_users, horizon=horizon, cpu_total=cpu_total,
                        seed=seed, arrival_rate=0.15, mean_work=25,
                        class_mix=(0.15, 0.35, 0.5))
    users = make_users(spec)
    jobs = make_jobs(spec, users)[:n_jobs]
    return users, jobs


def _sized_workload(n_jobs, cpu_total, seed=1, n_users=16):
    """Workload that actually reaches ``n_jobs`` rows (bench generator)."""
    gen_horizon = max(200, int(1.5 * n_jobs / (n_users * 0.5)))
    spec = WorkloadSpec(n_users=n_users, horizon=gen_horizon,
                        cpu_total=cpu_total, seed=seed, arrival_rate=0.5,
                        mean_work=60)
    users = make_users(spec)
    jobs = make_jobs(spec, users)[:n_jobs]
    assert len(jobs) == n_jobs
    return users, jobs


def _tiered_cfg(quantum=3, cap0=64, save_bw=256, spill_bw=32):
    tiers = TieredCRCostModel(
        tiers=(CRCostModel(save_mib_per_tick=save_bw,
                           restore_mib_per_tick=save_bw),
               CRCostModel(save_mib_per_tick=spill_bw,
                           restore_mib_per_tick=spill_bw,
                           save_base=1, restore_base=1)),
        capacity_mib=(cap0, UNBOUNDED))
    return SchedulerConfig(cpu_total=32, quantum=quantum, cr_overhead=1,
                           cr_tiers=tiers)


def _assert_results_equal(a, b):
    """Full EngineResult bit-identity: table (spill counts included),
    busy series, and — when recorded — the typed event log."""
    assert omfs_jax.tables_equal(a.table, b.table)
    assert np.array_equal(a.busy_series(), b.busy_series())
    assert np.array_equal(np.asarray(a.table.n_spill),
                          np.asarray(b.table.n_spill))
    if a.event_counts is not None or b.event_counts is not None:
        assert np.array_equal(np.asarray(a.event_counts),
                              np.asarray(b.event_counts))
        assert a.events == b.events
        assert a.events_dropped_total() == b.events_dropped_total()


# ---------------------------------------------------------------------------
# Kernel unit level: fused pallas_call vs the lexsort/scan reference
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_kernel_matches_reference_fuzz(seed):
    """Random bare columns at random tier counts T ∈ {2, 3, 4}, every
    static variant (faithful/cheap × untiered/unbounded/bounded): planned
    victims, feasibility bit, and T-tier lattice placement must match the
    lexsort reference exactly."""
    rng = np.random.default_rng(seed)
    j = int(rng.integers(1, 300))
    n_tiers = int(rng.integers(2, 5))
    save_lat = rng.integers(0, 60, (j, n_tiers)).astype(np.int32)
    cols = dict(
        prio=rng.integers(0, 5, j).astype(np.int32),
        run_start=rng.integers(-1, 40, j).astype(np.int32),
        jid=rng.permutation(j).astype(np.int32),
        key_cost=save_lat[:, 0],
        evictable=rng.random(j) < 0.5,
        cpus=rng.integers(1, 8, j).astype(np.int32),
        state_mib=rng.integers(0, 64, j).astype(np.int32),
        is_ckpt=rng.random(j) < 0.7,
        save_lat=save_lat,
    )
    occ = rng.integers(0, 128, n_tiers).astype(np.int32)
    # random finite caps with sporadic unbounded (-1) tiers; the last
    # tier is always the unbounded spill target (model invariant)
    cap = rng.integers(0, 256, n_tiers).astype(np.int32)
    cap[rng.random(n_tiers) < 0.3] = -1
    cap[-1] = -1
    scalars = dict(idle=int(rng.integers(0, 20)),
                   cpus_needed=int(rng.integers(0, 48)),
                   occ=occ, cap=cap)
    for cheap in (False, True):
        for tiered, bounded in ((False, False), (True, False), (True, True)):
            sc = dict(scalars)
            if not bounded:
                sc["cap"] = np.full(n_tiers, -1, np.int32)
            got = plan_evictions_fused(
                *cols.values(), *sc.values(),
                cheap=cheap, tiered=tiered, bounded=bounded, interpret=True)
            want = plan_evictions_ref(
                *cols.values(), *sc.values(),
                cheap=cheap, tiered=tiered, bounded=bounded)
            for name, g, w in zip(("planned", "enough", "tier"),
                                  got, want):
                assert np.array_equal(np.asarray(g), np.asarray(w)), (
                    f"{name} cheap={cheap} tiered={tiered} bounded={bounded}")


# ---------------------------------------------------------------------------
# Engine level: every registered policy, lax vs pallas_interpret
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", POLICY_NAMES)
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000), quantum=st.integers(0, 8))
def test_policy_lax_pallas_identical(policy, seed, quantum):
    users, jobs = _workload(seed)
    if not jobs:
        return
    cfg = SchedulerConfig(cpu_total=32, quantum=quantum, cr_overhead=2)
    lax = engine.simulate(users, jobs, cfg, 100, policy=policy,
                          backend="jax", record_events=True)
    pal = engine.simulate(users, jobs, _pallas(cfg), 100, policy=policy,
                          backend="jax", record_events=True)
    _assert_results_equal(lax, pal)


@pytest.mark.parametrize("policy", ["omfs", "omfs_cheap_victim", "backfill_cr"])
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000), quantum=st.integers(1, 6),
       cap0=st.integers(0, 256), save_bw=st.integers(32, 2048),
       spill_bw=st.integers(16, 512))
def test_tiered_costs_lax_pallas_identical(policy, seed, quantum, cap0,
                                           save_bw, spill_bw):
    """Random tiered C/R cost models: placement tiers (ckpt_tier), spill
    counts, and charged overheads must match across backends — the greedy
    in-kernel placement against the lax.scan."""
    users, jobs = _workload(seed)
    if not jobs:
        return
    cfg = _tiered_cfg(quantum, cap0, save_bw, spill_bw)
    lax = engine.simulate(users, jobs, cfg, 100, policy=policy, backend="jax")
    pal = engine.simulate(users, jobs, _pallas(cfg), 100, policy=policy,
                          backend="jax")
    _assert_results_equal(lax, pal)
    assert np.array_equal(np.asarray(lax.table.ckpt_tier),
                          np.asarray(pal.table.ckpt_tier))


def test_acceptance_j64_all_policies_tiered():
    """J=64: all 7 policies, tiered costs live, events recorded — full
    EngineResult bit-identity, with evictions + spills actually exercised
    (uneven arrivals so early over-entitlement admits become victims)."""
    spec = WorkloadSpec(n_users=3, horizon=400, cpu_total=32, seed=5,
                        arrival_rate=0.1, mean_work=40,
                        class_mix=(0.1, 0.2, 0.7))
    users = make_users(spec)
    jobs = make_jobs(spec, users)[:64]
    assert len(jobs) == 64
    cfg = _tiered_cfg(quantum=2, cap0=8)
    preempts = spills = 0
    for policy in POLICY_NAMES:
        lax = engine.simulate(users, jobs, cfg, 120, policy=policy,
                              backend="jax", record_events=True)
        pal = engine.simulate(users, jobs, _pallas(cfg), 120, policy=policy,
                              backend="jax", record_events=True)
        _assert_results_equal(lax, pal)
        preempts += int(np.asarray(pal.table.n_preempt).sum())
        spills += int(np.asarray(pal.table.n_spill).sum())
    assert preempts > 0, "fixture never hit the eviction machinery"
    assert spills > 0, "fixture never exercised tiered spill accounting"


def test_acceptance_j10k_all_policies_matrix():
    """J=10k: all 7 policies through ONE compiled `simulate_matrix` per
    backend (per-policy results are bit-identical to `simulate` by the
    matrix contract), pass_depth-bounded like the scale benchmarks."""
    users, jobs = _sized_workload(10_000, cpu_total=64)
    cfg = SchedulerConfig(cpu_total=64, quantum=2, cr_overhead=1)
    lax = engine.simulate_matrix(users, jobs, cfg, 20, pass_depth=16)
    pal = engine.simulate_matrix(users, jobs, _pallas(cfg), 20, pass_depth=16)
    preempts = 0
    for a, b in zip(lax, pal):
        assert omfs_jax.tables_equal(a.table, b.table)
        assert np.array_equal(a.busy_series(), b.busy_series())
        preempts += int(np.asarray(b.table.n_preempt).sum())
    assert preempts > 0, "fixture never hit the eviction machinery"


# ---------------------------------------------------------------------------
# Batched / streaming engines
# ---------------------------------------------------------------------------


def test_simulate_batch_cells_pallas():
    """A policy × quantum-knob grid of batch cells under the pallas backend
    equals the same batch under lax, cell by cell (knob overrides force the
    traced-quantum path, where the per-tick hoist must stay disabled)."""
    users, jobs = _workload(seed=5)
    cfg = _tiered_cfg(quantum=3)
    cells = [engine.BatchCell(users=users, jobs=jobs, policy=p, quantum=q)
             for p in ("omfs", "omfs_cheap_victim", "backfill_cr")
             for q in (1, 4)]
    lax = engine.simulate_batch(cells, cfg, 80)
    pal = engine.simulate_batch(cells, _pallas(cfg), 80)
    for a, b in zip(lax, pal):
        assert omfs_jax.tables_equal(a.table, b.table)
        assert np.array_equal(a.busy_series(), b.busy_series())


def test_simulate_stream_pallas():
    users, jobs = _workload(seed=9, n_jobs=60, horizon=120)
    cfg = _tiered_cfg(quantum=2)
    kw = dict(capacity=24, segment_len=16, policy="omfs")
    lax = engine.simulate_stream(users, arrival_stream(jobs), cfg, 120, **kw)
    pal = engine.simulate_stream(users, arrival_stream(jobs), _pallas(cfg),
                                 120, **kw)
    assert lax.signature() == pal.signature()
    assert np.array_equal(lax.busy_series(), pal.busy_series())
    assert lax.stream_stats == pal.stream_stats


def test_reference_pass_pallas():
    """The un-optimized reference pass dispatches too (`_try_admit`)."""
    users, jobs = _workload(seed=3)
    cfg = SchedulerConfig(cpu_total=32, quantum=2, cr_overhead=1)
    t_lax, b_lax = omfs_jax.simulate_jax(users, jobs, cfg, 80,
                                         incremental=False)
    t_pal, b_pal = omfs_jax.simulate_jax(users, jobs, _pallas(cfg), 80,
                                         incremental=False)
    assert omfs_jax.tables_equal(t_lax, t_pal)
    assert np.array_equal(np.asarray(b_lax), np.asarray(b_pal))


# ---------------------------------------------------------------------------
# Dispatch contract
# ---------------------------------------------------------------------------


def test_pallas_raises_off_tpu():
    """``kernel_backend="pallas"`` compiles the kernel for the TPU or
    raises: away from a TPU it never falls back to interpret mode."""
    import jax
    assert jax.default_backend() != "tpu"
    users, jobs = _workload(seed=1)
    cfg = SchedulerConfig(cpu_total=32, quantum=2, kernel_backend="pallas")
    with pytest.raises(ValueError, match="interpret"):
        engine.simulate(users, jobs, cfg, 60, policy="omfs", backend="jax")


def test_kernel_rejects_tables_above_vmem_limit():
    """Above `ops.MAX_JOBS` rows the kernel raises, naming the limit,
    instead of falling back to the lax path."""
    from repro.kernels.sched_select.ops import MAX_JOBS
    j = MAX_JOBS + 1
    z = np.zeros(j, np.int32)
    with pytest.raises(ValueError, match=str(MAX_JOBS)):
        plan_evictions_fused(z, z, z, z, z.astype(bool), z, z,
                             z.astype(bool), z.reshape(j, 1), 0, 0,
                             np.zeros(1, np.int32), np.full(1, -1, np.int32),
                             interpret=True)


def test_unknown_backend_raises():
    users, jobs = _workload(seed=1)
    cfg = SchedulerConfig(cpu_total=32, kernel_backend="cuda")
    with pytest.raises(ValueError, match="kernel_backend"):
        engine.simulate(users, jobs, cfg, 10, policy="omfs", backend="jax")
