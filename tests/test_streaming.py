"""Streaming-engine tests (`engine.simulate_stream`): a fixed-capacity
JobTable fed by an arrival iterator, run in jitted segments with host-side
compaction between them, must reproduce the monolithic whole-table run
bit-for-bit whenever every arrival finds a slot — including under
eviction churn, where queue/victim tie-breaks ride the ``jid`` column
through recycled slots — and must degrade to deferred (late) arrivals,
not errors, when capacity runs out.
"""
import contextlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, omfs_jax
from repro.core.crcost import UNBOUNDED, CRCostModel, TieredCRCostModel
from repro.core.types import Job, JobClass, SchedulerConfig, User
from repro.core.workload import (WorkloadSpec, arrival_stream,
                                 endless_arrivals, make_users)
from repro.obs import profile as obs_profile

CAPACITY = 12
N_JOBS = 10 * CAPACITY


def _conveyor_jobs():
    """Deterministic conveyor: ten× more jobs than table slots, arrivals
    paced so the live set stays well under CAPACITY, plus periodic entitled
    claims from user A that land when B's flood holds >half the machine —
    each claim goes through the evict path (slot-recycling under C/R
    churn)."""
    users = [User("A", 50.0), User("B", 50.0)]
    jobs = [Job(user="B", cpus=4, work=8, priority=i % 4,
                job_class=JobClass.CHECKPOINTABLE,
                submit_time=3 * i, state_bytes=(64 + i % 5) << 20)
            for i in range(N_JOBS)]
    for k in range(10):
        jobs.append(Job(user="A", cpus=8, work=6,
                        job_class=JobClass.CHECKPOINTABLE,
                        submit_time=25 + 30 * k, state_bytes=32 << 20))
    horizon = 3 * N_JOBS + 60
    return users, jobs, horizon


def _cfg(tiered=False):
    if not tiered:
        return SchedulerConfig(cpu_total=16, quantum=2, cr_overhead=1)
    tiers = TieredCRCostModel(
        tiers=(CRCostModel(save_mib_per_tick=256, restore_mib_per_tick=256),
               CRCostModel(save_mib_per_tick=32, restore_mib_per_tick=32,
                           save_base=1, restore_base=1)),
        capacity_mib=(64, UNBOUNDED))
    return SchedulerConfig(cpu_total=16, quantum=2, cr_overhead=1,
                           cr_tiers=tiers)


def test_stream_matches_monolithic_at_10x_capacity():
    users, jobs, horizon = _conveyor_jobs()
    cfg = _cfg()
    mono = engine.simulate(users, jobs, cfg, horizon,
                           policy="omfs", backend="jax")
    res = engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                                 capacity=CAPACITY, segment_len=16)
    stats = res.stream_stats
    # the bounded-memory premise actually held: never more live jobs than
    # slots, nothing deferred, every job flowed through the small table
    assert stats["deferrals"] == 0 and stats["dropped"] == 0
    assert stats["peak_live"] <= CAPACITY
    assert stats["inserted"] == len(jobs) >= 10 * CAPACITY
    assert res.table.cpus.shape[0] == len(jobs)
    assert int(np.asarray(mono.table.n_preempt).sum()) > 0, \
        "fixture must exercise eviction under slot recycling"
    # ...and the merged result is the monolithic run, bit for bit
    assert omfs_jax.tables_equal(res.table, mono.table)
    assert np.array_equal(np.asarray(res.table.n_spill),
                          np.asarray(mono.table.n_spill))
    assert np.array_equal(res.busy_series(), mono.busy_series())
    assert res.signature() == mono.signature()
    assert res.summary()["goodput"] == mono.summary()["goodput"]


def test_stream_eviction_churn_tiered_costs():
    """Eviction/restart churn with tiered snapshot placement: recycled
    slots must not perturb victim ordering (jid tie-break) or spill
    accounting."""
    users, jobs, horizon = _conveyor_jobs()
    cfg = _cfg(tiered=True)
    mono = engine.simulate(users, jobs, cfg, horizon,
                           policy="omfs_cheap_victim", backend="jax")
    assert int(np.asarray(mono.table.n_preempt).sum()) > 0, \
        "fixture must actually evict"
    assert int(np.asarray(mono.table.n_spill).sum()) > 0, \
        "fixture must actually spill"
    # tiered C/R overhead stretches slot residency; 16 slots keep the
    # live set inside capacity (deferrals==0 is this test's precondition)
    res = engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                                 "omfs_cheap_victim",
                                 capacity=16, segment_len=16)
    assert res.stream_stats["deferrals"] == 0
    assert omfs_jax.tables_equal(res.table, mono.table)
    assert np.array_equal(np.asarray(res.table.n_spill),
                          np.asarray(mono.table.n_spill))
    assert np.array_equal(res.busy_series(), mono.busy_series())


def test_stream_compiles_one_segment_program():
    """N segments, ONE compiled scan: the segment start tick is traced, so
    `_cache_size()` stays 1 however long the stream runs (the acceptance
    criterion the jaxpr/retrace audit re-checks)."""
    users, jobs, horizon = _conveyor_jobs()
    cfg = _cfg()
    res = engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                                 capacity=CAPACITY, segment_len=32)
    assert res.stream_stats["segments"] >= 8
    pass_fn = engine.POLICIES["omfs"].jax_factory(None)
    runner = engine._jitted_segment_runner(cfg, pass_fn, 32)
    assert runner._cache_size() == 1


def test_stream_capacity_exhaustion_defers_not_crashes():
    """More live jobs than slots: surplus arrivals are deferred to later
    boundaries (counted), the run completes, and accounting stays
    consistent."""
    users, jobs, horizon = _conveyor_jobs()
    cfg = _cfg()
    res = engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                                 capacity=4, segment_len=32)
    stats = res.stream_stats
    assert stats["deferrals"] > 0
    assert stats["peak_live"] <= 4
    assert res.table.cpus.shape[0] == stats["inserted"]
    assert stats["inserted"] + stats["dropped"] <= len(jobs)
    assert res.busy_series().shape == (horizon,)


def test_endless_arrivals_feed_contract_and_bounded_memory():
    """The unbounded generator yields sorted arrivals forever; the stream
    consumes exactly the prefix due before the horizon and holds at most
    `capacity` rows."""
    spec = WorkloadSpec(n_users=3, horizon=120, cpu_total=32, seed=13,
                        arrival_rate=0.05, mean_work=10)
    users = make_users(spec)
    feed = endless_arrivals(spec, users)
    peek = list(itertools.islice(endless_arrivals(spec, users), 300))
    submits = [j.submit_time for j in peek]
    assert submits == sorted(submits), "endless_arrivals must be sorted"
    assert submits[-1] > spec.horizon, "must cross epoch boundaries"
    cfg = SchedulerConfig(cpu_total=32, quantum=3)
    horizon = 3 * spec.horizon          # several generator epochs
    res = engine.simulate_stream(users, feed, cfg, horizon,
                                 capacity=64, segment_len=40)
    stats = res.stream_stats
    assert stats["peak_live"] <= 64
    # every inserted job is accounted for in the merged table
    assert res.table.cpus.shape[0] == stats["inserted"] > 0
    # arrivals stopped at the horizon even though the feed is infinite
    assert int(np.asarray(res.table.submit).max()) < horizon


#: (tiered, policy, capacity): the tiered conveyor's C/R overhead stretches
#: slot residency, so it needs 16 slots to defer nothing
BOUNDARY_CASES = {"untiered": (False, "omfs", CAPACITY),
                  "tiered": (True, "omfs_cheap_victim", 16)}


@pytest.mark.parametrize("case", sorted(BOUNDARY_CASES))
def test_stream_boundary_makes_no_implicit_transfer(case, monkeypatch):
    """The boundary and the final extraction put what the device needs
    explicitly: under a host-to-device transfer guard that disallows
    implicit transfers (and only around those two spans, not the segment
    dispatch) the stream runs, bit-identical to the monolithic run."""
    tiered, policy, capacity = BOUNDARY_CASES[case]
    with jax.transfer_guard_host_to_device("disallow"):
        with pytest.raises(Exception, match="host-to-device"):
            jnp.asarray([1, 2], jnp.int32)   # the guard bites here
    real = obs_profile.span

    @contextlib.contextmanager
    def guarded(name, *args, **kw):
        with real(name, *args, **kw) as note:
            if name in ("stream.boundary", "stream.extract"):
                with jax.transfer_guard_host_to_device("disallow"):
                    yield note
            else:
                yield note
    monkeypatch.setattr(obs_profile, "span", guarded)

    users, jobs, horizon = _conveyor_jobs()
    cfg = _cfg(tiered)
    mono = engine.simulate(users, jobs, cfg, horizon, policy=policy,
                           backend="jax")
    res = engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                                 policy, capacity=capacity, segment_len=16)
    # boundaries where arrivals and completions share the round
    finish = np.asarray(mono.table.finish)
    submit = np.asarray(mono.table.submit)
    shared = [t0 for t0 in range(16, horizon, 16)
              if ((finish >= t0 - 16) & (finish < t0)).any()
              and ((submit >= t0) & (submit < t0 + 16)).any()]
    assert len(shared) > 5
    assert res.stream_stats["deferrals"] == 0
    assert res.stream_stats["inserted"] == len(jobs)
    for f in omfs_jax.JobTable._fields:
        assert np.array_equal(np.asarray(getattr(res.table, f)),
                              np.asarray(getattr(mono.table, f))), f
    assert np.array_equal(res.busy_series(), mono.busy_series())
    assert res.signature() == mono.signature()


def _eager_table(jobs, users, cfg, rows):
    """The arrival block as the boundary once built it, one eager device
    array per column and then `pad_table`: the reference for the numpy
    build of `omfs_jax.table_from_jobs`."""
    uidx = {u.name: i for i, u in enumerate(users)}
    j = sorted(jobs, key=lambda x: x.id)
    n, n_tiers = len(j), cfg.n_cost_tiers
    arr = lambda f: jnp.asarray([f(x) for x in j], jnp.int32)
    lat = lambda f: jnp.asarray(
        [[f(x, k) for k in range(n_tiers)] for x in j],
        jnp.int32).reshape(n, n_tiers)
    minus1 = jnp.full((n,), -1, jnp.int32)
    zero = jnp.zeros((n,), jnp.int32)
    tbl = omfs_jax.JobTable(
        jid=arr(lambda x: x.id), user=arr(lambda x: uidx[x.user]),
        cpus=arr(lambda x: x.cpus), work=arr(lambda x: x.work),
        priority=arr(lambda x: x.priority),
        jclass=arr(lambda x: int(x.job_class)),
        submit=arr(lambda x: x.submit_time),
        state_mib=arr(lambda x: x.state_mib),
        cost_save_lat=lat(
            lambda x, k: cfg.eviction_save_cost(x.state_mib, k)),
        cost_rsave_lat=lat(
            lambda x, k: cfg.eviction_save_cost(x.state_mib, k,
                                                recurrent=True)),
        cost_restore_lat=lat(
            lambda x, k: cfg.restart_restore_cost(x.state_mib, k)),
        state=jnp.full((n,), omfs_jax.UNSUB, jnp.int32), progress=zero,
        run_start=minus1, first_start=minus1, finish=minus1,
        n_preempt=zero, n_ckpt=zero, overhead=zero,
        backfilled=arr(lambda x: int(x.backfilled)), ckpt_tier=minus1,
        n_spill=zero)
    return omfs_jax.pad_table(tbl, rows)


@pytest.mark.parametrize("tiered", [False, True],
                         ids=["untiered", "tiered"])
@pytest.mark.parametrize("n_jobs", [0, 1, 40])
def test_host_build_matches_the_eager_block(n_jobs, tiered):
    users, jobs, _ = _conveyor_jobs()
    jobs = jobs[::-1][:n_jobs]          # out of id order: the build sorts
    cfg = _cfg(tiered)
    n_tiers = cfg.n_cost_tiers
    rows = n_jobs + 5
    host, host_ent = omfs_jax.table_from_jobs(jobs, users, cfg.cpu_total,
                                              cfg, rows=rows, host=True)
    assert isinstance(host_ent, np.ndarray) and host_ent.dtype == np.int32
    eager = _eager_table(jobs, users, cfg, rows)
    # without node_size the table has no node columns at all
    assert host.node is None and host.n_frag is None
    for f in omfs_jax.JobTable._fields[:-2]:
        col = getattr(host, f)
        assert isinstance(col, np.ndarray) and col.dtype == np.int32, f
        assert col.shape == ((rows, n_tiers) if f.endswith("_lat")
                             else (rows,)), f
        assert np.array_equal(col, np.asarray(getattr(eager, f))), f
        assert (col[n_jobs:] == omfs_jax._PAD_VALUES.get(f, 0)).all(), f
    # table_from_jobs is that table put on the device, with the same
    # types as ever: jax int32 columns and entitlements
    for padded in (None, rows):
        tbl, ent = omfs_jax.table_from_jobs(jobs, users, cfg.cpu_total, cfg,
                                            rows=padded)
        assert isinstance(tbl, omfs_jax.JobTable)
        want, _ = omfs_jax.table_from_jobs(jobs, users, cfg.cpu_total, cfg,
                                           rows=padded, host=True)
        for f in omfs_jax.JobTable._fields[:-2]:
            col = getattr(tbl, f)
            assert isinstance(col, jax.Array) and col.dtype == jnp.int32, f
            assert np.array_equal(np.asarray(col), getattr(want, f)), f
        assert isinstance(ent, jax.Array) and ent.dtype == jnp.int32
        assert np.array_equal(
            np.asarray(ent),
            np.asarray(omfs_jax.entitlements(users, cfg.cpu_total)))
    # the numpy pad mask of the read-back table is the device one
    mask = omfs_jax.host_is_pad(jax.device_get(tbl))
    assert isinstance(mask, np.ndarray)
    assert np.array_equal(mask, np.asarray(omfs_jax.is_pad(tbl)))
    assert int(mask.sum()) == rows - n_jobs


@pytest.mark.parametrize("tiered", [False, True],
                         ids=["untiered", "tiered"])
def test_insert_packed_is_insert_rows(tiered):
    """One packed transfer and `insert_packed` write what `insert_rows`
    writes from the same rows, slots and valid."""
    users, jobs, _ = _conveyor_jobs()
    cfg = _cfg(tiered)
    rows = 16
    tbl, _ = omfs_jax.table_from_jobs(jobs[:rows], users, cfg.cpu_total, cfg)
    tbl = tbl._replace(state=tbl.state.at[:5].set(omfs_jax.DONE))
    block, _ = omfs_jax.table_from_jobs(jobs[rows:rows + 3], users,
                                        cfg.cpu_total, cfg, rows=rows,
                                        host=True)
    slots = np.concatenate([np.arange(3, 8), np.arange(3), np.arange(8, 16)])
    valid = np.arange(rows) < 5
    packed = omfs_jax.pack_insert(block, slots, valid)
    assert packed.dtype == np.int32
    assert packed.shape == (rows, 19 + 3 * cfg.n_cost_tiers + 2)
    want = omfs_jax.insert_rows(
        jax.tree_util.tree_map(jnp.copy, tbl), jnp.asarray(slots, jnp.int32),
        jax.device_put(block), jnp.asarray(valid))
    got = omfs_jax.insert_packed(tbl, jax.device_put(packed))
    for f in omfs_jax.JobTable._fields:
        assert np.array_equal(np.asarray(getattr(got, f)),
                              np.asarray(getattr(want, f))), f
