"""Gang placement on nodes (`SchedulerConfig.node_size`, DESIGN.md §Node
placement): the JAX pass, the Python twin and the benchmark's plain
reference (`bench/reference/gang_ref.py`) agree on every compared column,
on seeded random fleets and on hand-made cases that only a node-aware
victim prefix gets right."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, omfs_jax
from repro.core.crcost import MIB, CRCostModel, TieredCRCostModel
from repro.core.types import Job, JobClass, SchedulerConfig, User

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from reference import gang_ref  # noqa: E402

G = 8
TIERS = [dict(save_mib_per_tick=4096, restore_mib_per_tick=8192,
              save_base=1, restore_base=1, capacity_mib=24576),
         dict(save_mib_per_tick=1024, restore_mib_per_tick=2048,
              save_base=2, restore_base=2, capacity_mib=-1)]


def fleet_config(n_nodes, shares, quantum, tiers=()):
    """The reference's configuration dict and the program's config."""
    config = {"cpu_total": n_nodes * G, "tenants": len(shares),
              "shares": list(shares), "cr_overhead": 1,
              "scheduler": {"node_size": G}}
    kw = {}
    if tiers:
        config["cr_tiers"] = [dict(t) for t in tiers]
        kw["cr_tiers"] = TieredCRCostModel(
            tiers=tuple(CRCostModel(**{k: v for k, v in t.items()
                                       if k != "capacity_mib"})
                        for t in tiers),
            capacity_mib=tuple(t["capacity_mib"] for t in tiers))
    cfg = SchedulerConfig(cpu_total=n_nodes * G, quantum=quantum,
                          cr_overhead=1, node_size=G, **kw)
    return config, cfg


def columns(spec):
    """Job columns from ``(user, gpus, work, priority, submit, jclass)``
    rows, ids in the order given (submit order)."""
    rows = np.asarray(spec, np.int64).reshape(-1, 6)
    return {"user": rows[:, 0], "cpus": rows[:, 1], "work": rows[:, 2],
            "priority": rows[:, 3], "submit": rows[:, 4],
            "jclass": rows[:, 5], "state_mib": rows[:, 1] * 1024}


def jobs_of(cols):
    return [Job(user=f"u{u}", cpus=int(c), work=int(w), priority=int(p),
                job_class=JobClass(int(k)), submit_time=int(s),
                state_bytes=int(m) * MIB, id=i)
            for i, (u, c, w, p, k, s, m) in enumerate(zip(
                cols["user"], cols["cpus"], cols["work"], cols["priority"],
                cols["jclass"], cols["submit"], cols["state_mib"]))]


def random_fleet(seed, n_nodes, n_jobs, horizon):
    rng = np.random.default_rng(seed)
    gpus = rng.choice([1, 2, 4, 8, 16, 24], n_jobs,
                      p=[.35, .15, .15, .2, .1, .05])
    return columns(np.stack([
        rng.integers(0, 4, n_jobs), gpus, rng.integers(1, 30, n_jobs),
        rng.integers(0, 3, n_jobs),
        np.sort(rng.integers(0, horizon // 2, n_jobs)),
        rng.choice([0, 1, 2, 2, 2, 2], n_jobs)], axis=1))


def agree(program, ref):
    got = {k: np.asarray(getattr(jax.device_get(program.table), k))
           for k in ("jid", *gang_ref.COMPARED)}
    return gang_ref.mismatches(got, ref.table())


def python_columns(res):
    jobs = res.sim.job_table()
    return {"node": [j.node for j in jobs], "n_frag": [j.n_frag for j in jobs]}


FLEETS = [  # seed, nodes, jobs, horizon, quantum, tiers, pass depth
    (0, 4, 64, 80, 3, (), 16),
    (1, 5, 120, 100, 0, (), 30),
    (2, 6, 200, 120, 5, TIERS, 40),
    (3, 5, 160, 100, 2, TIERS, 200),
]


@pytest.mark.parametrize("policy", ["omfs", "omfs_cheap_victim"])
@pytest.mark.parametrize("fleet", FLEETS, ids=lambda f: f"seed{f[0]}")
def test_random_fleets_agree_with_twin_and_reference(fleet, policy):
    seed, n_nodes, n_jobs, horizon, quantum, tiers, depth = fleet
    shares = [40, 30, 20, 10]
    config, cfg = fleet_config(n_nodes, shares, quantum, tiers)
    cols = random_fleet(seed, n_nodes, n_jobs, horizon)
    users = [User(f"u{i}", s) for i, s in enumerate(shares)]
    jobs = jobs_of(cols)

    jx = engine.simulate(users, jobs, cfg, horizon, policy=policy,
                         backend="jax", pass_depth=depth)
    py = engine.simulate(users, jobs, cfg, horizon, policy=policy,
                         backend="python")
    ref = gang_ref.RefSim(cols, config, policy, quantum=quantum, depth=depth)
    ref.run_all(horizon)
    assert agree(jx, ref) == 0
    assert list(jx.busy) == ref.busy

    # the twin runs the whole queue each tick: compare it with a full pass
    full = engine.simulate(users, jobs, cfg, horizon, policy=policy,
                           backend="jax")
    assert py.signature() == full.signature()
    assert (py.busy_series() == full.busy_series()).all()
    t = jax.device_get(full.table)
    assert python_columns(py) == {"node": list(t.node),
                                  "n_frag": list(t.n_frag)}
    assert py.summary()["frag_evict_admits"] == \
        full.summary()["frag_evict_admits"]
    # the fleets evict, and some evictions free a node that counting
    # would not have picked
    assert jx.summary()["preemptions"] > 0


@pytest.mark.parametrize("fleet", FLEETS[:3], ids=lambda f: f"seed{f[0]}")
def test_random_fleets_stream_agrees_with_reference(fleet):
    seed, n_nodes, n_jobs, horizon, quantum, tiers, depth = fleet
    shares = [40, 30, 20, 10]
    config, cfg = fleet_config(n_nodes, shares, quantum, tiers)
    cols = random_fleet(seed, n_nodes, n_jobs, horizon)
    users = [User(f"u{i}", s) for i, s in enumerate(shares)]
    capacity, seg = n_jobs // 2, 4
    res = engine.simulate_stream(users, iter(jobs_of(cols)), cfg, horizon,
                                 "omfs", capacity=capacity, segment_len=seg,
                                 pass_depth=depth)
    ref = gang_ref.RefSim(cols, config, "omfs", quantum=quantum, depth=depth)
    stats = ref.run_stream(horizon, capacity, seg)
    assert agree(res, ref) == 0
    assert list(res.busy) == ref.busy
    assert {k: res.stream_stats[k] for k in stats} == stats
    assert res.stream_stats["frag_evict_admits"] == int(
        np.asarray(res.table.n_frag).sum())


def test_batched_sweep_places_like_sequential_runs():
    """`simulate_batch` traces the pass with knobs (no per-tick hoist) and
    under `vmap`: every cell matches its sequential run, node included."""
    shares = [40, 30, 20, 10]
    _, cfg = fleet_config(5, shares, 3)
    users = [User(f"u{i}", s) for i, s in enumerate(shares)]
    jobs = jobs_of(random_fleet(1, 5, 120, 100))
    cells = [engine.BatchCell(users=users, jobs=jobs, policy=p, quantum=q,
                              pass_depth=d)
             for p, q, d in [("omfs", 0, 40), ("omfs", 3, 10),
                             ("omfs_cheap_victim", 3, 40)]]
    for c, res in zip(cells, engine.simulate_batch(cells, cfg, 100)):
        seq = engine.simulate(users, jobs, SchedulerConfig(
            cpu_total=cfg.cpu_total, quantum=c.quantum, cr_overhead=1,
            node_size=G), 100, policy=c.policy, backend="jax",
            pass_depth=c.pass_depth)
        a, b = jax.device_get(res.table), jax.device_get(seq.table)
        for f in omfs_jax.JobTable._fields:
            assert np.array_equal(getattr(a, f), getattr(b, f)), f


# -- hand cases: users 0 (the requester) and 1 (the running jobs) ----------

CKPT, NONP = int(JobClass.CHECKPOINTABLE), int(JobClass.NON_PREEMPTIBLE)


def run_three(n_nodes, spec, shares, quantum, horizon):
    """The JAX pass, the Python twin and the reference on one case: they
    must agree, and the JAX table is returned."""
    config, cfg = fleet_config(n_nodes, shares, quantum)
    cols = columns(spec)
    users = [User(f"u{i}", s) for i, s in enumerate(shares)]
    jobs = jobs_of(cols)
    jx = engine.simulate(users, jobs, cfg, horizon, backend="jax")
    py = engine.simulate(users, jobs, cfg, horizon, backend="python")
    ref = gang_ref.RefSim(cols, config, "omfs", quantum=quantum,
                          depth=len(jobs))
    ref.run_all(horizon)
    assert py.signature() == jx.signature()
    assert agree(jx, ref) == 0
    t = jax.device_get(jx.table)
    assert python_columns(py) == {"node": list(t.node),
                                  "n_frag": list(t.n_frag)}
    return t


def test_spread_idle_gpus_make_an_eight_gpu_job_evict_on_one_node():
    # three nodes, 5 GPUs used on each: 9 idle, yet no node takes 8
    t = run_three(3, [(1, 5, 100, 0, 0, CKPT)] * 3
                  + [(0, 8, 100, 0, 3, CKPT)], [50, 50], 2, 6)
    # reach: node 0 frees 8 at the first victim (rank 0)
    assert t.node[3] == 0 and t.state[3] == omfs_jax.RUNNING
    assert list(t.n_preempt) == [1, 0, 0, 0]
    assert list(t.n_frag) == [0, 0, 0, 1]
    assert t.node[0] == 0        # eviction keeps the latest placement


def test_sixteen_gpu_job_takes_the_window_of_least_clearing_rank():
    # one 8-GPU job a node, placed in submit order; victim ranks by
    # priority are 0, 3, 1, 2 on nodes 0..3
    t = run_three(4, [(1, 8, 100, 0, 0, CKPT), (1, 8, 100, 3, 1, CKPT),
                      (1, 8, 100, 1, 2, CKPT), (2, 8, 100, 2, 3, CKPT),
                      (0, 16, 100, 0, 8, CKPT)], [50, 25, 25], 2, 9)
    assert list(t.node) == [0, 1, 2, 3, 2]
    # windows [0,1], [1,2], [2,3] clear at ranks 3, 3, 2: the last wins,
    # where counting would have taken ranks 0 and 1 on nodes 0 and 2
    assert list(t.n_preempt) == [0, 0, 1, 1, 0]
    assert t.state[4] == omfs_jax.RUNNING


def test_multi_node_victim_past_the_window_is_evicted_whole():
    # node 0: the last victim; node 1 frees at tick 1; nodes 2-3: a
    # 16-GPU job, the first victim; node 4: the second
    t = run_three(5, [(1, 8, 100, 5, 0, CKPT), (1, 8, 1, 0, 0, CKPT),
                      (1, 16, 100, 0, 0, CKPT), (2, 8, 100, 0, 0, CKPT),
                      (0, 16, 100, 0, 4, CKPT)], [40, 20, 40], 2, 6)
    assert list(t.node[:4]) == [0, 1, 2, 4]
    # windows clear at ranks 2, 0, 0, 1: the lowest of the ties, [1, 2],
    # takes the multi-node job whole and leaves node 3 free
    assert t.node[4] == 1 and t.state[4] == omfs_jax.RUNNING
    assert list(t.n_preempt) == [0, 0, 1, 0, 0]
    busy = np.where(t.state == omfs_jax.RUNNING, t.cpus, 0).sum()
    assert busy == 8 + 16 + 8


def test_non_evictable_job_makes_its_node_unclearable():
    # nodes 0 and 1 hold an evictable 6-GPU job each; a 2-GPU job still
    # inside its quantum joins node 0 (best fit)
    t = run_three(3, [(1, 6, 100, 0, 0, CKPT), (1, 6, 100, 0, 0, CKPT),
                      (1, 2, 100, 0, 3, CKPT), (0, 16, 100, 0, 4, CKPT)],
                  [80, 20], 2, 5)
    assert list(t.node[:3]) == [0, 1, 0]
    # window [0, 1] would clear at rank 1 but node 0 cannot be cleared;
    # [1, 2] clears at rank 1: counting would have evicted job 0
    assert t.node[3] == 1 and t.state[3] == omfs_jax.RUNNING
    assert list(t.n_preempt) == [0, 1, 0, 0]


# -- the guards ------------------------------------------------------------


def test_table_build_refuses_a_gang_no_node_placement_holds():
    users = [User("u0", 100.0)]
    cfg = SchedulerConfig(cpu_total=32, node_size=8)
    jobs = [Job(user="u0", cpus=12, work=5, id=0)]
    with pytest.raises(ValueError, match="12 GPUs.*node_size=8"):
        omfs_jax.table_from_jobs(jobs, users, cfg.cpu_total, cfg)
    with pytest.raises(ValueError, match="node_size=8"):
        engine.simulate(users, jobs, cfg, 4, backend="python")
    with pytest.raises(ValueError, match="node_size"):
        SchedulerConfig(cpu_total=30, node_size=8)


@pytest.mark.parametrize("policy", ["fcfs", "capping", "static_partition",
                                    "backfill", "backfill_cr"])
def test_other_policies_refuse_nodes(policy):
    users = [User("u0", 100.0)]
    cfg = SchedulerConfig(cpu_total=16, node_size=8)
    jobs = [Job(user="u0", cpus=4, work=5, id=0)]
    for backend in ("python", "jax"):
        with pytest.raises(NotImplementedError, match="node_size"):
            engine.simulate(users, jobs, cfg, 4, policy=policy,
                            backend=backend)
    with pytest.raises(NotImplementedError, match="node_size"):
        engine.simulate_stream(users, iter(jobs), cfg, 4, policy,
                               capacity=4, segment_len=2)


def test_fused_kernel_refuses_nodes():
    users = [User("u0", 100.0)]
    cfg = SchedulerConfig(cpu_total=16, node_size=8,
                          kernel_backend="pallas_interpret")
    jobs = [Job(user="u0", cpus=4, work=5, id=0)]
    with pytest.raises(NotImplementedError, match="node_size"):
        engine.simulate(users, jobs, cfg, 4, backend="jax")


def _scopes(cfg):
    users = [User("A", 50.0), User("B", 50.0)]
    tbl, ent = omfs_jax.table_from_jobs([], users, cfg.cpu_total, cfg,
                                        rows=16)
    runner = engine._jitted_segment_runner(
        cfg, engine.POLICIES["omfs"].jax_factory(8), 2)
    return runner.lower(tbl, ent, jnp.int32(0)).compile().as_text()


def test_untopologized_pass_has_no_node_scopes():
    text = _scopes(SchedulerConfig(cpu_total=32, quantum=2))
    assert "sched.admit" in text
    assert "sched.place_nodes" not in text
    assert "sched.node_victims" not in text
    text = _scopes(SchedulerConfig(cpu_total=32, quantum=2, node_size=8))
    assert "sched.place_nodes" in text and "sched.node_victims" in text
