"""Unified scheduling engine: one tick kernel, pluggable policies, two backends.

The tick protocol is defined ONCE here and shared by every consumer:

  1. arrivals   — jobs with ``submit_time <= t`` become PENDING,
  2. progress   — every running job accrues one work unit; completed jobs
                  free their CPUs,
  3. scheduling — one policy pass over the pending-queue snapshot,
  4. metrics    — per-tick accounting (busy CPUs, per-user usage).

``tick_python`` runs it over `core.types.ClusterState` with any Python
policy (`core.omfs.scheduler_pass`, `core.baselines.*`, or user callables);
``tick_jax`` runs the identical semantics over the fixed-size `JobTable`
(`core.omfs_jax`) with any vectorized pass.  `core.simulator`,
`core.omfs_jax.simulate_jax`, and `cluster.executor.ClusterExecutor` are
thin adapters over these two kernels — there is no other tick loop in the
repo (DESIGN.md §Engine).

``simulate(users, jobs, cfg, horizon, policy=..., backend=...)`` is the
single entry point: every registered policy runs on every backend, and
`EngineResult.signature()` is directly comparable across backends, which is
what the per-policy Python-vs-JAX property tests assert.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core import omfs_jax, policies_jax
from repro.core.baselines import ALL_BASELINES
from repro.core.omfs import Decision, cheap_victim_pass, scheduler_pass
from repro.core.placement import gang_nodes
from repro.core.types import ClusterState, Job, JobState, SchedulerConfig, User

PythonPolicy = Callable[[ClusterState], List[Decision]]
# JAX policy contract: pass_fn(cfg, entitled[U], t, JobTable) -> JobTable
JaxPass = Callable[[SchedulerConfig, jax.Array, jax.Array, "omfs_jax.JobTable"],
                   "omfs_jax.JobTable"]
JaxPassFactory = Callable[[Optional[int]], JaxPass]


# ---------------------------------------------------------------------------
# Policy registry: every policy names its Python pass and its JAX-pass factory
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PolicySpec:
    name: str
    python_pass: PythonPolicy
    jax_factory: JaxPassFactory


POLICIES: Dict[str, PolicySpec] = {}


def register_policy(name: str, python_pass: PythonPolicy,
                    jax_factory: JaxPassFactory) -> PolicySpec:
    spec = PolicySpec(name, python_pass, jax_factory)
    POLICIES[name] = spec
    return spec


register_policy("omfs", scheduler_pass,
                lambda pass_depth=None: omfs_jax.make_omfs_pass(pass_depth))
# beyond-paper OMFS variant: size-aware victim selection — evict the
# cheapest-to-checkpoint victims first (DESIGN.md §Tier placement)
register_policy(
    "omfs_cheap_victim", cheap_victim_pass,
    lambda pass_depth=None: omfs_jax.make_omfs_pass(pass_depth,
                                                    cheap_victims=True))
for _name, _factory in policies_jax.JAX_BASELINES.items():
    register_policy(_name, ALL_BASELINES[_name], _factory)


#: the policies that place jobs on nodes (`SchedulerConfig.node_size`)
NODE_POLICIES = ("omfs", "omfs_cheap_victim")


def _check_nodes(config: SchedulerConfig, policies) -> None:
    """With ``node_size`` set, only the OMFS passes place jobs on nodes
    (the fused kernel does not either: `omfs_jax.make_omfs_pass`); every
    other policy would count GPUs as one pool, so it raises instead."""
    if config.node_size is None:
        return
    for p in policies:
        if isinstance(p, str) and p not in NODE_POLICIES:
            raise NotImplementedError(
                f"policy {p!r} does not place jobs on nodes; with "
                f"SchedulerConfig.node_size set use one of {NODE_POLICIES}")


def _resolve_python(policy: Union[str, PythonPolicy]) -> PythonPolicy:
    if callable(policy):
        return policy
    if policy not in POLICIES:
        raise ValueError(
            f"unknown policy {policy!r}; known: {sorted(POLICIES)}")
    return POLICIES[policy].python_pass


# ---------------------------------------------------------------------------
# The tick kernel — Python backend
# ---------------------------------------------------------------------------


def tick_python(
    state: ClusterState,
    policy: PythonPolicy,
    *,
    work_fn: Optional[Callable[[Job], None]] = None,
    on_complete: Optional[Callable[[Job], None]] = None,
) -> Tuple[List[Decision], List[Tuple[Job, JobState, JobState]]]:
    """One tick at ``state.time``: arrivals -> progress -> policy pass.

    ``work_fn(job)`` is called for each running job before its progress
    accrues (the executor runs real optimizer steps here); ``on_complete``
    fires when a job finishes.  Returns the pass's decisions plus the state
    transitions it caused, ``[(job, was, now), ...]``, so adapters can react
    (checkpoint on eviction, restore on restart) without re-deriving them.
    """
    t = state.time
    # 1. arrivals
    for j in state.jobs.values():
        if j.state == JobState.UNSUBMITTED and j.submit_time <= t:
            j.state = JobState.PENDING
    # 2. progress + completions (jobs that ran during the previous tick)
    for j in state.running_jobs():
        if work_fn is not None:
            work_fn(j)
        j.progress += 1
        if j.progress >= j.work + j.overhead:
            j.state = JobState.DONE
            j.finish_time = t
            if on_complete is not None:
                on_complete(j)
    # 3. scheduling pass, with transition capture
    pre = {jid: j.state for jid, j in state.jobs.items()}
    decisions = policy(state)
    transitions = [
        (j, pre[jid], j.state)
        for jid, j in state.jobs.items() if j.state != pre[jid]
    ]
    return decisions, transitions


# ---------------------------------------------------------------------------
# The tick kernel — JAX backend (same four steps over the JobTable)
# ---------------------------------------------------------------------------


def tick_jax(cfg: SchedulerConfig, ent: jax.Array, tbl: "omfs_jax.JobTable",
             t: jax.Array, policy_pass: JaxPass,
             knobs: Optional["omfs_jax.Knobs"] = None
             ) -> "omfs_jax.JobTable":
    # 1. arrivals
    arrived = (tbl.state == omfs_jax.UNSUB) & (tbl.submit <= t)
    tbl = tbl._replace(state=jnp.where(arrived, omfs_jax.PENDING, tbl.state))
    # 2. progress + completions
    running = tbl.state == omfs_jax.RUNNING
    progress = tbl.progress + running.astype(jnp.int32)
    done = running & (progress >= tbl.work + tbl.overhead)
    tbl = tbl._replace(
        progress=progress,
        state=jnp.where(done, omfs_jax.DONE, tbl.state),
        finish=jnp.where(done, t, tbl.finish),
    )
    # 3. scheduling pass over the submitted queue snapshot; ``knobs`` (the
    # batched sweep's traced quantum/depth overrides) is only forwarded when
    # set, so 4-arg custom passes keep working and the sequential trace is
    # byte-identical to the pre-batching program
    if knobs is None:
        return policy_pass(cfg, ent, t, tbl)
    return policy_pass(cfg, ent, t, tbl, knobs)


def _tick_step(cfg: SchedulerConfig, ent: jax.Array,
               tbl: "omfs_jax.JobTable", t: jax.Array, pass_fn: JaxPass,
               knobs: Optional["omfs_jax.Knobs"] = None):
    """One scan step shared by ALL jitted runners (per-policy, matrix, and
    batched): the tick plus the per-tick busy reduction (protocol step 4) —
    defined once so `simulate`, `simulate_matrix`, and `simulate_batch`
    cannot drift apart."""
    tbl = tick_jax(cfg, ent, tbl, t, pass_fn, knobs)
    busy = jnp.sum(jnp.where(tbl.state == omfs_jax.RUNNING, tbl.cpus, 0))
    return tbl, busy


@functools.lru_cache(maxsize=128)
def _jitted_runner(cfg: SchedulerConfig, pass_fn: JaxPass, horizon: int):
    """One jitted scan per (cfg, pass, horizon): repeated `simulate` calls
    reuse the compilation (pass factories are memoized for the same reason —
    a fresh closure per call would defeat every warmup).

    The input table is DONATED: XLA reuses its buffers for the output, so a
    large-J sweep holds one table copy, not two.  Callers hand over a table
    they built for the call (`run_jax`) or an explicit copy."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(tbl, ent):
        def step(tbl, t):
            return _tick_step(cfg, ent, tbl, t, pass_fn)

        return jax.lax.scan(step, tbl, jnp.arange(horizon, dtype=jnp.int32))

    return run


def run_jax(users: List[User], jobs: List[Job], cfg: SchedulerConfig,
            horizon: int, pass_fn: JaxPass
            ) -> Tuple["omfs_jax.JobTable", jax.Array]:
    """Scan the jitted tick kernel over ``horizon`` ticks.

    Returns (final JobTable, busy[t] series); step 4 of the protocol is the
    per-tick busy reduction carried out of the scan."""
    tbl, ent = omfs_jax.table_from_jobs(jobs, users, cfg.cpu_total, cfg)
    if tbl.cpus.shape[0] == 0:
        # passes index order[0]/cumsum[-1]; match the python backend instead
        return tbl, jnp.zeros((horizon,), jnp.int32)
    return _jitted_runner(cfg, pass_fn, horizon)(tbl, ent)


# ---------------------------------------------------------------------------
# Instrumented runners: the SAME tick program plus in-scan event capture.
# Kept as separate lru_cached builders so the uninstrumented hot path above
# stays byte-identical with instrumentation off (repro.analysis enforces the
# confinement); the capture wraps _tick_step, it never reaches inside it.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _jitted_runner_events(cfg: SchedulerConfig, pass_fn: JaxPass,
                          horizon: int, ring_size: int):
    """`_jitted_runner` + per-tick event capture (`obs.jax_capture`): each
    scan step also emits (counts[E], ring[R,3], dropped) built from the
    tick-boundary diff.  ``ring_size`` is static per compile — the capture
    adds fixed-shape outputs only, so the runner compiles exactly once per
    (cfg, pass, horizon, ring) like its uninstrumented twin."""
    from repro.obs import jax_capture

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(tbl, ent):
        def step(tbl, t):
            pre = tbl
            tbl, busy = _tick_step(cfg, ent, tbl, t, pass_fn)
            cap = jax_capture.capture_tick(pre, tbl, t, ring_size)
            return tbl, (busy,) + cap

        return jax.lax.scan(step, tbl, jnp.arange(horizon, dtype=jnp.int32))

    return run


# ---------------------------------------------------------------------------
# Results (TickLog/SimResult live here; core.simulator re-exports them)
# ---------------------------------------------------------------------------


@dataclass
class TickLog:
    time: int
    busy: int
    pending: int
    running: int
    per_user_cpus: Dict[str, int]
    decisions: List[Decision]


@dataclass
class SimResult:
    state: ClusterState
    log: List[TickLog]

    # -- headline metrics (see core.metrics for derived scores) ------------
    def utilization(self) -> float:
        cfg = self.state.config
        if not self.log:
            return 0.0
        return float(np.mean([t.busy for t in self.log]) / cfg.cpu_total)

    def job_table(self) -> List[Job]:
        return sorted(self.state.jobs.values(), key=lambda j: j.id)

    def schedule_signature(self):
        """Hashable summary used by the Python-vs-JAX equivalence tests."""
        return tuple(
            (j.id, int(j.state), j.first_start, j.finish_time, j.progress,
             j.n_preemptions, j.n_checkpoints)
            for j in self.job_table()
        )


@dataclass
class EngineResult:
    """Backend-agnostic simulation outcome from `simulate`."""

    policy: str
    backend: str
    config: SchedulerConfig
    sim: Optional[SimResult] = None                    # python backend
    table: Optional["omfs_jax.JobTable"] = None        # jax backend
    busy: Optional[np.ndarray] = None                  # busy[t], both backends
    stream_stats: Optional[Dict[str, int]] = None      # simulate_stream only
    # -- observability (record_events=True); see repro.obs -----------------
    events: Optional[list] = None                      # List[obs.Event]
    event_counts: Optional[np.ndarray] = None          # [T, N_EVENT_TYPES]
    events_dropped: Optional[np.ndarray] = None        # [T] ring overflow

    def busy_series(self) -> np.ndarray:
        return np.asarray(self.busy)

    def events_dropped_total(self) -> int:
        if self.events_dropped is None:
            return 0
        return int(np.asarray(self.events_dropped).sum())

    def utilization(self) -> float:
        b = self.busy_series()
        return float(b.mean() / self.config.cpu_total) if b.size else 0.0

    def signature(self):
        """Id-free schedule signature, identical across backends when the
        policy's two implementations are step-equivalent."""
        if self.sim is not None:
            return tuple(s[1:] for s in self.sim.schedule_signature())
        return tuple(s[1:] for s in omfs_jax.signature_from_table(self.table))

    def summary(self) -> Dict[str, float]:
        """One comparison-table row: utilization / wait / preemption counts
        plus the paper's thrashing-cost terms — goodput (cpu-ticks that
        advanced *useful* work, per machine capacity) and the fraction of
        executed cpu-ticks wasted on C/R overhead or killed jobs."""
        if self.sim is not None:
            jobs = self.sim.job_table()
            started = [j for j in jobs if j.first_start >= 0]
            waits = [j.first_start - j.submit_time for j in started]
            preempt = sum(j.n_preemptions for j in jobs)
            ckpt = sum(j.n_checkpoints for j in jobs)
            spills = sum(j.n_spills for j in jobs)
            frag = sum(j.n_frag for j in jobs)
            killed = sum(1 for j in jobs if j.state == JobState.KILLED)
            done = sum(1 for j in jobs if j.state == JobState.DONE)
            was_killed = np.asarray(
                [j.state == JobState.KILLED for j in jobs])
            progress = np.asarray([j.progress for j in jobs])
            work = np.asarray([j.work for j in jobs])
            cpus = np.asarray([j.cpus for j in jobs])
        else:
            t = jax.device_get(self.table)
            started = t.first_start >= 0
            waits = (t.first_start - t.submit)[started]
            preempt = int(t.n_preempt.sum())
            ckpt = int(t.n_ckpt.sum())
            spills = int(t.n_spill.sum())
            frag = 0 if t.n_frag is None else int(t.n_frag.sum())
            killed = int((t.state == omfs_jax.KILLED).sum())
            done = int((t.state == omfs_jax.DONE).sum())
            was_killed = np.asarray(t.state) == omfs_jax.KILLED
            progress = np.asarray(t.progress)
            work = np.asarray(t.work)
            cpus = np.asarray(t.cpus)
        # useful = progress toward `work` (overhead units come on top and
        # count as waste); killed jobs' entire progress is lost work
        useful = np.where(was_killed, 0, np.minimum(progress, work)) * cpus
        executed = progress * cpus
        wasted = executed.sum() - useful.sum()
        horizon = max(self.busy_series().size, 1)
        return {
            "policy": self.policy,
            "backend": self.backend,
            "utilization": self.utilization(),
            "goodput": float(useful.sum())
            / float(self.config.cpu_total * horizon),
            "wasted_frac": float(wasted) / float(max(executed.sum(), 1)),
            "mean_wait": float(np.mean(waits)) if len(waits) else 0.0,
            "preemptions": preempt,
            "checkpoints": ckpt,
            "spills": spills,        # checkpoints placed beyond the fast tier
            # admissions by eviction though idle GPUs sufficed: no node
            # could take the job (always 0 without node_size)
            "frag_evict_admits": frag,
            "killed": killed,
            "done": done,
        }


# ---------------------------------------------------------------------------
# The single entry point
# ---------------------------------------------------------------------------


def simulate(
    users: List[User],
    jobs: List[Job],
    config: SchedulerConfig,
    horizon: int,
    policy: Union[str, PythonPolicy] = "omfs",
    backend: str = "python",
    *,
    pass_depth: Optional[int] = None,
    record_events: bool = False,
    event_ring: Optional[int] = None,
) -> EngineResult:
    """Run ``policy`` on ``backend`` over the same tick protocol.

    ``policy`` is a registry name (see POLICIES) — or, on the python backend
    only, any ``ClusterState -> List[Decision]`` callable.  ``pass_depth``
    bounds the per-tick queue sweep on the jax backend (SLURM's
    sched_max_job_start); None sweeps the whole queue.

    ``record_events=True`` additionally captures the typed per-job lifecycle
    event log (`repro.obs`): on the python backend via an `obs.bus.EventBus`
    tick diff, on the jax backend inside the jitted scan with a bounded
    per-tick ring (`event_ring` overrides the per-tick capacity; the default
    `obs.events.lossless_ring_size` can never drop — any overflow of a
    smaller ring lands in ``EngineResult.events_dropped``, never silently).
    """
    name = policy if isinstance(policy, str) else getattr(
        policy, "__name__", "custom")
    _check_nodes(config, [policy])

    if backend == "python":
        pol = _resolve_python(policy)
        state = ClusterState(config=config, users={u.name: u for u in users})
        for j in sorted(jobs, key=lambda x: x.id):
            if config.node_size is not None:
                gang_nodes(j.cpus, config.node_size)
            j = j.clone()
            j.state = JobState.UNSUBMITTED
            state.jobs[j.id] = j
        bus = None
        if record_events:
            from repro.obs.bus import EventBus
            bus = EventBus()
        log: List[TickLog] = []
        for t in range(horizon):
            state.time = t
            if bus is not None:
                bus.snapshot(state.jobs)
            decisions, _ = tick_python(state, pol)
            if bus is not None:
                bus.record_tick(state.jobs, t)
            # 4. metrics
            per_user = {u: 0 for u in state.users}
            for j in state.running_jobs():
                per_user[j.user] += j.cpus
            log.append(TickLog(
                time=t, busy=state.cpu_busy(),
                pending=len(state.pending_jobs()),
                running=len(state.running_jobs()),
                per_user_cpus=per_user, decisions=decisions,
            ))
        sim = SimResult(state=state, log=log)
        res = EngineResult(
            policy=name, backend=backend, config=config, sim=sim,
            busy=np.asarray([tl.busy for tl in log]))
        if bus is not None:
            res.events = bus.events
            res.event_counts = bus.counts_matrix(horizon)
            res.events_dropped = bus.dropped_series(horizon)
        return res

    if backend == "jax":
        if not isinstance(policy, str):
            raise ValueError(
                "jax backend needs a registered policy name, got a callable; "
                f"known: {sorted(POLICIES)}")
        if policy not in POLICIES:
            raise ValueError(
                f"unknown policy {policy!r}; known: {sorted(POLICIES)}")
        pass_fn = POLICIES[policy].jax_factory(pass_depth)
        if not record_events:
            tbl, busy = run_jax(users, jobs, config, horizon, pass_fn)
            return EngineResult(
                policy=name, backend=backend, config=config, table=tbl,
                busy=np.asarray(busy))
        from repro.obs import jax_capture
        from repro.obs.events import lossless_ring_size
        tbl, ent = omfs_jax.table_from_jobs(jobs, users, config.cpu_total,
                                            config)
        n_rows = tbl.cpus.shape[0]
        if n_rows == 0:
            return EngineResult(
                policy=name, backend=backend, config=config, table=tbl,
                busy=np.zeros((horizon,), np.int32), events=[],
                event_counts=np.zeros((horizon, jax_capture.N_EVENT_TYPES),
                                      np.int64),
                events_dropped=np.zeros((horizon,), np.int64))
        ring = lossless_ring_size(n_rows) if event_ring is None else event_ring
        run = _jitted_runner_events(config, pass_fn, horizon, ring)
        tbl, (busy, counts, ring_buf, dropped) = run(tbl, ent)
        return EngineResult(
            policy=name, backend=backend, config=config, table=tbl,
            busy=np.asarray(busy),
            events=jax_capture.decode_events(counts, ring_buf, dropped),
            event_counts=np.asarray(counts, dtype=np.int64),
            events_dropped=np.asarray(dropped, dtype=np.int64))

    raise ValueError(f"unknown backend {backend!r}; use 'python' or 'jax'")


# ---------------------------------------------------------------------------
# Multi-policy matrix runner: ONE compiled scan shared by every policy
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _jitted_matrix_runner(cfg: SchedulerConfig, pass_fns: tuple, horizon: int):
    """One jitted scan whose tick ``lax.switch``es over the policy passes.

    Compiling the union program once and selecting the policy by a dynamic
    index is measurably cheaper than compiling one scan per policy (the
    tick protocol, table plumbing, and XLA fixed costs are shared) — this
    is what keeps `bench_scheduler --smoke`'s policy matrix off the CI
    critical path.

    The input table is DONATED (see `_jitted_runner`); `simulate_matrix`
    passes each policy a fresh copy of the stacked table."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(tbl, ent, pidx):
        def step(tbl, t):
            branches = [
                lambda tb, p=p: _tick_step(cfg, ent, tb, t, p)
                for p in pass_fns
            ]
            return jax.lax.switch(pidx, branches, tbl)

        return jax.lax.scan(step, tbl, jnp.arange(horizon, dtype=jnp.int32))

    return run


def simulate_matrix(
    users: List[User],
    jobs: List[Job],
    config: SchedulerConfig,
    horizon: int,
    policies: Optional[List[str]] = None,
    *,
    pass_depth: Optional[int] = None,
) -> List[EngineResult]:
    """Run many registered policies on the JAX backend through one shared
    compiled scan (see `_jitted_matrix_runner`); per-policy results are
    bit-identical to ``simulate(..., backend="jax")`` — the policy pass is
    selected by ``lax.switch`` index, everything else is the same program.
    """
    names = list(policies) if policies is not None else sorted(POLICIES)
    unknown = [n for n in names if n not in POLICIES]
    if unknown:
        raise ValueError(f"unknown policies {unknown}; known: {sorted(POLICIES)}")
    _check_nodes(config, names)
    pass_fns = tuple(POLICIES[n].jax_factory(pass_depth) for n in names)
    tbl, ent = omfs_jax.table_from_jobs(jobs, users, config.cpu_total, config)
    if tbl.cpus.shape[0] == 0:
        busy = jnp.zeros((horizon,), jnp.int32)
        return [EngineResult(policy=n, backend="jax", config=config,
                             table=tbl, busy=np.asarray(busy)) for n in names]
    run = _jitted_matrix_runner(config, pass_fns, horizon)
    out = []
    for k, name in enumerate(names):
        # the runner donates its input table; each policy gets its own copy
        final, busy = run(_copy_table(tbl), ent, k)
        out.append(EngineResult(policy=name, backend="jax", config=config,
                                table=final, busy=np.asarray(busy)))
    return out


def _copy_table(tbl: "omfs_jax.JobTable") -> "omfs_jax.JobTable":
    """Fresh buffers for every column — what callers hand to the donating
    jitted runners when they need to keep (or reuse) the original."""
    return jax.tree_util.tree_map(lambda a: a.copy(), tbl)


# ---------------------------------------------------------------------------
# Batched sweep engine: ONE compiled program for a scenario×policy×seed grid
# ---------------------------------------------------------------------------


@dataclass
class BatchCell:
    """One cell of a `simulate_batch` sweep: a workload (scenario × seed),
    a registered policy, and optional traced knob overrides.

    ``quantum``/``pass_depth`` override ``cfg.quantum`` / the full-queue
    sweep *without* recompiling: they ride the batch axis as int32 scalars
    (`omfs_jax.Knobs`), so a quantum×pass_depth×policy grid is one XLA
    program (see DESIGN.md §Batched execution)."""

    users: List[User]
    jobs: List[Job]
    policy: str = "omfs"
    quantum: Optional[int] = None
    pass_depth: Optional[int] = None


def _batch_mesh(n_dev: int) -> Mesh:
    """1-D mesh over the first ``n_dev`` local devices; the batch axis of
    `simulate_batch` is split along its ``"b"`` axis."""
    return Mesh(np.asarray(jax.devices()[:n_dev]), ("b",))


@functools.lru_cache(maxsize=16)
def _jitted_batch_runner(cfg: SchedulerConfig, pass_fns: tuple, horizon: int,
                         n_dev: int = 1):
    """`jax.vmap` of the matrix runner's tick scan over a leading batch
    axis: one compiled program sweeps every (table, ent, pidx, knobs) cell.

    With ``n_dev > 1`` the vmapped program runs under `shard_map`, the
    batch axis split evenly across devices (cells are independent — no
    collectives, no replication checks needed).  The batched table is
    donated like the sequential runners' tables."""

    def cell(tbl, ent, pidx, knobs):
        def step(tbl, t):
            branches = [
                lambda tb, p=p: _tick_step(cfg, ent, tb, t, p, knobs)
                for p in pass_fns
            ]
            return jax.lax.switch(pidx, branches, tbl)

        return jax.lax.scan(step, tbl, jnp.arange(horizon, dtype=jnp.int32))

    vcell = jax.vmap(cell)
    if n_dev > 1:
        spec = PartitionSpec("b")
        vcell = jax.shard_map(vcell, mesh=_batch_mesh(n_dev),
                              in_specs=(spec, spec, spec, spec),
                              out_specs=(spec, spec), check_vma=False)
    return jax.jit(vcell, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def _jitted_batch_runner_events(cfg: SchedulerConfig, pass_fns: tuple,
                                horizon: int, ring_size: int, n_dev: int = 1):
    """`_jitted_batch_runner` + per-cell in-scan event capture: every cell
    of the vmapped sweep carries its own (counts, ring, dropped) series out
    of the scan, batch-stacked on the leading axis."""
    from repro.obs import jax_capture

    def cell(tbl, ent, pidx, knobs):
        def step(tbl, t):
            pre = tbl

            def branch(p):
                def run_branch(tb):
                    tb, busy = _tick_step(cfg, ent, tb, t, p, knobs)
                    return tb, (busy,) + jax_capture.capture_tick(
                        pre, tb, t, ring_size)
                return run_branch

            return jax.lax.switch(pidx, [branch(p) for p in pass_fns], tbl)

        return jax.lax.scan(step, tbl, jnp.arange(horizon, dtype=jnp.int32))

    vcell = jax.vmap(cell)
    if n_dev > 1:
        spec = PartitionSpec("b")
        vcell = jax.shard_map(vcell, mesh=_batch_mesh(n_dev),
                              in_specs=(spec, spec, spec, spec),
                              out_specs=(spec, (spec, spec, spec, spec)),
                              check_vma=False)
    return jax.jit(vcell, donate_argnums=(0,))


def simulate_batch(
    cells: List[BatchCell],
    config: SchedulerConfig,
    horizon: int,
    *,
    devices: Optional[int] = None,
    record_events: bool = False,
    event_ring: Optional[int] = None,
) -> List[EngineResult]:
    """Run ``B`` independent simulations as ONE compiled batched scan.

    Stacks every cell's `JobTable` / entitlement vector onto a leading
    batch axis (`omfs_jax.stack_tables` — short tables get inert pad rows),
    selects each cell's policy by `lax.switch` index and its quantum /
    pass-depth by traced `Knobs`, and `jax.vmap`s the shared tick scan.
    Per-cell results are bit-identical to sequential
    ``simulate(..., backend="jax")`` with the matching config
    (tests/test_simulate_batch.py asserts this for every registered
    policy).

    ``devices`` caps how many local devices the batch axis is sharded
    across (default: all of them; 1 on the CPU host).  With more than one
    device the batch is padded to a multiple of the device count with
    replicas of the last cell (dropped from the results).

    Empty corners match the sequential paths exactly: ``cells == []``
    returns ``[]``, and a batch whose tables are ALL empty skips the jitted
    path just like `simulate_matrix`'s early return (a mixed batch keeps
    empty cells on the jitted path via all-pad tables — same result either
    way, which is the regression test's point).
    """
    cells = list(cells)
    if not cells:
        return []
    names = sorted({c.policy for c in cells})
    unknown = [n for n in names if n not in POLICIES]
    if unknown:
        raise ValueError(f"unknown policies {unknown}; known: {sorted(POLICIES)}")
    _check_nodes(config, names)
    # Per-cell depth rides the knobs (traced masking), but the fori_loop
    # trip count is static: when EVERY cell caps pass_depth, truncate the
    # compiled loop at the batch-wide max.  Iterations past a cell's own
    # depth are masked no-ops either way, so results are unchanged — the
    # truncation only drops dead work (a depth-4 cell in a J=40 table
    # otherwise pays all 40 positions under vmap).
    depths = [c.pass_depth for c in cells]
    bound = None if any(d is None for d in depths) else max(depths)
    pass_fns = tuple(POLICIES[n].jax_factory(bound) for n in names)
    built = [omfs_jax.table_from_jobs(c.jobs, c.users, config.cpu_total,
                                      config) for c in cells]
    sizes = [t.cpus.shape[0] for t, _ in built]
    if max(sizes) == 0:
        # all-empty batch: same early return simulate/simulate_matrix take
        out = [EngineResult(policy=c.policy, backend="jax", config=config,
                            table=t, busy=np.zeros((horizon,), np.int32))
               for c, (t, _) in zip(cells, built)]
        if record_events:
            from repro.obs.events import N_EVENT_TYPES
            for r in out:
                r.events = []
                r.event_counts = np.zeros((horizon, N_EVENT_TYPES), np.int64)
                r.events_dropped = np.zeros((horizon,), np.int64)
        return out

    tbl, ent = omfs_jax.stack_tables([t for t, _ in built],
                                     [e for _, e in built])
    pidx = jnp.asarray([names.index(c.policy) for c in cells], jnp.int32)
    knobs = omfs_jax.Knobs(
        quantum=jnp.asarray(
            [config.quantum if c.quantum is None else c.quantum
             for c in cells], jnp.int32),
        depth=jnp.asarray(
            [int(omfs_jax.BIG) if c.pass_depth is None else c.pass_depth
             for c in cells], jnp.int32),
    )

    n_dev = len(jax.devices()) if devices is None else int(devices)
    n_dev = max(1, min(n_dev, len(cells)))
    pad = (-len(cells)) % n_dev
    if pad:
        rep = lambda a: jnp.concatenate(
            [a, jnp.repeat(a[-1:], pad, axis=0)], axis=0)
        tbl = jax.tree_util.tree_map(rep, tbl)
        ent, pidx = rep(ent), rep(pidx)
        knobs = jax.tree_util.tree_map(rep, knobs)

    if record_events:
        from repro.obs import jax_capture
        from repro.obs.events import lossless_ring_size
        ring = (lossless_ring_size(tbl.cpus.shape[1])
                if event_ring is None else event_ring)
        run = _jitted_batch_runner_events(config, pass_fns, horizon, ring,
                                          n_dev)
        final, (busy, counts, ring_buf, dropped) = run(tbl, ent, pidx, knobs)
        counts = np.asarray(counts)
        ring_buf = np.asarray(ring_buf)
        dropped = np.asarray(dropped)
    else:
        run = _jitted_batch_runner(config, pass_fns, horizon, n_dev)
        final, busy = run(tbl, ent, pidx, knobs)
    busy = np.asarray(busy)
    out = []
    for i, (c, J) in enumerate(zip(cells, sizes)):
        # slice the cell back out of the batch axis and drop its pad rows
        # (rows never permute in the table, so [:J] is exactly the cell)
        cell_tbl = jax.tree_util.tree_map(lambda a: a[i, :J], final)
        res = EngineResult(policy=c.policy, backend="jax",
                           config=config, table=cell_tbl,
                           busy=busy[i])
        if record_events:
            res.events = jax_capture.decode_events(counts[i], ring_buf[i],
                                                   dropped[i])
            res.event_counts = counts[i].astype(np.int64)
            res.events_dropped = dropped[i].astype(np.int64)
        out.append(res)
    return out


# ---------------------------------------------------------------------------
# Chunked-epoch streaming engine: unbounded arrivals at bounded memory
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _jitted_segment_runner(cfg: SchedulerConfig, pass_fn: JaxPass,
                           seg_len: int):
    """One jitted fixed-length segment of the tick scan, with the segment's
    start tick ``t0`` TRACED (an int32 scalar, not a Python constant): every
    segment of a stream reuses the one compilation — `_cache_size() == 1`
    after N segments is asserted by the jaxpr/retrace audit.  Donates the
    table like the other runners (between segments exactly one [capacity]-
    shaped table is alive)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(tbl, ent, t0):
        def step(tbl, i):
            return _tick_step(cfg, ent, tbl, t0 + i, pass_fn)

        return jax.lax.scan(step, tbl, jnp.arange(seg_len, dtype=jnp.int32))

    return run


@functools.lru_cache(maxsize=32)
def _jitted_segment_runner_events(cfg: SchedulerConfig, pass_fn: JaxPass,
                                  seg_len: int, ring_size: int):
    """`_jitted_segment_runner` + in-scan event capture.  The ring records
    true job ids, so recycled slots decode correctly; the start tick stays
    traced — one compile per (cfg, pass, seg_len, ring) across the whole
    stream, same as the uninstrumented runner."""
    from repro.obs import jax_capture

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(tbl, ent, t0):
        def step(tbl, i):
            pre = tbl
            tbl, busy = _tick_step(cfg, ent, tbl, t0 + i, pass_fn)
            cap = jax_capture.capture_tick(pre, tbl, t0 + i, ring_size)
            return tbl, (busy,) + cap

        return jax.lax.scan(step, tbl, jnp.arange(seg_len, dtype=jnp.int32))

    return run


def simulate_stream(
    users: List[User],
    jobs,
    config: SchedulerConfig,
    horizon: int,
    policy: str = "omfs",
    *,
    capacity: int,
    segment_len: int,
    pass_depth: Optional[int] = None,
    record_events: bool = False,
    event_ring: Optional[int] = None,
    profile=None,
) -> EngineResult:
    """Run an arrival *stream* through a fixed-``capacity`` JobTable in
    jitted ``segment_len``-tick chunks — unbounded workloads at bounded
    memory (ROADMAP "million-job streaming simulation").

    ``jobs`` is any iterable of `core.types.Job` in ascending
    ``(submit_time, id)`` order (`core.workload.arrival_stream` sorts a
    list; `core.workload.endless_arrivals` generates forever).  The loop:

      1. host boundary: pull every job due before the segment's end from
         the iterator, fetch the table, compact finished (DONE/KILLED)
         rows out into a host-side archive, and scatter the arrivals into
         the freed slots (`omfs_jax.insert_rows` — one jitted program for
         the whole stream).  Arrivals land as UNSUBMITTED rows and fire at
         their true submit tick inside the scan, so inserting a segment
         early is semantics-free.
      2. run the jitted segment (`_jitted_segment_runner` — traced start
         tick, one compile across segments).

    When every due arrival always finds a slot (live jobs never exceed
    ``capacity``), the merged result is bit-identical to the monolithic
    ``simulate(..., backend="jax")`` run over the same jobs: row identity
    (queue/victim tie-breaks) rides the table's ``jid`` column, not row
    position.  When slots run out, surplus arrivals are DEFERRED to a
    later boundary (they arrive late, like a submit-rate-limited
    front-end); ``stream_stats["deferrals"]`` counts those events, and
    ``stream_stats["frag_evict_admits"]`` the admissions that evicted
    although enough GPUs sat idle (`SchedulerConfig.node_size`).

    Jobs whose ``submit_time >= horizon`` are left in the iterator and do
    not appear in the result table (the monolithic run keeps them as
    UNSUBMITTED rows — every metric still matches).

    ``record_events`` captures the lifecycle event log in-scan exactly like
    `simulate` (the ring records true job ids, so recycled slots decode
    correctly and finished jobs' events survive compaction — they were
    captured at their tick, before the row was archived).

    Every round opens the ``stream.*`` spans of `repro.obs.profile.span`
    (profiler annotations, free when no profiler runs), with the round's
    counters as arguments: ``finished``, ``inserted``, ``deferred`` and
    ``live`` on ``stream.boundary``; ``t0``, ``ticks`` and ``fresh`` on
    ``stream.segment``.  ``profile`` is an optional section hook (an object
    with ``section(name)``, such as `repro.obs.profile.ProfileTimers`); it
    receives exactly three sections: ``compaction`` (the host boundary),
    then ``compile`` (the segment runner was built in this call) or
    ``dispatch`` (it was not), each segment ending in
    ``block_until_ready``.
    """
    if capacity <= 0:
        raise ValueError(f"capacity must be positive, got {capacity}")
    if segment_len <= 0:
        raise ValueError(f"segment_len must be positive, got {segment_len}")
    if not isinstance(policy, str) or policy not in POLICIES:
        raise ValueError(
            f"unknown policy {policy!r}; known: {sorted(POLICIES)}")
    _check_nodes(config, [policy])
    from repro.obs.profile import span
    pass_fn = POLICIES[policy].jax_factory(pass_depth)

    ring: Optional[int] = None
    if record_events:
        from repro.obs.events import lossless_ring_size
        ring = (lossless_ring_size(capacity) if event_ring is None
                else event_ring)

    tbl, ent = omfs_jax.table_from_jobs([], users, config.cpu_total, config,
                                        rows=capacity)

    feed = iter(jobs)
    lookahead: Optional[Job] = None
    due: List[Job] = []
    archived: List["omfs_jax.JobTable"] = []   # host-side finished rows
    busy_parts: List[np.ndarray] = []
    stats = {"segments": 0, "inserted": 0, "deferrals": 0, "peak_live": 0,
             "capacity": capacity}

    def boundary(tbl, note):
        """Compact finished rows out, insert due arrivals.  The work is
        numpy on the read-back table; the padded arrival block, ``slots``
        and ``valid`` cross to the device as one packed array, then one
        `insert_packed` dispatch.  The round's counters go on ``note``, the
        boundary's annotation."""
        with span("stream.read_back"):
            host = jax.device_get(tbl)
        with span("stream.compact"):
            pad = omfs_jax.host_is_pad(host)
            finished = np.isin(host.state,
                               (omfs_jax.DONE, omfs_jax.KILLED)) & ~pad
            n_finished = 0
            if finished.any():
                idx = np.flatnonzero(finished)
                n_finished = idx.size
                archived.append(
                    jax.tree_util.tree_map(lambda a: a[idx], host))
            free_mask = finished | pad
            free = np.flatnonzero(free_mask)
            stats["peak_live"] = max(stats["peak_live"],
                                     capacity - free.size)
            k = min(len(due), free.size)
            if k < len(due):
                stats["deferrals"] += len(due) - k
        note.set_metadata(finished=n_finished, inserted=k,
                          deferred=len(due) - k,
                          live=capacity - free.size + k)
        if k == 0 and not n_finished:
            return tbl
        take, due[:] = due[:k], due[k:]
        with span("stream.build") as build:
            # arrivals fill the first k free slots; pad rows clear the rest
            # of the freed slots; occupied slots get a masked write-back.
            # `slots` is a permutation of arange(capacity) by construction.
            slots = np.concatenate([free, np.flatnonzero(~free_mask)])
            valid = np.arange(capacity) < free.size
            rows, _ = omfs_jax.table_from_jobs(take, users, config.cpu_total,
                                               config, rows=capacity,
                                               host=True)
            packed = jax.device_put(omfs_jax.pack_insert(rows, slots, valid))
            build.set_metadata(rows=k, h2d_bytes=packed.nbytes)
        with span("stream.insert"):
            stats["inserted"] += k
            return omfs_jax.insert_packed(tbl, packed)

    ev_counts: List[np.ndarray] = []
    ev_rings: List[np.ndarray] = []
    ev_dropped: List[np.ndarray] = []
    seg_starts: List[int] = []

    # the host blocks on each segment at block_until_ready when a profile
    # times it or events are read, else at the busy read-back
    blocking = record_events or profile is not None
    t0 = 0
    while t0 < horizon:
        seg = min(segment_len, horizon - t0)
        with span("stream.round"):
            with span("stream.feed"):
                while True:
                    if lookahead is None:
                        lookahead = next(feed, None)
                    if (lookahead is None
                            or lookahead.submit_time >= t0 + seg):
                        break
                    due.append(lookahead)
                    lookahead = None
            with span("stream.boundary", profile, "compaction") as note:
                tbl = boundary(tbl, note)
            if record_events:
                factory, key = _jitted_segment_runner_events, (
                    config, pass_fn, seg, ring)
            else:
                factory, key = _jitted_segment_runner, (config, pass_fn, seg)
            # a cache miss of the runner factory means this call traces +
            # XLA-compiles the segment program; later segments dispatch it
            misses = factory.cache_info().misses
            runner = factory(*key)
            fresh = factory.cache_info().misses > misses
            with span("stream.segment", profile,
                      "compile" if fresh else "dispatch",
                      t0=t0, ticks=seg, fresh=int(fresh)):
                with span("stream.dispatch"):
                    tbl, out = runner(tbl, ent, jnp.int32(t0))
                with span("stream.wait"):
                    if record_events:
                        busy, cnt, rbuf, drp = out
                        busy = jax.block_until_ready(busy)
                        ev_counts.append(np.asarray(cnt))
                        ev_rings.append(np.asarray(rbuf))
                        ev_dropped.append(np.asarray(drp))
                        seg_starts.append(t0)
                    elif blocking:
                        busy = jax.block_until_ready(out)
                    else:
                        busy = np.asarray(out)
            busy_parts.append(np.asarray(busy))
            stats["segments"] += 1
        t0 += seg

    # final extraction: archive + still-live rows, merged in job-id order
    # (= the monolithic table's row order).  Arrivals still deferred here
    # never entered the table; they stay out of the result (counted below).
    with span("stream.extract"):
        stats["dropped"] = len(due)
        host = jax.device_get(tbl)
        live = np.flatnonzero(~omfs_jax.host_is_pad(host))
        parts = archived + [
            jax.tree_util.tree_map(lambda a: a[live], host)]
        merged_np = jax.tree_util.tree_map(
            lambda *cols: np.concatenate(cols), *parts)
        order = np.argsort(merged_np.jid, kind="stable")
        merged = jax.device_put(
            jax.tree_util.tree_map(lambda a: a[order], merged_np))
        busy = (np.concatenate(busy_parts) if busy_parts
                else np.zeros((0,), np.int32))
        stats["frag_evict_admits"] = (0 if merged_np.n_frag is None
                                      else int(merged_np.n_frag.sum()))
        res = EngineResult(policy=policy, backend="jax", config=config,
                           table=merged, busy=busy, stream_stats=stats)
        if record_events:
            from repro.obs import jax_capture
            from repro.obs.events import N_EVENT_TYPES
            events = []
            for cnt, rbuf, drp, s0 in zip(ev_counts, ev_rings, ev_dropped,
                                          seg_starts):
                events.extend(
                    jax_capture.decode_events(cnt, rbuf, drp, t0=s0))
            res.events = events
            res.event_counts = (
                np.concatenate(ev_counts).astype(np.int64) if ev_counts
                else np.zeros((0, N_EVENT_TYPES), np.int64))
            res.events_dropped = (
                np.concatenate(ev_dropped).astype(np.int64) if ev_dropped
                else np.zeros((0,), np.int64))
            stats["events_dropped"] = int(res.events_dropped.sum())
    return res
