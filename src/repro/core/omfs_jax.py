"""OMFS vectorized in JAX: the paper's contribution as a composable module.

The whole scheduler state is a table of fixed-size arrays (`JobTable`); the
tick protocol (arrivals -> progress/completions -> scheduling pass) is defined
once in `core.engine` and shared by every policy and backend.  This module
owns the table representation, the JobTable *primitives* every vectorized
policy builds on (queue ordering, admission, victim selection/eviction), and
the two OMFS passes:

* ``make_omfs_pass(incremental=False)`` — the original reference pass: each
  admission recomputes O(J) masked usage sums and a fresh ``lexsort`` for
  victim selection, faithful but O(J log J) per queue position.
* ``make_omfs_pass(incremental=True)`` — the optimized pass (the default):
  per-user usage ``[U]`` and the busy scalar ride the ``fori_loop`` carry and
  are updated in O(1) per admission; the idle-admit fast path touches no
  victim machinery at all, and the ``lexsort``+``cumsum`` victim selection
  runs only on the eviction branch of a ``lax.cond``.

Both produce bit-identical schedules (tests/test_policies_equivalence.py and
benchmarks/bench_sched_scale.py assert signature equality) — this is what
makes 1000+-node / 100k-job what-if simulation cheap.

Sequential admission is inherent to Algorithm 1 (each admission changes the
state the next decision sees), so the pass is a ``fori_loop`` over queue
positions; the ``pass_depth`` knob (same as SLURM's sched_max_job_start)
bounds it at scale.

C/R costs are size-aware (`core.crcost.CRCostModel`) and live in a
``[J, T]`` **cost lattice**: the table carries per-job ``state_mib`` plus
three precomputed lattices — ``cost_save_lat`` (first save per tier),
``cost_rsave_lat`` (recurrent/delta save per tier) and ``cost_restore_lat``
(restore per tier) — one column per tier of ``cfg.cr_tiers`` (T=1 when
untiered).  Sizes are static per job (until `update_state_mib`), so the
model evaluates once at build time with Python-int arithmetic — the exact
numbers the Python backend charges at runtime, which is what makes
cross-backend bit-equality hold by construction.  The shared primitives
charge from the lattice: `apply_evictions` adds the placed tier's save
cost (first or recurrent, by ``n_ckpt``) to each checkpointed victim,
`admit_job` adds the restore cost of the tier the snapshot was placed on.
Both are O(1) gathers/scatters, so the non-eviction fast path does no
extra O(J) work.  The legacy two-column accessors (``cost_save``,
``cost_save2``, ``cost_restore``, ``cost_restore2``) remain as read-only
views over the lattice for compatibility.

Tiered eviction placement (`SchedulerConfig.cr_tiers`,
`core.crcost.TieredCRCostModel`): the runtime ``ckpt_tier`` column records
where each pending job's latest snapshot lives.  `apply_evictions` places
each victim greedily (cheapest feasible tier over the T lattice columns,
spilling down the hierarchy when capacity-bounded tiers are full) with a
short ``lax.scan`` in victim order — confined to the eviction branch, so
the admit fast path stays O(1) — and `admit_job` charges the restore cost
of the *placed* tier, then frees the slot.  Sizes may change at runtime
via `update_state_mib` (O(1) scatters recomputing the lattice rows with
the same arithmetic, no re-trace of the jitted scan).

Node placement (`SchedulerConfig.node_size`, DESIGN.md §Node placement):
the ``node`` column records the first node of each job's latest
placement.  The OMFS pass carries the free GPUs of every node (``[N]``)
beside the per-user usage: the idle-admit fast path admits only where
`fit_free` finds a place on free capacity, and the eviction branch takes
the node-aware victim prefix (`plan_node_evictions`).  With ``node_size``
None the table has no ``node`` and ``n_frag`` columns (None), so every
program and transfer is what it is without nodes.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.crcost import MAX_STATE_MIB
from repro.core.placement import gang_nodes
from repro.core.types import JobClass, SchedulerConfig

# JobState encoding (matches types.JobState)
UNSUB, PENDING, RUNNING, DONE, KILLED = 0, 1, 2, 3, 4
BIG = jnp.int32(2**30)
#: infeasible-tier sentinel for the placement argmin: larger than any real
#: lattice entry (costs saturate at cap_ticks << int32 max)
MASK = jnp.int32(jnp.iinfo(jnp.int32).max)
NONP = int(JobClass.NON_PREEMPTIBLE)
CKPT = int(JobClass.CHECKPOINTABLE)


class JobTable(NamedTuple):
    """Static job attributes + mutable runtime state, all [J]-shaped."""

    jid: jax.Array         # int32 job id — the tie-break identity.  For a
    #   monolithic table rows are sorted by id, so this is order-isomorphic
    #   to the row index (schedules unchanged); for the streaming engine a
    #   recycled slot keeps the job's true id, so queue/victim tie-breaking
    #   stays bit-identical to the monolithic run (DESIGN.md §Batched
    #   execution).  Pad rows carry BIG.
    user: jax.Array        # int32 user index
    cpus: jax.Array        # int32
    work: jax.Array        # int32 work units
    priority: jax.Array    # int32
    jclass: jax.Array      # int32 JobClass
    submit: jax.Array      # int32 tick
    state_mib: jax.Array   # int32 checkpoint image size (MiB)
    # The [J, T] C/R cost lattice, precomputed from (cfg.cr_cost /
    # cr_tiers, cfg.cr_overhead, state_mib): sizes are static per job
    # (until `update_state_mib`), so the model evaluates once at table
    # build and the passes pay only an O(1) gather per charge.  Column k
    # prices tier k of ``cfg.cr_tiers`` (T=1 untiered); tier 0 is the
    # fastest tier, the last column the durable spill target.
    cost_save_lat: jax.Array     # int32 [J, T] FIRST-save cost per tier
    cost_rsave_lat: jax.Array    # int32 [J, T] RECURRENT (delta) save cost
    cost_restore_lat: jax.Array  # int32 [J, T] restore cost per tier
    # runtime
    state: jax.Array       # int32 JobState
    progress: jax.Array
    run_start: jax.Array
    first_start: jax.Array
    finish: jax.Array
    n_preempt: jax.Array
    n_ckpt: jax.Array
    overhead: jax.Array
    backfilled: jax.Array  # int32 0/1: ever admitted by queue-jumping
    ckpt_tier: jax.Array   # int32 tier holding the latest snapshot (-1: none)
    n_spill: jax.Array     # int32 checkpoints placed beyond the fast tier
    # With ``node_size`` set (None otherwise: a table without nodes holds,
    # moves and traces the columns above and nothing more):
    node: Optional[jax.Array] = None    # int32 first node of the latest
    #   placement (-1: never started); completion and eviction keep it
    n_frag: Optional[jax.Array] = None  # int32 admissions by eviction
    #   although more GPUs than the job's were idle: no node could take it
    #   (the fragmentation counter ``frag_evict_admits`` is its sum)

    # Legacy two-column accessors, kept as read-only VIEWS over the lattice
    # during the [J, T] migration (DESIGN.md §Cost lattice).  ``...``
    # indexing keeps them correct for batched [B, J, T] tables too.  With
    # T=1 fast==durable (the old untiered aliasing); with T=2 these are
    # bit-exactly the old columns.  They are deliberately NOT fields: the
    # column-dataflow contract (`repro.analysis`) tracks lattice columns.
    @property
    def cost_save(self) -> jax.Array:
        """Fast-tier (tier 0) first-save cost — view of cost_save_lat."""
        return self.cost_save_lat[..., 0]

    @property
    def cost_save2(self) -> jax.Array:
        """Durable-tier (last) first-save cost — view of cost_save_lat."""
        return self.cost_save_lat[..., -1]

    @property
    def cost_restore(self) -> jax.Array:
        """Fast-tier restore cost — view of cost_restore_lat."""
        return self.cost_restore_lat[..., 0]

    @property
    def cost_restore2(self) -> jax.Array:
        """Durable-tier restore cost — view of cost_restore_lat."""
        return self.cost_restore_lat[..., -1]


def table_from_jobs(jobs, users, cpu_total: int,
                    config: Optional[SchedulerConfig] = None, *,
                    rows: Optional[int] = None, host: bool = False,
                    ) -> Tuple[JobTable, jax.Array]:
    """Build ``(JobTable, entitled_cpus[U])`` from core.types objects.

    Rows are ordered by job id, matching the Python backend's job table, so
    per-row signatures are directly comparable across backends.  ``config``
    supplies the C/R cost model: the per-job save/restore cost columns are
    evaluated here with Python integers — the exact arithmetic the Python
    backend charges at runtime — so cross-backend bit-equality holds by
    construction.  ``config=None`` builds a free-C/R table (legacy callers).
    ``rows`` pads the table to that many rows with inert pad rows, as
    `pad_table` does.  The columns are built as int32 numpy arrays and
    cross to the device in one ``jax.device_put``; ``host=True`` returns
    them (and the entitlements) as numpy, untransferred.  With
    ``config.node_size`` set, a job of a size no node placement can hold
    raises (`placement.gang_nodes`).
    """
    uidx = {u.name: i for i, u in enumerate(users)}
    j = sorted(jobs, key=lambda x: x.id)
    n = len(j)
    cfg = config if config is not None else SchedulerConfig()
    n_tiers = cfg.n_cost_tiers
    arr = lambda f: np.asarray([f(x) for x in j], np.int32).reshape(n)
    cpus = arr(lambda x: x.cpus)
    nodes = cfg.node_size is not None
    if nodes:
        for c in np.unique(cpus):
            gang_nodes(int(c), cfg.node_size)
    # the [J, T] lattices: evaluated per (job, tier) with Python ints —
    # the exact arithmetic omfs._evict / _start charge at runtime
    lat = lambda f: np.asarray(
        [[f(x, k) for k in range(n_tiers)] for x in j],
        np.int32).reshape(n, n_tiers)
    table = JobTable(
        jid=arr(lambda x: x.id),
        user=arr(lambda x: uidx[x.user]),
        cpus=cpus,
        work=arr(lambda x: x.work),
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
        state=np.full((n,), UNSUB, np.int32),
        progress=np.zeros((n,), np.int32),
        run_start=np.full((n,), -1, np.int32),
        first_start=np.full((n,), -1, np.int32),
        finish=np.full((n,), -1, np.int32),
        n_preempt=np.zeros((n,), np.int32),
        n_ckpt=np.zeros((n,), np.int32),
        overhead=np.zeros((n,), np.int32),
        backfilled=arr(lambda x: int(x.backfilled)),
        ckpt_tier=np.full((n,), -1, np.int32),
        n_spill=np.zeros((n,), np.int32),
        node=np.full((n,), -1, np.int32) if nodes else None,
        n_frag=np.zeros((n,), np.int32) if nodes else None,
    )
    if rows is not None:
        table = _pad(table, rows, np)
    out = (table, _host_entitlements(users, cpu_total))
    return out if host else jax.device_put(out)


def _host_entitlements(users, cpu_total: int) -> np.ndarray:
    return np.asarray([u.entitled_cpus(cpu_total) for u in users], np.int32)


def entitlements(users, cpu_total: int) -> jnp.ndarray:
    return jax.device_put(_host_entitlements(users, cpu_total))


class Knobs(NamedTuple):
    """Per-cell *traced* scheduling knobs for the batched sweep engine.

    A sequential `simulate` bakes ``cfg.quantum`` and ``pass_depth`` into
    the trace as Python constants — sweeping them means one XLA program
    per grid point.  `engine.simulate_batch` instead threads them through
    the pass as int32 scalars (one per batch cell under ``vmap``), so ONE
    compiled program covers the whole quantum×pass_depth grid.  Passes
    read them only when ``knobs is not None``; the default path traces
    exactly as before (bit-identity with the per-cell programs is asserted
    by tests/test_simulate_batch.py).

    ``depth`` bounds the per-tick queue sweep by *masking* loop iterations
    past it (the fori_loop still runs the full static trip count), which is
    result-identical to truncating the loop: a masked iteration admits
    nothing and the eviction branch is never taken.
    """

    quantum: jax.Array     # int32 — minimal uninterrupted run before evictable
    depth: jax.Array       # int32 — queue positions processed per tick


def default_knobs(cfg: SchedulerConfig,
                  pass_depth: Optional[int] = None) -> Knobs:
    return Knobs(quantum=jnp.int32(cfg.quantum),
                 depth=jnp.int32(BIG if pass_depth is None else pass_depth))


# ---------------------------------------------------------------------------
# JobTable primitives shared by every vectorized policy (OMFS + baselines)
# ---------------------------------------------------------------------------


@jax.named_scope("sched.queue_order")
def queue_order(tbl: JobTable) -> Tuple[jax.Array, jax.Array]:
    """Snapshot the submitted queue: (order[J], eligible[J]).

    Order is (-priority, submit, id) — the same key as queues.submitted_key —
    with ineligible rows pushed to the end.  The id tie-break is the ``jid``
    column (== row order for monolithic tables; the true job id for
    streaming tables whose slots are recycled)."""
    eligible = tbl.state == PENDING
    qkey = jnp.where(eligible, -tbl.priority, BIG)
    order = jnp.lexsort((tbl.jid, tbl.submit, qkey))
    return order, eligible


def running_usage(tbl: JobTable, num_users: int):
    """Aggregates at pass start: (usage[U], non_preemptible_usage[U], busy)."""
    running = tbl.state == RUNNING
    run_cpus = jnp.where(running, tbl.cpus, 0)
    usage = jax.ops.segment_sum(run_cpus, tbl.user, num_segments=num_users)
    nonp = jax.ops.segment_sum(
        jnp.where(running & (tbl.jclass == NONP), tbl.cpus, 0),
        tbl.user, num_segments=num_users)
    return usage, nonp, jnp.sum(run_cpus)


def admit_job(tbl: JobTable, idx: jax.Array, t: jax.Array,
              admit: jax.Array) -> JobTable:
    """Start job ``idx`` (lines 37-38) iff ``admit``; O(1) scatter updates.

    A job with a checkpoint (``n_ckpt > 0``) restarts by restoring its
    latest snapshot, so admission charges the restore cost of the tier the
    snapshot was *placed* on at eviction (``ckpt_tier``; lattice column 0
    when untiered) — the twin of ``omfs._start``.  The restore consumes
    the snapshot: ``ckpt_tier`` clears, freeing the placed tier's capacity
    for the next victim."""
    tier = jnp.maximum(tbl.ckpt_tier[idx], 0)
    restore = jnp.where(
        admit & (tbl.n_ckpt[idx] > 0),
        tbl.cost_restore_lat[idx, tier],
        0)
    return tbl._replace(
        state=tbl.state.at[idx].set(
            jnp.where(admit, RUNNING, tbl.state[idx])),
        run_start=tbl.run_start.at[idx].set(
            jnp.where(admit, t, tbl.run_start[idx])),
        first_start=tbl.first_start.at[idx].set(
            jnp.where(admit & (tbl.first_start[idx] < 0), t,
                      tbl.first_start[idx])),
        overhead=tbl.overhead.at[idx].add(restore),
        ckpt_tier=tbl.ckpt_tier.at[idx].set(
            jnp.where(admit, -1, tbl.ckpt_tier[idx])),
    )


def effective_save_lat(tbl: JobTable) -> jax.Array:
    """The ``[J, T]`` save costs evicting each job *now* would charge:
    recurrent (delta) rows for warm jobs (``n_ckpt > 0`` — they already
    hold a snapshot), first-save rows otherwise.  Evaluated before the
    pass bumps ``n_ckpt``, mirroring ``omfs._evict``'s pre-increment
    ``recurrent`` flag."""
    return jnp.where((tbl.n_ckpt > 0)[..., None],
                     tbl.cost_rsave_lat, tbl.cost_save_lat)


def tier_occupancy(tbl: JobTable, n_tiers: int) -> jax.Array:
    """Per-tier MiB held by evicted-and-pending snapshots, ``[T]`` — the
    twin of ``omfs._tier_occupancy`` (a restore consumes the slot:
    `admit_job` cleared ``ckpt_tier``)."""
    held = (tbl.state == PENDING) & (tbl.ckpt_tier >= 0)
    return jax.ops.segment_sum(
        jnp.where(held, tbl.state_mib, 0),
        jnp.clip(tbl.ckpt_tier, 0, n_tiers - 1), num_segments=n_tiers)


@jax.named_scope("sched.victim_order")
def victim_order(tbl: JobTable, cheap: bool = False) -> jax.Array:
    """Victim permutation.  Standard: ``(priority, run_start, id)`` —
    queues.running_victim_key.  ``cheap`` (the `omfs_cheap_victim` policy):
    ``(save_cost, priority, run_start, id)`` — cheapest-to-checkpoint
    first, priced at the fast tier with the delta-aware effective cost
    (warm jobs only rewrite their delta — queues.cheap_victim_key)."""
    if cheap:
        key = effective_save_lat(tbl)[..., 0]
        return jnp.lexsort((tbl.jid, tbl.run_start, tbl.priority, key))
    return jnp.lexsort((tbl.jid, tbl.run_start, tbl.priority))


def select_victims(tbl: JobTable, evictable: jax.Array, idle: jax.Array,
                   cpus_needed: jax.Array,
                   order: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, jax.Array]:
    """The paper's while-loop (lines 32-36) as lexsort+cumsum: the minimal
    prefix of evictable jobs — in ``order`` (default: the standard victim
    key) — whose release makes ``cpus_needed`` fit.

    Returns (planned[J] victim mask, enough: idle + all evictable suffices)."""
    if order is None:
        order = victim_order(tbl)
    evict_sorted = evictable[order]
    cpus_sorted = jnp.where(evict_sorted, tbl.cpus[order], 0)
    freed_cum = jnp.cumsum(cpus_sorted)
    need = jnp.maximum(cpus_needed - idle, 0)
    prefix_needed = freed_cum - cpus_sorted < need   # victim still required
    planned_sorted = evict_sorted & prefix_needed
    enough = idle + freed_cum[-1] >= cpus_needed
    planned = jnp.zeros_like(evictable).at[order].set(planned_sorted)
    return planned, enough


@jax.named_scope("sched.place_checkpoints")
def place_checkpoints(cfg: SchedulerConfig, tbl: JobTable, ckpt: jax.Array,
                      order: Optional[jax.Array] = None,
                      ) -> Tuple[jax.Array, jax.Array]:
    """Tier placement for the ``ckpt`` victims: greedy cheapest-feasible
    over the T lattice columns in victim ``order``, spilling down the
    hierarchy when capacity-bounded tiers are full.  Returns
    ``(tier[J], save_cost[J])`` (tier 0 / cost 0 on non-victims).

    Per victim the chosen tier is the first-occurrence ``argmin`` of its
    *effective* (delta-aware) save row over feasible tiers — bit-identical
    to `TieredCRCostModel.choose_tier`'s ascending scan with ties toward
    the faster tier, the last tier always feasible (UNBOUNDED invariant).
    Occupancy counts evicted-and-pending snapshots per tier (a restore
    consumed the slot — `admit_job` cleared the tier), plus the victims
    placed earlier in this very batch: the ``lax.scan`` walks the batch in
    victim order so a victim that doesn't fit spills while a later,
    smaller one may still claim the remaining space — exactly the
    sequential greedy the Python reference performs per `_evict` call."""
    tiers = cfg.cr_tiers
    assert tiers is not None
    n_tiers = tiers.n_tiers
    caps = jnp.asarray(tiers.capacity_mib, jnp.int32)
    if order is None:
        order = victim_order(tbl)
    ckpt_sorted = ckpt[order]
    lat_sorted = effective_save_lat(tbl)[order]          # [J, T]
    if all(c < 0 for c in tiers.capacity_mib):
        # every tier unbounded: no occupancy to carry, pure row-argmin
        tier_sorted = jnp.argmin(lat_sorted, axis=1).astype(jnp.int32)
    else:
        occ0 = tier_occupancy(tbl, n_tiers)
        mib_sorted = jnp.where(ckpt_sorted, tbl.state_mib[order], 0)

        def place(occ, x):
            want, mib, costs = x
            feasible = (caps < 0) | (occ + mib <= caps)
            tier = jnp.argmin(
                jnp.where(feasible, costs, MASK)).astype(jnp.int32)
            taken = jnp.where(want & (jnp.arange(n_tiers) == tier), mib, 0)
            return occ + taken, tier

        _, tier_sorted = jax.lax.scan(
            place, occ0, (ckpt_sorted, mib_sorted, lat_sorted))
    tier_sorted = jnp.where(ckpt_sorted, tier_sorted, 0)
    tier = jnp.zeros_like(tbl.ckpt_tier).at[order].set(tier_sorted)
    save = jnp.take_along_axis(
        effective_save_lat(tbl), tier[:, None], axis=1)[:, 0]
    save = jnp.where(ckpt, save, 0)
    return tier, save


def _tiered(cfg: SchedulerConfig) -> bool:
    return cfg.cr_tiers is not None and cfg.cr_tiers.n_tiers > 1


@jax.named_scope("sched.plan_evictions")
def plan_evictions(cfg: SchedulerConfig, tbl: JobTable, evictable: jax.Array,
                   idle: jax.Array, cpus_needed: jax.Array,
                   cheap: bool = False, order: Optional[jax.Array] = None):
    """The whole per-eviction decision, dispatched on ``cfg.kernel_backend``.

    Returns ``(planned, enough, order, placement)``: the minimal victim
    prefix, the feasibility bit, the victim order to reuse downstream
    (lax path only), and the precomputed ``(take_fast, save_cost)`` tier
    placement (pallas path only, ``None`` otherwise — `apply_evictions`
    computes it from ``order`` when absent).

    * ``"lax"`` — `victim_order` lexsort + `select_victims` cumsum cutoff;
      placement deferred to `place_checkpoints` inside `apply_evictions`.
    * ``"pallas"`` / ``"pallas_interpret"`` — the fused
      `kernels.sched_select` kernel: masked bitonic sort + prefix-sum
      cutoff + greedy T-tier placement over the effective save lattice in
      one ``pallas_call``.  ``"pallas"`` compiles it for the TPU and fails
      to lower anywhere else; ``"pallas_interpret"`` runs it in the Pallas
      interpreter on any backend.  Tables above
      `kernels.sched_select.ops.MAX_JOBS` rows raise.  Placement here is
      computed on the pre-feasibility-mask ``planned``; callers mask
      ``planned`` with an all-or-nothing scalar, and every table write in
      `apply_evictions` is gated on the masked victim set, so the results
      are bit-identical either way.

    The dispatch is a static Python branch on the (hashable, jit-static)
    config, so each backend traces its own program — toggling the flag
    selects a different lru-cached runner, never a retrace."""
    backend = cfg.kernel_backend
    if backend == "lax":
        if order is None:
            order = victim_order(tbl, cheap)
        planned, enough = select_victims(tbl, evictable, idle, cpus_needed,
                                         order)
        return planned, enough, order, None
    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown SchedulerConfig.kernel_backend "
                         f"{backend!r}: expected 'lax', 'pallas' or "
                         f"'pallas_interpret'")
    from repro.kernels.sched_select.ops import plan_evictions_fused
    interpret = backend == "pallas_interpret"
    tiered = _tiered(cfg)
    eff_lat = effective_save_lat(tbl)
    if tiered:
        caps = tuple(cfg.cr_tiers.capacity_mib)
        bounded = any(c >= 0 for c in caps)
        occ = tier_occupancy(tbl, cfg.cr_tiers.n_tiers)
        is_ckpt = tbl.jclass == CKPT
    else:
        caps = (-1,)
        bounded = False
        occ = jnp.zeros((1,), jnp.int32)
        is_ckpt = jnp.zeros_like(evictable)
    planned, enough, tier = plan_evictions_fused(
        tbl.priority, tbl.run_start, tbl.jid, eff_lat[..., 0],
        evictable, tbl.cpus, tbl.state_mib, is_ckpt, eff_lat,
        idle, cpus_needed, occ, jnp.asarray(caps, jnp.int32),
        cheap=cheap, tiered=tiered, bounded=bounded, interpret=interpret)
    placement = None
    if tiered:
        save = jnp.take_along_axis(eff_lat, tier[:, None], axis=1)[:, 0]
        placement = (tier, save)
    return planned, enough, None, placement


def apply_evictions(cfg: SchedulerConfig, t: jax.Array, tbl: JobTable,
                    planned: jax.Array,
                    order: Optional[jax.Array] = None,
                    placement: Optional[Tuple[jax.Array, jax.Array]] = None,
                    ) -> JobTable:
    """Lines 33-36 for every planned victim: checkpoint (or drop) and free.

    With ``cfg.cr_tiers`` set, each checkpointed victim is *placed* on a
    tier first (``placement`` precomputed by `plan_evictions`' fused
    kernel, else `place_checkpoints` in victim ``order``) and charged that
    tier's save cost; the placement is recorded in ``ckpt_tier`` so the
    later restore (`admit_job`) reads from the same tier."""
    is_ckpt = tbl.jclass == CKPT
    kill = planned & ~is_ckpt
    ckpt = planned & is_ckpt
    if _tiered(cfg):
        tier_of, save_cost = (place_checkpoints(cfg, tbl, ckpt, order)
                              if placement is None else placement)
        spilled = ckpt & (tier_of > 0)
    else:
        save_cost = effective_save_lat(tbl)[..., 0]
        tier_of = jnp.zeros_like(tbl.ckpt_tier)
        spilled = jnp.zeros_like(ckpt)
    return tbl._replace(
        state=jnp.where(
            ckpt, PENDING,
            jnp.where(kill, (KILLED if cfg.drop_killed else PENDING),
                      tbl.state)),
        progress=jnp.where(kill & (not cfg.drop_killed), 0, tbl.progress),
        overhead=tbl.overhead + jnp.where(ckpt, save_cost, 0),
        run_start=jnp.where(planned, -1, tbl.run_start),
        finish=jnp.where(kill & cfg.drop_killed, t, tbl.finish),
        n_preempt=tbl.n_preempt + planned.astype(jnp.int32),
        n_ckpt=tbl.n_ckpt + ckpt.astype(jnp.int32),
        ckpt_tier=jnp.where(ckpt, tier_of, tbl.ckpt_tier),
        n_spill=tbl.n_spill + spilled.astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Node placement (cfg.node_size; DESIGN.md §Node placement): the twins of
# `core.placement`'s node functions over the JobTable
# ---------------------------------------------------------------------------


def _cover(cfg: SchedulerConfig, tbl: JobTable, multi: jax.Array,
           value: jax.Array) -> jax.Array:
    """Per node ``[N]``, the sum of ``value`` over the ``multi`` rows whose
    whole-node range covers it (a difference array: O(J) scatter, O(N)
    cumsum).  Running ranges never overlap, so at most one row counts."""
    n = cfg.n_nodes
    lo = jnp.where(multi, tbl.node, n)
    hi = jnp.where(multi, tbl.node + tbl.cpus // cfg.node_size, n)
    v = jnp.where(multi, value, 0)
    diff = jnp.zeros((n + 1,), jnp.int32).at[lo].add(v).at[hi].add(-v)
    return jnp.cumsum(diff)[:n]


def node_free(cfg: SchedulerConfig, tbl: JobTable) -> jax.Array:
    """Free GPUs per node ``[N]``, counted from the RUNNING rows."""
    g, n = cfg.node_size, cfg.n_nodes
    running = tbl.state == RUNNING
    single = running & (tbl.cpus <= g)
    used = jax.ops.segment_sum(jnp.where(single, tbl.cpus, 0),
                               jnp.where(single, tbl.node, n),
                               num_segments=n + 1)[:n]
    return g - used - g * _cover(cfg, tbl, running & (tbl.cpus > g),
                                 jnp.ones_like(tbl.cpus))


@jax.named_scope("sched.place_nodes")
def fit_free(cfg: SchedulerConfig, free: jax.Array,
             cpus: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(first node, found)`` of a placement on free capacity: best fit
    for one node (fewest free GPUs that hold ``cpus``, lowest node on
    ties), the lowest start of ``cpus / G`` contiguous free nodes
    otherwise — `placement.fit_free`.  O(N) per queue position."""
    g, n = cfg.node_size, cfg.n_nodes
    ar = jnp.arange(n, dtype=jnp.int32)
    fits = free >= cpus
    best = jnp.argmin(jnp.where(fits, free, BIG)).astype(jnp.int32)
    k = cpus // g
    whole = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum((free == g).astype(jnp.int32))])
    window = (ar + k <= n) & (whole[jnp.minimum(ar + k, n)] - whole[ar] == k)
    start = jnp.argmax(window).astype(jnp.int32)
    one = cpus <= g
    return jnp.where(one, best, start), jnp.where(one, jnp.any(fits),
                                                  jnp.any(window))


def take_nodes(cfg: SchedulerConfig, free: jax.Array, at: jax.Array,
               cpus: jax.Array, admit: jax.Array) -> jax.Array:
    """``free`` after a job of ``cpus`` is placed from node ``at``."""
    g = cfg.node_size
    ar = jnp.arange(cfg.n_nodes, dtype=jnp.int32)
    span = jnp.maximum(cpus // g, 1)
    held = jnp.where(cpus <= g, cpus, g)
    return free - jnp.where(admit & (ar >= at) & (ar < at + span), held, 0)


@jax.named_scope("sched.node_victims")
def node_victim_order(cfg: SchedulerConfig, tbl: JobTable,
                      order: jax.Array):
    """``(rank[J], grouped[J], key[J])``: each row's rank in victim
    ``order``; the order regrouped by node, victim order kept inside a
    node (running single-node rows by node, every other row last); and
    each grouped row's node key (N for the rest).  Hoisted once a tick
    with the victim order where `_hoistable` holds: mid-pass, rows only
    leave the evictable set, and a row that stays keeps its node."""
    j = tbl.cpus.shape[0]
    rank = jnp.zeros((j,), jnp.int32).at[order].set(
        jnp.arange(j, dtype=jnp.int32))
    single = (tbl.state == RUNNING) & (tbl.cpus <= cfg.node_size)
    key = jnp.where(single, tbl.node, cfg.n_nodes)
    grouped = order[jnp.argsort(key[order], stable=True)]
    return rank, grouped, key[grouped]


@jax.named_scope("sched.node_victims")
def plan_node_evictions(cfg: SchedulerConfig, tbl: JobTable,
                        evictable: jax.Array, free: jax.Array,
                        cpus: jax.Array, ranks) -> Tuple[jax.Array, ...]:
    """The node-aware victim prefix, `placement.plan_on_nodes` over the
    table: ``(planned[J], enough, first node)``.  ``ranks`` is
    `node_victim_order`'s triple.

    One node (``cpus <= G``): a node's reach is the least rank at which
    its free GPUs plus its evictable jobs' up to that rank hold ``cpus``
    (a segmented cumsum over the node-grouped victim order), -1 where the
    free GPUs do already; a node under a multi-node job reaches at that
    job's rank.  Whole nodes: a node's clearing rank is the largest rank
    on it (-1 free, BIG if a job on it is not evictable), and a window's
    is the largest over its ``cpus / G`` nodes.  The least wins, lowest
    node on ties; its jobs up to that rank are the victims."""
    g, n = cfg.node_size, cfg.n_nodes
    rank, grouped, key = ranks
    running = tbl.state == RUNNING
    single = running & (tbl.cpus <= g)
    multi = running & (tbl.cpus > g)
    rows = jnp.arange(tbl.cpus.shape[0], dtype=jnp.int32)
    cover = _cover(cfg, tbl, multi, rows + 1) - 1
    at_cover = jnp.maximum(cover, 0)
    cover_rank = jnp.where((cover >= 0) & evictable[at_cover],
                           rank[at_cover], BIG)
    # one node: the reach of every node
    ev = evictable[grouped] & (key < n)
    gpus = jnp.where(ev, tbl.cpus[grouped], 0)
    cum = jnp.cumsum(gpus)
    base = jax.ops.segment_min(cum - gpus, key, num_segments=n + 1)
    hit = ev & (free[jnp.minimum(key, n - 1)] + cum - base[key] >= cpus)
    reach = jax.ops.segment_min(jnp.where(hit, rank[grouped], BIG), key,
                                num_segments=n + 1)[:n]
    reach = jnp.where(free >= cpus, -1,
                      jnp.where(cover >= 0, cover_rank,
                                jnp.minimum(reach, BIG)))
    node = jnp.argmin(reach).astype(jnp.int32)
    # whole nodes: the clearing rank of every window
    k = cpus // g
    ar = jnp.arange(n, dtype=jnp.int32)
    clear = jax.ops.segment_max(jnp.where(evictable, rank, BIG),
                                jnp.where(single, tbl.node, n),
                                num_segments=n + 1)[:n]
    clear = jnp.where(free == g, -1, jnp.where(cover >= 0, cover_rank, clear))
    inside = (ar[None, :] >= ar[:, None]) & (ar[None, :] < ar[:, None] + k)
    worst = jnp.max(jnp.where(inside, clear[None, :], -1), axis=1)
    worst = jnp.where(ar + k <= n, worst, BIG)
    start = jnp.argmin(worst).astype(jnp.int32)

    one = cpus <= g
    at = jnp.where(one, node, start)
    cut = jnp.where(one, reach[node], worst[start])
    span = jnp.where(tbl.cpus > g, tbl.cpus // g, 1)
    on = running & (tbl.node < at + jnp.where(one, 1, k)) & (
        tbl.node + span > at)
    return evictable & on & (rank <= cut), cut < BIG, at


# ---------------------------------------------------------------------------
# Reference pass: one Algorithm-1 admission, everything recomputed (O(J))
# ---------------------------------------------------------------------------


def _hoistable(cfg: SchedulerConfig, knobs: Optional[Knobs]) -> bool:
    """Whether one `victim_order` per tick serves every admission (the lax
    path's per-tick hoist).  Mid-pass admissions/evictions only move rows
    *out* of the evictable set when ``quantum >= 1`` (an admitted job has
    ``t - run_start == 0 < quantum``; an evicted one stops running), and
    untouched rows keep their keys — so the stale order restricted to the
    still-evictable rows is exactly the fresh order, which is all
    `select_victims` / `place_checkpoints` consume.  ``quantum == 0``
    (reachable: tests fuzz it) makes a just-admitted job immediately
    evictable under a *new* key, and a traced ``knobs.quantum`` cannot be
    inspected — both keep the faithful in-branch recompute."""
    return knobs is None and cfg.quantum >= 1


def _try_admit(cfg: SchedulerConfig, ent: jax.Array, t: jax.Array,
               tbl: JobTable, idx: jax.Array, eligible: jax.Array,
               cheap_victims: bool = False,
               knobs: Optional[Knobs] = None,
               order: Optional[jax.Array] = None) -> JobTable:
    """Process job ``idx`` (runner, lines 18-38); no-op unless eligible and
    still pending.  Kept as the un-optimized reference the incremental pass
    is benchmarked and property-tested against."""
    quantum = cfg.quantum if knobs is None else knobs.quantum
    running = tbl.state == RUNNING
    preempt_able = tbl.jclass != NONP

    ju = tbl.user[idx]
    jc = tbl.cpus[idx]
    same_user = tbl.user == ju
    non_p_usage = jnp.sum(jnp.where(running & same_user & ~preempt_able, tbl.cpus, 0))
    total_usage = jnp.sum(jnp.where(running & same_user, tbl.cpus, 0))
    busy = jnp.sum(jnp.where(running, tbl.cpus, 0))
    idle = cfg.cpu_total - busy
    entitled = ent[ju]

    job_non_p = tbl.jclass[idx] == NONP
    # line 23 (note >=): non-preemptible beyond (or exactly at) entitlement
    reject_23 = job_non_p & (non_p_usage + jc >= entitled)
    # line 26 (note >): enough idle -> run anyways
    admit_26 = idle > jc
    # line 28: request exceeds unused entitlement
    reject_28 = jc > entitled - total_usage

    # lines 31-36: victim selection among quantum-expired running jobs
    evictable = running & preempt_able & ((t - tbl.run_start) >= quantum)
    if cfg.avoid_self_eviction:                # beyond-paper flag
        evictable = evictable & ~same_user
    if cfg.victim_filter_over_entitlement:     # beyond-paper flag
        usage_per_user = jax.ops.segment_sum(
            jnp.where(running, tbl.cpus, 0), tbl.user, num_segments=ent.shape[0])
        over = usage_per_user[tbl.user] > ent[tbl.user]
        evictable = evictable & over

    planned, enough, order, placement = plan_evictions(
        cfg, tbl, evictable, idle, jc, cheap_victims, order)

    admit_evict = (~reject_23) & (~admit_26) & (~reject_28) & enough
    admit = eligible & (tbl.state[idx] == PENDING) & (~reject_23) & (
        admit_26 | admit_evict)
    do_evict = admit & (~admit_26)
    planned = planned & do_evict

    tbl = apply_evictions(cfg, t, tbl, planned, order, placement)
    return admit_job(tbl, idx, t, admit)


# ---------------------------------------------------------------------------
# The OMFS scheduling pass (policy contract: pass_fn(cfg, ent, t, tbl) -> tbl)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def make_omfs_pass(pass_depth: Optional[int] = None, incremental: bool = True,
                   cheap_victims: bool = False):
    """Build the Algorithm-1 scheduling pass for `core.engine`.
    Memoized so repeated `engine.simulate` calls reuse the jitted scan.

    ``incremental=True`` threads (usage[U], non_preemptible_usage[U], busy)
    through the fori_loop carry — O(1) per admission decision on the
    idle-admit fast path and on every rejection — and defers the victim
    lexsort+cumsum to a ``lax.cond`` branch taken only when eviction is
    actually needed.  ``incremental=False`` is the original reference pass.

    ``cheap_victims=True`` is the `omfs_cheap_victim` registry policy:
    victims order by ``(save_cost, priority, run_start, id)``.

    Every pass accepts an optional trailing ``knobs`` argument
    (`Knobs`): traced per-cell quantum / pass-depth overrides used by
    `engine.simulate_batch`.  ``knobs=None`` (every sequential caller)
    traces exactly the pre-batching program.

    With ``cfg.node_size`` set the incremental pass also carries the free
    GPUs per node (DESIGN.md §Node placement); the other passes and the
    fused kernel raise.
    """

    def pass_fn(cfg: SchedulerConfig, ent: jax.Array, t: jax.Array,
                tbl: JobTable, knobs: Optional[Knobs] = None) -> JobTable:
        n = tbl.cpus.shape[0]
        order, eligible = queue_order(tbl)
        depth = n if pass_depth is None else min(pass_depth, n)
        quantum = cfg.quantum if knobs is None else knobs.quantum
        nodes = cfg.node_size is not None
        if nodes and (not incremental or cfg.kernel_backend != "lax"):
            raise NotImplementedError(
                "SchedulerConfig.node_size is placed by the incremental "
                "OMFS pass on the lax backend only, not with "
                f"incremental={incremental}, kernel_backend="
                f"{cfg.kernel_backend!r}")

        # satellite hoist: one victim_order per tick (see _hoistable) —
        # the lax path reuses it across every admission of the pass; the
        # pallas kernel re-sorts internally (the fusion is the point), so
        # the hoisted lexsort would only be dead weight there.
        hoist = cfg.kernel_backend == "lax" and _hoistable(cfg, knobs)
        vorder0 = victim_order(tbl, cheap_victims) if hoist else None
        ranks0 = (node_victim_order(cfg, tbl, vorder0)
                  if hoist and nodes else None)

        if not incremental:
            def body_ref(i, tbl):
                idx = order[i]
                elig = eligible[idx]
                if knobs is not None:
                    elig = elig & (i < knobs.depth)
                return _try_admit(cfg, ent, t, tbl, idx, elig,
                                  cheap_victims, knobs, vorder0)
            with jax.named_scope("sched.admit"):
                return jax.lax.fori_loop(0, depth, body_ref, tbl)

        usage0, nonp0, busy0 = running_usage(tbl, ent.shape[0])
        if nodes:
            with jax.named_scope("sched.place_nodes"):
                free0 = node_free(cfg, tbl)
        else:
            free0 = None

        def body(i, carry):
            tbl, usage, nonp_usage, busy, free = carry
            idx = order[i]
            ju = tbl.user[idx]
            jc = tbl.cpus[idx]
            pending_now = eligible[idx] & (tbl.state[idx] == PENDING)
            if knobs is not None:
                pending_now = pending_now & (i < knobs.depth)
            job_non_p = tbl.jclass[idx] == NONP
            idle = cfg.cpu_total - busy
            # lines 23 / 26 / 28 from the carried aggregates — O(1)
            reject_23 = job_non_p & (nonp_usage[ju] + jc >= ent[ju])
            admit_26 = idle > jc
            reject_28 = jc > ent[ju] - usage[ju]
            ok = pending_now & ~reject_23
            if nodes:
                # line 26 on nodes: the idle GPUs must hold a placement
                at, fits = fit_free(cfg, free, jc)
                fast_admit = ok & admit_26 & fits
                need_evict = ok & ~fast_admit & ~reject_28
            else:
                fast_admit = ok & admit_26
                need_evict = ok & ~admit_26 & ~reject_28

            def evict_case(carry):
                tbl, usage, nonp_usage, busy, free = carry
                running = tbl.state == RUNNING
                preempt_able = tbl.jclass != NONP
                evictable = running & preempt_able & (
                    (t - tbl.run_start) >= quantum)
                if cfg.avoid_self_eviction:            # beyond-paper flag
                    evictable = evictable & (tbl.user != ju)
                if cfg.victim_filter_over_entitlement:  # beyond-paper flag
                    evictable = evictable & (usage[tbl.user] > ent[tbl.user])
                if nodes:
                    vorder = (victim_order(tbl, cheap_victims)
                              if vorder0 is None else vorder0)
                    ranks = (node_victim_order(cfg, tbl, vorder)
                             if ranks0 is None else ranks0)
                    planned, enough, at = plan_node_evictions(
                        cfg, tbl, evictable, free, jc, ranks)
                    placement = None
                else:
                    planned, enough, vorder, placement = plan_evictions(
                        cfg, tbl, evictable, idle, jc, cheap_victims,
                        vorder0)
                admit = enough
                planned = planned & admit
                freed = jnp.where(planned, tbl.cpus, 0)
                tbl = apply_evictions(cfg, t, tbl, planned, vorder, placement)
                usage = usage - jax.ops.segment_sum(
                    freed, tbl.user, num_segments=ent.shape[0])
                busy = busy - jnp.sum(freed)
                tbl = admit_job(tbl, idx, t, admit)
                if nodes:
                    tbl = _placed(tbl, idx, at, admit, admit & admit_26)
                    free = node_free(cfg, tbl)
                grant = jnp.where(admit, jc, 0)
                usage = usage.at[ju].add(grant)
                nonp_usage = nonp_usage.at[ju].add(
                    jnp.where(job_non_p, grant, 0))
                busy = busy + grant
                return tbl, usage, nonp_usage, busy, free

            tbl, usage, nonp_usage, busy, free = jax.lax.cond(
                need_evict, evict_case, lambda c: c,
                (tbl, usage, nonp_usage, busy, free))

            # idle-admit fast path: no victim machinery, O(1) updates
            tbl = admit_job(tbl, idx, t, fast_admit)
            if nodes:
                tbl = _placed(tbl, idx, at, fast_admit)
                free = take_nodes(cfg, free, at, jc, fast_admit)
            grant = jnp.where(fast_admit, jc, 0)
            usage = usage.at[ju].add(grant)
            nonp_usage = nonp_usage.at[ju].add(jnp.where(job_non_p, grant, 0))
            busy = busy + grant
            return tbl, usage, nonp_usage, busy, free

        with jax.named_scope("sched.admit"):
            tbl, _, _, _, _ = jax.lax.fori_loop(
                0, depth, body, (tbl, usage0, nonp0, busy0, free0))
        return tbl

    return pass_fn


def _placed(tbl: JobTable, idx: jax.Array, node: jax.Array,
            admit: jax.Array, frag: Optional[jax.Array] = None) -> JobTable:
    """Record job ``idx``'s placement from ``node`` iff ``admit``; count
    an admission by eviction with more GPUs idle than it needs (``frag``)."""
    tbl = tbl._replace(
        node=tbl.node.at[idx].set(jnp.where(admit, node, tbl.node[idx])))
    if frag is None:
        return tbl
    return tbl._replace(n_frag=tbl.n_frag.at[idx].add(
        frag.astype(jnp.int32)))


# ---------------------------------------------------------------------------
# Thin adapters over core.engine (kept for API compatibility)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "pass_depth"))
def omfs_tick(cfg: SchedulerConfig, ent: jax.Array, tbl: JobTable, t: jax.Array,
              pass_depth: Optional[int] = None) -> JobTable:
    """One engine tick with the (incremental) OMFS pass."""
    from repro.core import engine
    return engine.tick_jax(cfg, ent, tbl, t, make_omfs_pass(pass_depth))


def simulate_jax(
    users, jobs, cfg: SchedulerConfig, horizon: int,
    pass_depth: Optional[int] = None, incremental: bool = True,
    cheap_victims: bool = False,
) -> Tuple[JobTable, jax.Array]:
    """Run the full fleet simulation; returns (final table, busy[t] series)."""
    from repro.core import engine
    return engine.run_jax(users, jobs, cfg, horizon,
                          make_omfs_pass(pass_depth, incremental,
                                         cheap_victims))


def update_state_mib(tbl: JobTable, idx, state_mib,
                     config: SchedulerConfig) -> JobTable:
    """Grow/shrink job ``idx``'s checkpoint image at runtime — O(1) scatters.

    Real training state changes size (optimizer warmup grows it, quantized
    fast-tier saves shrink it); this hook rewrites ``state_mib`` and
    re-evaluates the per-tier cost columns with the SAME integer arithmetic
    `table_from_jobs` used at build time (`CRCostModel` evaluates on traced
    int32 just as on Python ints).  Shapes and dtypes are unchanged, so a
    jitted tick/scan compiled for the table keeps its cache — no re-trace.
    The Python backend needs no twin: it prices ``Job.state_mib`` at charge
    time, so assigning ``job.state_bytes`` is already enough.

    ``idx`` and ``state_mib`` may be Python ints or traced int32 scalars;
    ``config`` must be the same (static) config the pass runs under.
    """
    mib = jnp.clip(jnp.asarray(state_mib, jnp.int32), 0, MAX_STATE_MIB)
    flat = config.cr_overhead
    models = [config.tier_model(k) for k in range(config.n_cost_tiers)]
    row = lambda vals: jnp.stack(
        [jnp.asarray(v, jnp.int32) for v in vals])
    save_row = row([flat + m.save_cost(mib) for m in models])
    rsave_row = row([flat + m.recurrent_save_cost(mib) for m in models])
    restore_row = row([m.restore_cost(mib) for m in models])
    return tbl._replace(
        state_mib=tbl.state_mib.at[idx].set(mib),
        cost_save_lat=tbl.cost_save_lat.at[idx].set(save_row),
        cost_rsave_lat=tbl.cost_rsave_lat.at[idx].set(rsave_row),
        cost_restore_lat=tbl.cost_restore_lat.at[idx].set(restore_row),
    )


# ---------------------------------------------------------------------------
# Batch stacking + streaming-segment compaction (engine.simulate_batch /
# engine.simulate_stream build on these; DESIGN.md §Batched execution)
# ---------------------------------------------------------------------------

#: pad-row values per column; unlisted columns pad with 0.  A pad row is
#: inert by construction: ``submit=BIG`` never arrives (state stays UNSUB,
#: never PENDING/RUNNING), ``cpus=0`` so even a bug admitting one would
#: not move any aggregate, and ``jid=BIG`` keeps it last in every
#: tie-break.
_PAD_VALUES = {"jid": int(BIG), "submit": int(BIG), "run_start": -1,
               "first_start": -1, "finish": -1, "ckpt_tier": -1, "node": -1}


def pad_table(tbl: JobTable, rows: int) -> JobTable:
    """Grow ``tbl`` to ``rows`` with inert pad rows (identity if equal)."""
    return _pad(tbl, rows, jnp)


def _pad(tbl: JobTable, rows: int, xp) -> JobTable:
    """`pad_table` in array module ``xp``: ``jnp`` on the device, ``np`` on
    the host."""
    n = tbl.cpus.shape[0]
    if rows == n:
        return tbl
    assert rows > n, f"cannot shrink table {n} -> {rows}"
    k = rows - n
    return JobTable(**{
        f: xp.concatenate(
            [getattr(tbl, f),
             xp.full((k,) + getattr(tbl, f).shape[1:],
                     _PAD_VALUES.get(f, 0), xp.int32)])
        for f in JobTable._fields if getattr(tbl, f) is not None})


def is_pad(tbl: JobTable) -> jax.Array:
    """Mask of inert pad rows (see ``_PAD_VALUES``)."""
    return (tbl.jid == BIG) & (tbl.submit == BIG)


def host_is_pad(tbl: JobTable) -> np.ndarray:
    """`is_pad` of a table read back to the host, in numpy."""
    return ((tbl.jid == _PAD_VALUES["jid"])
            & (tbl.submit == _PAD_VALUES["submit"]))


def stack_tables(tables, ents) -> Tuple[JobTable, jax.Array]:
    """Stack per-cell ``(JobTable[Ji], ent[Ui])`` pairs onto a leading
    batch axis: pad every table to max(Ji) rows (inert rows, see
    `pad_table`) and every entitlement vector to max(Ui) users (0 CPUs —
    a user that owns no rows and can admit nothing), then stack.

    The result feeds ``jax.vmap`` over axis 0; per-cell schedules are
    unaffected by the padding because pad rows are never eligible, never
    running, and sort last in every queue/victim key."""
    rows = max(t.cpus.shape[0] for t in tables)
    n_users = max(e.shape[0] for e in ents)
    padded = [pad_table(t, rows) for t in tables]
    ents = [jnp.concatenate(
        [e, jnp.zeros((n_users - e.shape[0],), jnp.int32)])
        if e.shape[0] < n_users else e for e in ents]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)
    return stacked, jnp.stack(ents)


@partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("stream.insert_rows")
def insert_rows(tbl: JobTable, slots: jax.Array, rows: JobTable,
                valid: jax.Array) -> JobTable:
    """Segment-compaction scatter for the streaming engine: overwrite
    ``tbl[slots[i]]`` with ``rows[i]`` where ``valid[i]``, keep the
    current row otherwise.

    ``slots`` MUST be a permutation of ``arange(J)`` (the caller sends
    every free slot first — new arrivals, then pad rows clearing the
    compacted-out finished jobs — and the occupied slots as write-back
    targets), so scatter indices never collide and the update is
    order-independent.  Donates the table: between segments exactly one
    [J]-shaped table exists.  One compile per table shape — segment
    boundaries never re-trace (`python -m repro.analysis`, rule: retrace).
    """
    def put(col, new):
        v = valid.reshape(valid.shape + (1,) * (col.ndim - 1))
        return col.at[slots].set(jnp.where(v, new, col[slots]))

    return JobTable(**{f: put(getattr(tbl, f), getattr(rows, f))
                       for f in JobTable._fields
                       if getattr(tbl, f) is not None})


def pack_insert(rows: JobTable, slots: np.ndarray,
                valid: np.ndarray) -> np.ndarray:
    """`insert_rows`' host-side arguments as one int32 ``[J, C]`` array:
    the table's columns in field order (a ``[J, T]`` lattice takes T
    columns), then ``slots``, then ``valid``.  The device pays per array
    transferred, so the stream boundary sends this one (`insert_packed`)."""
    return np.concatenate(
        [c.reshape(len(slots), -1) for c in rows if c is not None]
        + [slots[:, None], valid[:, None]], axis=1, dtype=np.int32)


@partial(jax.jit, donate_argnums=(0,))
def insert_packed(tbl: JobTable, packed: jax.Array) -> JobTable:
    """`insert_rows` with its arguments packed by `pack_insert`."""
    cols, at = {}, 0
    for f in JobTable._fields:
        col = getattr(tbl, f)
        if col is None:
            continue
        width = col.shape[1] if col.ndim == 2 else 1
        cols[f] = packed[:, at:at + width].reshape(col.shape)
        at += width
    return insert_rows(tbl, packed[:, at], JobTable(**cols),
                       packed[:, at + 1] != 0)


def signature_from_table(tbl: JobTable):
    """Same shape as SimResult.schedule_signature() for equivalence tests."""
    t = jax.device_get(tbl)
    return tuple(
        (int(i), int(t.state[i]), int(t.first_start[i]), int(t.finish[i]),
         int(t.progress[i]), int(t.n_preempt[i]), int(t.n_ckpt[i]))
        for i in range(t.state.shape[0])
    )


def tables_equal(a: JobTable, b: JobTable) -> bool:
    """Fast whole-table schedule equality (the fields of the signature)."""
    fields = ("state", "first_start", "finish", "progress", "n_preempt",
              "n_ckpt")
    a, b = jax.device_get(a), jax.device_get(b)
    return all(np.array_equal(getattr(a, f), getattr(b, f)) for f in fields)
