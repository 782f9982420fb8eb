"""Baseline scheduling policies vectorized in JAX over the shared JobTable.

Twins of `core.baselines` (static_partition / capping / fcfs / backfill /
backfill_cr) for the engine's "jax" backend, built from the same JobTable
primitives as the OMFS pass (`core.omfs_jax`: queue_order, admit_job,
select_victims, apply_evictions) so every policy runs at fleet scale on the
same representation.  Property tests (tests/test_policies_equivalence.py)
assert each produces bit-identical schedules to its Python twin on
randomized workloads, exactly like the OMFS equivalence suite.

All passes share the engine's policy contract — ``pass_fn(cfg, ent, t, tbl,
knobs=None) -> tbl``, where ``knobs`` carries the traced per-cell
quantum/pass-depth overrides of `engine.simulate_batch` — and thread their
admission aggregates (per-user usage, busy,
head reservation) through the ``fori_loop`` carry: O(1) per queue position
for everything but backfill's once-per-tick reservation sort.

Size-aware C/R costs come for free: the shared `admit_job` /
`apply_evictions` primitives charge the JobTable's precomputed ``[J, T]``
cost lattice (``cost_save_lat`` / ``cost_rsave_lat`` / ``cost_restore_lat``,
`core.crcost`), so backfill_cr's preemptions and every restart pay the same
size- and delta-dependent overhead as the Python twins (first saves price
the full image, recurrent saves the measured delta).  The same holds for
tiered eviction placement (``cfg.cr_tiers``): `apply_evictions` places each
backfill_cr victim's snapshot (cheapest feasible tier across the whole
hierarchy, in the standard victim order — the same order
`baselines.make_backfill` walks `sorted_victims`) and `admit_job` charges
the placed tier's restore cost, with no baseline-specific code here.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.omfs_jax import (
    BIG,
    NONP,
    PENDING,
    RUNNING,
    JobTable,
    Knobs,
    admit_job,
    apply_evictions,
    plan_evictions,
    queue_order,
    running_usage,
)
from repro.core.types import SchedulerConfig


def _depth(n: int, pass_depth: Optional[int]) -> int:
    return n if pass_depth is None else min(pass_depth, n)


def _mask_depth(elig: jax.Array, i, knobs: Optional[Knobs]) -> jax.Array:
    """Batched pass-depth bound: mask queue positions past ``knobs.depth``.

    Result-identical to the static ``_depth`` loop truncation — a masked
    iteration admits nothing and updates no aggregate — but keeps the trip
    count static so one compiled program serves every depth in a sweep
    (`engine.simulate_batch`)."""
    return elig if knobs is None else elig & (i < knobs.depth)


def _est_remaining(work, overhead, progress, error: float):
    """baselines._estimated_remaining: true remaining inflated by ``error``."""
    rem = work + overhead - progress
    if error:
        rem = jnp.ceil(rem.astype(jnp.float32) * (1.0 + error)).astype(jnp.int32)
    return jnp.maximum(rem, 1)


# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def make_static_partition_pass(pass_depth: Optional[int] = None):
    """Hard divisions: user blocks sized by entitlement; no pooling at all."""

    def pass_fn(cfg: SchedulerConfig, ent, t, tbl: JobTable,
                knobs: Optional[Knobs] = None) -> JobTable:
        n = tbl.cpus.shape[0]
        order, eligible = queue_order(tbl)
        usage0, _, _ = running_usage(tbl, ent.shape[0])

        def body(i, carry):
            tbl, usage = carry
            idx = order[i]
            ju, jc = tbl.user[idx], tbl.cpus[idx]
            admit = (_mask_depth(eligible[idx], i, knobs)
                     & (tbl.state[idx] == PENDING)
                     & (usage[ju] + jc <= ent[ju]))
            tbl = admit_job(tbl, idx, t, admit)
            usage = usage.at[ju].add(jnp.where(admit, jc, 0))
            return tbl, usage

        with jax.named_scope("sched.admit"):
            tbl, _ = jax.lax.fori_loop(0, _depth(n, pass_depth), body,
                                       (tbl, usage0))
        return tbl

    return pass_fn


@lru_cache(maxsize=None)
def make_capping_pass(pass_depth: Optional[int] = None):
    """Pooled CPUs + per-user cap at the entitlement (no over-subscription)."""

    def pass_fn(cfg: SchedulerConfig, ent, t, tbl: JobTable,
                knobs: Optional[Knobs] = None) -> JobTable:
        n = tbl.cpus.shape[0]
        order, eligible = queue_order(tbl)
        usage0, _, busy0 = running_usage(tbl, ent.shape[0])

        def body(i, carry):
            tbl, usage, busy = carry
            idx = order[i]
            ju, jc = tbl.user[idx], tbl.cpus[idx]
            admit = (_mask_depth(eligible[idx], i, knobs)
                     & (tbl.state[idx] == PENDING)
                     & (usage[ju] + jc <= ent[ju])
                     & (cfg.cpu_total - busy >= jc))
            tbl = admit_job(tbl, idx, t, admit)
            grant = jnp.where(admit, jc, 0)
            return tbl, usage.at[ju].add(grant), busy + grant

        with jax.named_scope("sched.admit"):
            tbl, _, _ = jax.lax.fori_loop(
                0, _depth(n, pass_depth), body, (tbl, usage0, busy0))
        return tbl

    return pass_fn


@lru_cache(maxsize=None)
def make_fcfs_pass(pass_depth: Optional[int] = None):
    """Strict first-come-first-served: the queue head blocks everyone."""

    def pass_fn(cfg: SchedulerConfig, ent, t, tbl: JobTable,
                knobs: Optional[Knobs] = None) -> JobTable:
        n = tbl.cpus.shape[0]
        order, eligible = queue_order(tbl)
        _, _, busy0 = running_usage(tbl, ent.shape[0])

        def body(i, carry):
            tbl, busy, blocked = carry
            idx = order[i]
            jc = tbl.cpus[idx]
            elig = _mask_depth(eligible[idx], i, knobs) & (
                tbl.state[idx] == PENDING)
            fits = cfg.cpu_total - busy >= jc
            admit = elig & ~blocked & fits
            blocked = blocked | (elig & ~fits)   # head blocked: noone overtakes
            tbl = admit_job(tbl, idx, t, admit)
            return tbl, busy + jnp.where(admit, jc, 0), blocked

        with jax.named_scope("sched.admit"):
            tbl, _, _ = jax.lax.fori_loop(
                0, _depth(n, pass_depth), body,
                (tbl, busy0, jnp.asarray(False)))
        return tbl

    return pass_fn


@lru_cache(maxsize=None)
def make_backfill_pass(estimate_error: float = 0.0, with_cr: bool = False,
                       pass_depth: Optional[int] = None):
    """Conservative backfill; optionally with C/R preemption (Niu et al.).

    The head job's reservation is computed once per tick from estimated
    remaining runtimes (sort + cumsum over running jobs); the rest of the
    queue is a fori_loop with the (busy, reservation) carry."""

    def pass_fn(cfg: SchedulerConfig, ent, t, tbl: JobTable,
                knobs: Optional[Knobs] = None) -> JobTable:
        n = tbl.cpus.shape[0]
        quantum = cfg.quantum if knobs is None else knobs.quantum
        order, eligible = queue_order(tbl)
        any_pending = jnp.any(eligible)
        running = tbl.state == RUNNING
        busy = jnp.sum(jnp.where(running, tbl.cpus, 0))
        idle = cfg.cpu_total - busy
        head = order[0]
        head_cpus = tbl.cpus[head]
        est = _est_remaining(tbl.work, tbl.overhead, tbl.progress,
                             estimate_error)

        head_fits = any_pending & (idle >= head_cpus)

        # Reservation: earliest tick the head fits, assuming running jobs end
        # at their estimates (baselines._reservation_time).  Computed from the
        # pre-eviction state; only consumed when the head ends up waiting.
        # tie-break by true job id (not row position): order-isomorphic to
        # arange on monolithic tables (rows sorted by id) and stable when the
        # streaming engine recycles slots out of id order
        key = jnp.where(running, est, BIG)
        with jax.named_scope("sched.queue_order"):
            ordr = jnp.lexsort((tbl.jid, key))
        cum = idle + jnp.cumsum(jnp.where(running[ordr], tbl.cpus[ordr], 0))
        crossed = cum >= head_cpus
        reservation = jnp.where(
            jnp.any(crossed),
            t + est[ordr][jnp.argmax(crossed)],
            t + jnp.sum(jnp.where(running, est, 0)) + 1)

        head_admit = head_fits
        if with_cr:
            # Niu et al.: preempt checkpointable *backfilled* jobs to start
            # the head now instead of waiting for the reservation.
            evictable = (running & (tbl.jclass != NONP)
                         & ((t - tbl.run_start) >= quantum)
                         & (tbl.backfilled > 0))
            # plan_evictions dispatches lax/pallas and hands back the
            # victim order (or fused placement) so apply_evictions never
            # recomputes the lexsort
            planned, enough, vorder, placement = plan_evictions(
                cfg, tbl, evictable, idle, head_cpus)
            do_cr = any_pending & ~head_fits & enough
            planned = planned & do_cr
            busy = busy - jnp.sum(jnp.where(planned, tbl.cpus, 0))
            tbl = apply_evictions(cfg, t, tbl, planned, vorder, placement)
            head_admit = head_fits | do_cr

        tbl = admit_job(tbl, head, t, head_admit)
        busy = busy + jnp.where(head_admit, head_cpus, 0)
        head_start = jnp.where(any_pending & ~head_admit, reservation, BIG)

        def body(i, carry):
            tbl, busy = carry
            idx = order[i]
            jc = tbl.cpus[idx]
            elig = _mask_depth(eligible[idx], i, knobs) & (
                tbl.state[idx] == PENDING)
            cur_idle = cfg.cpu_total - busy
            fits = cur_idle >= jc
            # conservative: only backfill if the head reservation is kept
            no_delay = ((t + est[idx] <= head_start)
                        | (cur_idle - jc >= head_cpus))
            admit = elig & fits & no_delay
            tbl = admit_job(tbl, idx, t, admit)
            tbl = tbl._replace(backfilled=tbl.backfilled.at[idx].set(
                jnp.where(admit, 1, tbl.backfilled[idx])))
            return tbl, busy + jnp.where(admit, jc, 0)

        with jax.named_scope("sched.admit"):
            tbl, _ = jax.lax.fori_loop(1, _depth(n, pass_depth), body,
                                       (tbl, busy))
        return tbl

    return pass_fn


JAX_BASELINES = {
    "static_partition": make_static_partition_pass,
    "capping": make_capping_pass,
    "fcfs": make_fcfs_pass,
    "backfill": lambda pass_depth=None: make_backfill_pass(
        pass_depth=pass_depth),
    "backfill_cr": lambda pass_depth=None: make_backfill_pass(
        with_cr=True, pass_depth=pass_depth),
}
