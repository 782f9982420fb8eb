"""In-scan event capture for the JAX backend: fixed shapes, zero retrace.

One jitted tick cannot append to a Python list, so the instrumented scan
captures three fixed-shape outputs per tick:

* ``counts[E]``  — exact per-type event counts (never lossy; the metrics
  registry and the DROPPED accounting are built on these),
* ``ring[R, 3]`` — a bounded per-tick event ring of ``(etype, jid, arg)``
  rows.  Events are laid out in (etype, table-row) order; each event's
  ring slot is its prefix position (cumsum of the flattened flag matrix),
  and events past the capacity R scatter with ``mode="drop"`` — dropped,
  never aliased,
* ``dropped``    — scalar: how many events did not fit this tick.  The
  engine surfaces it per tick; with ``R >= lossless_ring_size(J)`` it is
  provably always 0 (`obs.events.MAX_EVENTS_PER_JOB_PER_TICK`).

Everything is int32 on the device; `decode_events` reconstructs the typed
`Event` list host-side after the scan (one `device_get`, no per-tick host
sync) and applies the canonical ``(etype, jid)`` per-tick sort — the ring's
(etype, row) write order already equals it for monolithic tables (rows are
sorted by id) but not for the streaming engine's recycled slots.

The capture is a pure function of ``(pre, post, t)`` — the SAME diff rules
as the Python emitter (`obs.events`, schema table there).  It allocates no
new table columns and mutates nothing: the uninstrumented tick program is
byte-identical with instrumentation off (`repro.analysis` rule
``event-schema`` checks the confinement; the retrace audit checks the
instrumented runners compile exactly once).
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.omfs_jax import DONE, PENDING, RUNNING, UNSUB, JobTable
from repro.obs.events import Event, EventType, N_EVENT_TYPES

#: ring row layout
RING_FIELDS = ("etype", "jid", "arg")


def event_flags(pre: JobTable, post: JobTable, t: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """``(flags[E, J], args[E, J])`` for one tick diff — the schema table
    of `obs.events`, vectorized.  Row order = EventType code order, so the
    flattened matrix enumerates events in (etype, table-row) order."""
    start = (post.state == RUNNING) & (post.run_start == t)
    rules = {
        EventType.SUBMIT: ((pre.state == UNSUB) & (pre.submit <= t),
                           post.cpus),
        EventType.START: (start, post.cpus),
        EventType.RESTORE: (start & (pre.n_ckpt > 0),
                            jnp.maximum(pre.ckpt_tier, 0)),
        EventType.EVICT: (post.n_preempt > pre.n_preempt, post.cpus),
        EventType.SAVE: (post.n_ckpt > pre.n_ckpt, post.ckpt_tier),
        EventType.SPILL: (post.n_spill > pre.n_spill, post.ckpt_tier),
        EventType.FINISH: ((post.state == DONE) & (post.finish == t),
                           post.progress),
        EventType.DEFER: (post.state == PENDING, post.cpus),
    }
    assert len(rules) == N_EVENT_TYPES
    flags = jnp.stack([rules[EventType(e)][0] for e in range(N_EVENT_TYPES)])
    args = jnp.stack([jnp.asarray(rules[EventType(e)][1], jnp.int32)
                      for e in range(N_EVENT_TYPES)])
    return flags, args


@jax.named_scope("sched.capture")
def capture_tick(pre: JobTable, post: JobTable, t: jax.Array, ring_size: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One tick's ``(counts[E], ring[R, 3], dropped)`` — all int32, shapes
    static in ``ring_size``, so the instrumented scan compiles once."""
    flags, args = event_flags(pre, post, t)
    counts = jnp.sum(flags, axis=1, dtype=jnp.int32)
    flat = flags.reshape(-1)
    pos = jnp.cumsum(flat.astype(jnp.int32)) - 1
    # non-events and overflow both land out of bounds -> scattered with
    # mode="drop": dropped, never silently aliased onto a live slot
    slot = jnp.where(flat, pos, ring_size)
    etype = jnp.repeat(jnp.arange(N_EVENT_TYPES, dtype=jnp.int32),
                       pre.jid.shape[0])
    jid = jnp.tile(post.jid, N_EVENT_TYPES)
    rows = jnp.stack([etype, jid, args.reshape(-1)], axis=1)
    ring = jnp.full((ring_size, len(RING_FIELDS)), -1, jnp.int32)
    ring = ring.at[slot].set(rows, mode="drop")
    total = jnp.sum(counts)
    dropped = jnp.maximum(total - ring_size, 0)
    return counts, ring, dropped


def decode_events(counts, ring, dropped, t0: int = 0) -> List[Event]:
    """Host-side reader: scan outputs -> canonical per-tick-sorted Events.

    ``counts``: [T, E], ``ring``: [T, R, 3], ``dropped``: [T] (device or
    host arrays).  Ring slots are contiguous (an event's slot is its
    prefix position), so tick t's valid rows are
    ``ring[t, :min(counts[t].sum(), R)]``; they are re-sorted to the
    canonical (etype, jid) order before being emitted.
    """
    counts = np.asarray(counts)
    ring = np.asarray(ring)
    dropped = np.asarray(dropped)
    cap = ring.shape[1]
    out: List[Event] = []
    totals = counts.sum(axis=1)
    for t in range(counts.shape[0]):
        k = int(min(totals[t], cap))
        if k == 0:
            continue
        rows = ring[t, :k]
        order = np.lexsort((rows[:, 1], rows[:, 0]))   # (etype, jid)
        for e, j, a in rows[order]:
            out.append(Event(t0 + t, int(e), int(j), int(a)))
    return out
