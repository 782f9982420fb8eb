"""Pallas kernel: fused victim-select + tier-placement (paper lines 32-36).

One ``pallas_call`` fuses the whole per-eviction decision that
``core/omfs_jax.py`` otherwise spells as ``jnp.lexsort`` + gather + cumsum
+ ``lax.scan``:

* masked victim keys — non-evictable rows pushed to ``MASK`` so the sort
  brings the victim candidates to the front in victim-key order
  (faithful ``(priority, run_start, jid)`` or cheap-victim
  ``(save_cost, priority, run_start, jid)`` — the save cost being the
  delta-aware effective tier-0 column), with the row index as a final
  tie-break so the order is total;
* a bitonic sort over the padded power-of-two tile, written as roll-based
  compare-exchange so it is gather-free — VPU selects and ``pltpu.roll``
  lane/sublane rotations only;
* a log-step prefix sum of the freed CPUs (lanes, then rows) and the
  paper's minimal-prefix capacity cutoff;
* the greedy cheapest-feasible T-tier placement over the ``[J, T]``
  effective save-cost lattice (the T columns ride the sort as extra value
  rows), bounded by the last planned position (the victim prefix), not
  the full tile.  Tier choice is a static ascending strict-``<`` argmin —
  first-occurrence semantics, bit-identical to
  `TieredCRCostModel.choose_tier` (ties toward the faster tier).

Layout: every column is an int32 ``[R, 128]`` tile holding the padded
length ``Jp = 128·R`` (a power of two, R = 1 or R >= 8) in row-major
order, so position ``i`` sits at sublane row ``i >> 7``, lane ``i & 127``.  The
kernel inherits the engine's integer-grid bit-exactness: there is no
arithmetic here that could round differently from the lax path.  The
sort's stage loop carries traced ``(k, j)`` shift amounts, so the traced
program is O(1) in ``Jp``; the per-tier placement unroll is O(T) — T is a
small static (2-4 in practice).

What Mosaic lowers, and so what this kernel is written with: rotations
by a traced amount only through ``pltpu.roll`` (``jnp.roll`` with a
traced shift becomes a ``dynamic_slice`` Mosaic refuses), which follows
``jnp.roll``'s direction (element ``i`` moves to ``i + shift``) and takes
a shift in ``[0, size)``; no scalar reads or writes at a traced lane — the
placement loop loads the whole 128-lane row at a traced sublane offset
and picks its lane with a one-hot reduction; scalars in and out live in
SMEM.  Single-block kernel: everything sits in VMEM at once, so
``ops.MAX_ROWS`` caps ``Jp`` at what compiles within the default scoped
VMEM limit of a v5e chip.  Compiled for v5e, every variant fits at
Jp = 65,536; at 131,072 the bounded-tier and the cheap unbounded-tier
variants run out of VMEM, and at 262,144 all of them do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: key for masked (non-evictable / padding) rows — sorts after any real key;
#: also the infeasible-tier sentinel in the placement argmin
MASK = jnp.iinfo(jnp.int32).max
#: lanes per tile row (the TPU vreg width)
LANES = 128


def _lex_lt(a, b):
    """Elementwise lexicographic ``a < b`` over equal-length key tuples."""
    lt, eq = a[0] < b[0], a[0] == b[0]
    for ai, bi in zip(a[1:], b[1:]):
        lt = lt | (eq & (ai < bi))
        eq = eq & (ai == bi)
    return lt


def sched_select_kernel(prio_ref, rstart_ref, jid_ref, key_ref, evict_ref,
                        cpus_ref, mib_ref, ckpt_ref, *rest,
                        cheap: bool, tiered: bool, bounded: bool,
                        n_tiers: int):
    """Fused plan: sorted-order rows, victim mask, T-tier placement.

    Inputs are ``[R, 128]`` int32 tiles (see the module docstring): the
    victim-key columns, the evictable/cpus columns, ``mib_ref``/``ckpt_ref``
    (state size and checkpointability) and — in ``rest`` — the ``n_tiers``
    effective save-lattice columns followed by ``scal_ref``, an SMEM
    ``[1, 2 + 2T]`` pack of (idle, cpus_needed, occ[0..T-1], cap[0..T-1]).
    Outputs (next in ``rest``): ``row_ref``/``planned_ref``/``tier_ref``
    are the sorted-position row index / planned-victim flag / placed tier
    (scattered back to row order by the wrapper), ``enough_ref`` the SMEM
    ``[1, 1]`` feasibility bit.  With ``bounded`` the last entry is a VMEM
    ``[2 + T, R, 128]`` scratch the placement loop reads rows from.
    """
    lat_refs = rest[:n_tiers]
    scal_ref = rest[n_tiers]
    row_ref, planned_ref, tier_ref, enough_ref = rest[n_tiers + 1:n_tiers + 5]
    shape = prio_ref.shape
    rows = shape[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    idx = sub * LANES + lane
    evict = evict_ref[...]
    is_victim = evict == 1

    def masked(ref):
        return jnp.where(is_victim, ref[...], MASK)

    # most-significant first; idx makes the order total (bitonic is not
    # stable, but every real tie is already broken by the unique jid)
    keys = [masked(prio_ref), masked(rstart_ref), masked(jid_ref), idx]
    if cheap:
        keys.insert(0, masked(key_ref))
    n_keys = len(keys)
    vals = [evict, cpus_ref[...]]
    if tiered:
        vals += [mib_ref[...], ckpt_ref[...]]
        vals += [r[...] for r in lat_refs]
    arrays = tuple(keys + vals)

    def rotate(x, s_lane, s_row):
        # element at flat i moves s_lane lanes within its row, then s_row
        # rows; one of the two amounts is always zero here
        x = pltpu.roll(x, s_lane, 1)
        return x if rows == 1 else pltpu.roll(x, s_row, 0)

    def stage(_, carry):
        k, j, arrs = carry
        # partner i ^ j (j a power of two): i + j where bit j of i is
        # clear, i - j where it is set.  j < 128 moves along lanes (never
        # across a row for the positions that read it), j >= 128 moves
        # whole rows.  One rotation pair per stage costs twice the rolls
        # of a lax.cond between the two, but compiles in a third the time.
        jl = j & (LANES - 1)
        js = j >> 7
        back_l = (LANES - jl) & (LANES - 1)
        back_s = (rows - js) & (rows - 1)
        clear = (idx & j) == 0
        part = tuple(jnp.where(clear, rotate(a, back_l, back_s),
                               rotate(a, jl, js)) for a in arrs)
        # ascending blocks of size k: position i keeps the smaller element
        # iff its direction bit and pair side agree
        want_min = ((idx & k) == 0) == clear
        # (a select between two bool vectors does not lower; and/or does)
        take_other = ((want_min & _lex_lt(part[:n_keys], arrs[:n_keys]))
                      | (~want_min & _lex_lt(arrs[:n_keys], part[:n_keys])))
        arrs = tuple(jnp.where(take_other, p, a) for p, a in zip(part, arrs))
        j = j // 2
        k = jnp.where(j == 0, k * 2, k)
        j = jnp.where(j == 0, k // 2, j)
        return k, j, arrs

    log2 = (rows * LANES).bit_length() - 1
    n_stages = log2 * (log2 + 1) // 2
    _, _, arrays = jax.lax.fori_loop(
        0, n_stages, stage, (jnp.int32(2), jnp.int32(1), arrays))

    row_s = arrays[n_keys - 1]
    live = arrays[n_keys] == 1
    freed = jnp.where(live, arrays[n_keys + 1], 0)

    def lane_pfx(s, x):        # Hillis-Steele inclusive prefix along lanes
        d = jnp.left_shift(jnp.int32(1), s)
        return x + jnp.where(lane >= d, pltpu.roll(x, d, 1), 0)

    def row_pfx(s, x):         # ... and along rows
        d = jnp.left_shift(jnp.int32(1), s)
        return x + jnp.where(sub >= d, pltpu.roll(x, d, 0), 0)

    in_row = jax.lax.fori_loop(0, LANES.bit_length() - 1, lane_pfx, freed)
    row_tot = jnp.broadcast_to(jnp.sum(freed, axis=1, keepdims=True), shape)
    before = jax.lax.fori_loop(0, rows.bit_length() - 1, row_pfx,
                               row_tot) - row_tot
    cum = in_row + before

    idle = scal_ref[0, 0]
    cpus_needed = scal_ref[0, 1]
    need = jnp.maximum(cpus_needed - idle, 0)
    planned = live & (cum - freed < need)      # the minimal victim prefix
    enough_ref[0, 0] = (idle + jnp.sum(freed) >= cpus_needed).astype(
        jnp.int32)
    row_ref[...] = row_s
    planned_ref[...] = planned.astype(jnp.int32)

    if not tiered:
        tier_ref[...] = jnp.zeros(shape, jnp.int32)
        return
    want = planned & (arrays[n_keys + 3] == 1)
    lats = arrays[n_keys + 4:]
    if not bounded:                # every tier unbounded: elementwise argmin
        best_c, best_t = lats[0], jnp.zeros(shape, jnp.int32)
        for k in range(1, n_tiers):
            better = lats[k] < best_c          # strict: ties keep lower k
            best_c = jnp.where(better, lats[k], best_c)
            best_t = jnp.where(better, k, best_t)
        tier_ref[...] = jnp.where(want, best_t, 0)
        return

    # greedy is sequential by nature (a skipped victim frees space a later
    # smaller one may claim) but only over the victim prefix
    buf_ref = rest[n_tiers + 5]
    buf_ref[0] = want.astype(jnp.int32)
    buf_ref[1] = arrays[n_keys + 2]
    for k in range(n_tiers):
        buf_ref[2 + k] = lats[k]
    tier_ref[...] = jnp.zeros(shape, jnp.int32)
    occs = tuple(scal_ref[0, 2 + k] for k in range(n_tiers))
    caps = tuple(scal_ref[0, 2 + n_tiers + k] for k in range(n_tiers))
    stop = jnp.max(jnp.where(planned, idx + 1, 0))
    row_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def greedy(i, occs):
        r = i >> 7
        hit = row_lane == (i & (LANES - 1))

        def at(b):             # buf_ref[b] at flat position i
            return jnp.sum(jnp.where(hit, buf_ref[b, pl.ds(r, 1), :], 0))

        w = at(0)
        m = at(1)
        best_c = jnp.int32(MASK)
        best_t = jnp.int32(0)
        for k in range(n_tiers):           # static unroll, T is small
            feas = (caps[k] < 0) | (occs[k] + m <= caps[k])
            c = jnp.where(feas, at(2 + k), MASK)
            better = c < best_c            # strict: ties keep lower k
            best_c = jnp.where(better, c, best_c)
            best_t = jnp.where(better, k, best_t)
        taken = w == 1
        cur = tier_ref[pl.ds(r, 1), :]
        tier_ref[pl.ds(r, 1), :] = jnp.where(hit & taken, best_t, cur)
        return tuple(occs[k] + jnp.where(taken & (best_t == k), m, 0)
                     for k in range(n_tiers))

    jax.lax.fori_loop(0, stop, greedy, occs)

