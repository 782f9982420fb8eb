"""Jit'd wrapper for the fused victim-select/placement kernel.

`plan_evictions_fused` is what `core/omfs_jax.plan_evictions` dispatches
to when ``SchedulerConfig.kernel_backend`` selects the pallas path.  The
wrapper pads the columns to a power-of-two length laid out row-major as
``[Jp/128, 128]`` tiles of one row or of 8 rows and more (pad rows carry
``evictable=0`` so the in-kernel mask retires them), splits the ``[J, T]``
effective save lattice into T tiles, packs the ``2 + 2T`` scalars, and
scatters the sorted-position outputs back to row order — the only pieces
kept outside the kernel, all O(J).

Outputs are bit-identical to `ref.plan_evictions_ref` (and hence to the
lax path) by construction: the kernel's masked total order restricted to
the evictable rows equals the lexsort order restricted to them, and the
planned/placement decisions depend on nothing else — padding and
non-evictable rows contribute zero CPUs and can never be planned.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sched_select.kernel import LANES, sched_select_kernel
from repro.kernels.sched_select.ref import plan_evictions_ref  # noqa: F401

#: smallest multi-row tile: Mosaic rotates along sublanes only in whole
#: (8, 128) vregs, so a tile is one row or a multiple of eight
MIN_ROWS = 8
#: largest tile (rows of 128) the single-block kernel compiles for within a
#: v5e chip's default scoped VMEM; ``MAX_ROWS * 128`` is the row limit
MAX_ROWS = 512
MAX_JOBS = MAX_ROWS * LANES


def _padded_rows(j: int) -> int:
    rows = 1 << max(0, -(-j // LANES) - 1).bit_length()
    return rows if rows == 1 else max(MIN_ROWS, rows)


@partial(jax.jit, static_argnames=("cheap", "tiered", "bounded", "interpret"))
def plan_evictions_fused(prio, run_start, jid, key_cost, evictable, cpus,
                         state_mib, is_ckpt, save_lat, idle, cpus_needed,
                         occ, cap, *, interpret: bool, cheap: bool = False,
                         tiered: bool = False, bounded: bool = False):
    """Fused plan over bare columns.

    ``planned`` is the paper's minimal victim prefix (lines 32-36) in the
    requested victim-key order (``key_cost`` — the delta-aware effective
    tier-0 save cost — leads the key when ``cheap``), ``enough`` the
    feasibility bit, and ``tier`` the greedy cheapest-feasible placement
    of the checkpointable planned victims over the ``[J, T]`` effective
    save lattice (all-zero when ``tiered=False``).  ``occ``/``cap`` are
    ``[T]`` per-tier occupancy/capacity vectors (``cap[k] < 0`` =
    unbounded); ``bounded`` is the static "some tier has finite capacity"
    flag.  ``interpret`` runs the kernel in the Pallas interpreter (any
    backend) instead of compiling it for the TPU.  Returns
    ``(planned[J] bool, enough bool, tier[J] int32)``.  Raises
    ``ValueError`` above `MAX_JOBS` rows.
    """
    j = prio.shape[0]
    rows = _padded_rows(j)
    if rows > MAX_ROWS:
        raise ValueError(
            f"sched_select kernel holds at most {MAX_JOBS} rows in one VMEM "
            f"block (v5e default scoped VMEM); got J={j}. Use "
            f"kernel_backend='lax' for larger tables.")
    jp = rows * LANES
    n_tiers = save_lat.shape[1]

    def col(x):
        x = jnp.asarray(x, jnp.int32).reshape(j)
        return jnp.pad(x, (0, jp - j)).reshape(rows, LANES)

    lat_cols = [col(save_lat[:, k]) for k in range(n_tiers)]
    scal = jnp.concatenate([
        jnp.stack([jnp.asarray(idle, jnp.int32),
                   jnp.asarray(cpus_needed, jnp.int32)]),
        jnp.asarray(occ, jnp.int32).reshape(n_tiers),
        jnp.asarray(cap, jnp.int32).reshape(n_tiers),
    ]).reshape(1, 2 + 2 * n_tiers)
    kern = partial(sched_select_kernel, cheap=cheap, tiered=tiered,
                   bounded=bounded, n_tiers=n_tiers)
    tile = jax.ShapeDtypeStruct((rows, LANES), jnp.int32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    scratch = ([pltpu.VMEM((2 + n_tiers, rows, LANES), jnp.int32)]
               if tiered and bounded else [])
    row_s, planned_s, tier_s, enough = pl.pallas_call(
        kern,
        out_shape=[tile, tile, tile, jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        in_specs=[vmem] * (8 + n_tiers) + [smem],
        out_specs=[vmem, vmem, vmem, smem],
        scratch_shapes=scratch,
        interpret=interpret,
        name="sched_select",
    )(col(prio), col(run_start), col(jid), col(key_cost), col(evictable),
      col(cpus), col(state_mib), col(is_ckpt), *lat_cols, scal)
    row_s = row_s.reshape(jp)
    planned = jnp.zeros((jp,), jnp.int32).at[row_s].set(
        planned_s.reshape(jp))[:j]
    tier = jnp.zeros((jp,), jnp.int32).at[row_s].set(tier_s.reshape(jp))[:j]
    return planned.astype(bool), enough[0, 0].astype(bool), tier
