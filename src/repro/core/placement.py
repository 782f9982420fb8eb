"""Placement on top of Algorithm 1's counts.

Two models of where a job's resources sit:

* **Nodes** (`SchedulerConfig.node_size`, the gang placement the OMFS pass
  runs; DESIGN.md §Node placement).  A GPU fleet of N nodes of G GPUs: a
  job of ``cpus <= G`` holds one node, a larger one ``cpus / G``
  contiguous whole nodes.  The functions below are the Python backend's
  half of it (`core.omfs.runner`); `core.omfs_jax` computes the same
  choices over the `JobTable`, bit for bit.
* **Buddy blocks** (`BuddyAllocator`): a TPU torus flattened into
  power-of-two aligned slices, a feasibility oracle with fragmentation.
  Fragmentation is the interesting failure mode: the counting policy may
  admit a job the buddy policy cannot place, and eviction picks victims
  that actually free a usable block (`victims_for_block`).
"""
from __future__ import annotations

from dataclasses import dataclass, field
import math
from typing import Dict, List, Optional, Sequence, Set, Tuple

#: a running job as the node functions see it: (id, first node, cpus)
Placed = Tuple[int, int, int]


def gang_nodes(cpus: int, node_size: int) -> int:
    """Nodes a job of ``cpus`` holds: 1 up to ``node_size``, else
    ``cpus / node_size`` whole nodes; any other size cannot be placed."""
    if 1 <= cpus <= node_size:
        return 1
    if cpus > node_size and cpus % node_size == 0:
        return cpus // node_size
    raise ValueError(
        f"a job of {cpus} GPUs cannot be placed on nodes of node_size="
        f"{node_size}: it needs at most node_size GPUs or a multiple of it")


def _on_nodes(running: Sequence[Placed], n_nodes: int,
              node_size: int) -> List[List[Tuple[int, int]]]:
    """Per node, the running jobs on it and the GPUs each holds there."""
    on: List[List[Tuple[int, int]]] = [[] for _ in range(n_nodes)]
    for jid, node, cpus in running:
        k = gang_nodes(cpus, node_size)
        for n in range(node, node + k):
            on[n].append((jid, cpus if k == 1 else node_size))
    return on


def node_free(running: Sequence[Placed], n_nodes: int,
              node_size: int) -> List[int]:
    """Free GPUs per node, counted from the running jobs."""
    return [node_size - sum(c for _, c in jobs)
            for jobs in _on_nodes(running, n_nodes, node_size)]


def fit_free(free: Sequence[int], cpus: int, node_size: int) -> Optional[int]:
    """The first node of a placement on free capacity, or None: best fit
    (fewest free GPUs that still fit, lowest node on ties) for one node;
    the lowest start of ``cpus / node_size`` contiguous free nodes."""
    k = gang_nodes(cpus, node_size)
    if k == 1:
        fits = [n for n, f in enumerate(free) if f >= cpus]
        return min(fits, key=lambda n: free[n]) if fits else None
    for s in range(len(free) - k + 1):
        if all(f == node_size for f in free[s:s + k]):
            return s
    return None


def plan_on_nodes(running: Sequence[Placed], rank: Dict[int, int],
                  n_nodes: int, node_size: int,
                  cpus: int) -> Optional[Tuple[int, List[int]]]:
    """The node-aware victim prefix: ``(first node, victim ids in rank
    order)`` for a job of ``cpus``, or None when no choice of evictable
    jobs (``rank``: id -> rank in victim order) frees a place.

    One node: a node's reach is the least rank at which its free GPUs
    plus those of its evictable jobs up to that rank hold ``cpus`` (-1 if
    its free GPUs do already); the node of least reach wins (lowest on
    ties) and gives up its evictable jobs up to the reach.  Whole nodes: a
    node's clearing rank is the largest rank of the jobs on it (-1 when
    free, infinite when one of them is not evictable); the window of least
    largest clearing rank wins (lowest start on ties) and gives up every
    job on it, a multi-node job whole."""
    on = _on_nodes(running, n_nodes, node_size)
    free = [node_size - sum(c for _, c in jobs) for jobs in on]
    k = gang_nodes(cpus, node_size)
    if k == 1:
        best, best_reach = None, math.inf
        for n in range(n_nodes):
            reach, got = (-1 if free[n] >= cpus else math.inf), free[n]
            if reach == math.inf:
                for jid, c in sorted((x for x in on[n] if x[0] in rank),
                                     key=lambda x: rank[x[0]]):
                    got += c
                    if got >= cpus:
                        reach = rank[jid]
                        break
            if reach < best_reach:
                best, best_reach = n, reach
        if best is None:
            return None
        victims = {j for j, _ in on[best] if j in rank and rank[j] <= best_reach}
    else:
        clear = [-1 if not jobs else max(
            (rank.get(j, math.inf) for j, _ in jobs)) for jobs in on]
        best, best_clear = None, math.inf
        for s in range(n_nodes - k + 1):
            c = max(clear[s:s + k])
            if c < best_clear:
                best, best_clear = s, c
        if best is None:
            return None
        victims = {j for n in range(best, best + k) for j, _ in on[n]}
    return best, sorted(victims, key=lambda j: rank[j])


def _round_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclass
class BuddyAllocator:
    """Buddy allocation over ``total`` chips (power of two)."""

    total: int
    free_blocks: Dict[int, Set[int]] = field(default_factory=dict)  # size -> offsets
    allocated: Dict[int, Tuple[int, int]] = field(default_factory=dict)  # job -> (off, size)

    def __post_init__(self):
        assert self.total & (self.total - 1) == 0, "total must be a power of two"
        if not self.free_blocks:
            self.free_blocks = {self.total: {0}}

    # -- queries -------------------------------------------------------------
    def can_place(self, cpus: int) -> bool:
        size = _round_pow2(max(cpus, 1))
        return any(s >= size and offs for s, offs in self.free_blocks.items())

    def largest_free(self) -> int:
        return max((s for s, offs in self.free_blocks.items() if offs), default=0)

    def free_chips(self) -> int:
        return sum(s * len(offs) for s, offs in self.free_blocks.items())

    # -- mutation --------------------------------------------------------------
    def place(self, job_id: int, cpus: int) -> Optional[Tuple[int, int]]:
        """First-fit smallest sufficient block; splits buddies as needed."""
        size = _round_pow2(max(cpus, 1))
        cand = sorted(s for s, offs in self.free_blocks.items() if s >= size and offs)
        if not cand:
            return None
        s = cand[0]
        off = min(self.free_blocks[s])
        self.free_blocks[s].discard(off)
        while s > size:  # split down to fit
            s //= 2
            self.free_blocks.setdefault(s, set()).add(off + s)
        self.allocated[job_id] = (off, size)
        return (off, size)

    def release(self, job_id: int) -> None:
        off, size = self.allocated.pop(job_id)
        # coalesce with buddy blocks as far as possible
        while size < self.total:
            buddy = off ^ size
            peers = self.free_blocks.get(size, set())
            if buddy in peers:
                peers.discard(buddy)
                off = min(off, buddy)
                size *= 2
            else:
                break
        self.free_blocks.setdefault(size, set()).add(off)

    # -- eviction planning ------------------------------------------------------
    def victims_for_block(self, cpus: int, candidates: List[Tuple[int, int]]) -> Optional[List[int]]:
        """Smallest set of candidate jobs [(job_id, victim_rank), ...] whose
        release (in rank order) makes a ``cpus`` block placeable.  Simulates
        releases on a copy; returns job ids or None."""
        sim = BuddyAllocator(
            self.total,
            {s: set(o) for s, o in self.free_blocks.items()},
            dict(self.allocated),
        )
        chosen: List[int] = []
        for job_id, _rank in candidates:
            if sim.can_place(cpus):
                break
            if job_id in sim.allocated:
                sim.release(job_id)
                chosen.append(job_id)
        return chosen if sim.can_place(cpus) else None
