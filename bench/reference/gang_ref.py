"""Plain reference of gang placement on nodes, for the benchmark's check.

`sched_ref`'s tick protocol, queue, victim order, C/R costs and stream
boundary, with Algorithm 1's admission on a fleet of N nodes of G GPUs
(the configuration's ``scheduler.node_size``), as DESIGN.md's "Node
placement" states it:

* a job of ``g <= G`` GPUs holds one node; a larger one, ``g / G``
  contiguous whole nodes; any other size is refused;
* line 26 admits on idle GPUs only where they hold a placement: the node
  with the fewest free GPUs that fit (lowest on ties), or the lowest run
  of ``g / G`` free nodes;
* otherwise, past lines 23 and 28, the eviction path: for one node, the
  node whose free GPUs plus its evictable jobs', taken in victim order,
  reach ``g`` at the earliest victim (lowest node on ties) gives up those
  jobs; for whole nodes, the run of nodes whose latest victim (every job
  on them must be evictable) comes earliest gives up every job on it, a
  job on several nodes whole;
* ``node`` is the first node of a job's latest placement, -1 before its
  first start; completion and eviction leave it.

It imports nothing from the program.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from reference import sched_ref
from reference.sched_ref import NONP, RUNNING

COMPARED = sched_ref.COMPARED + ("node",)


class RefSim(sched_ref.RefSim):
    """`sched_ref.RefSim` with the jobs placed on nodes."""

    def __init__(self, cols, config: dict, policy: str, **kw):
        if policy not in ("omfs", "omfs_cheap_victim"):
            raise NotImplementedError(
                f"no reference for policy {policy!r} on nodes")
        super().__init__(cols, config, policy, **kw)
        self.g = int(config["scheduler"]["node_size"])
        self.n_nodes = self.cpu_total // self.g
        cpus = self.static["cpus"]
        bad = (cpus < 1) | ((cpus > self.g) & (cpus % self.g != 0))
        if bad.any():
            raise ValueError(f"job sizes {sorted(set(cpus[bad].tolist()))} "
                             f"do not fit nodes of {self.g} GPUs")
        self.node = np.full(self.n, -1, np.int64)

    def _span(self, j: int) -> range:
        """The nodes job ``j`` holds while it runs."""
        c = int(self.static["cpus"][j])
        return range(int(self.node[j]),
                     int(self.node[j]) + (1 if c <= self.g else c // self.g))

    def _free(self) -> np.ndarray:
        """Free GPUs per node, counted from the running jobs."""
        run = np.flatnonzero(self.state == RUNNING)
        c = self.static["cpus"][run]
        one = c <= self.g
        free = self.g - np.bincount(self.node[run[one]], c[one],
                                    self.n_nodes).astype(np.int64)
        for j, k in zip(run[~one], c[~one] // self.g):
            free[self.node[j]:self.node[j] + k] -= self.g
        assert (free >= 0).all(), "a node holds more GPUs than it has"
        return free

    def _hold(self, free: np.ndarray, j: int, sign: int) -> None:
        """Take (``sign`` -1) or give back (+1) job ``j``'s GPUs."""
        for m in self._span(j):
            free[m] += sign * min(int(self.static["cpus"][j]), self.g)
        assert 0 <= free.min() and free.max() <= self.g

    def _fit(self, free: np.ndarray, c: int) -> Optional[int]:
        if c <= self.g:
            ok = np.flatnonzero(free >= c)
            return int(ok[np.argmin(free[ok])]) if ok.size else None
        k = c // self.g
        if k > self.n_nodes:
            return None
        runs = np.flatnonzero(np.convolve(free == self.g, np.ones(k, int),
                                          "valid") == k)
        return int(runs[0]) if runs.size else None

    def _plan_nodes(self, free: np.ndarray, victims: List[int],
                    c: int) -> Optional[Tuple[int, List[int]]]:
        inf = math.inf
        if c <= self.g:
            reach = [-1 if f >= c else inf for f in free]
            got = free.copy()
            for r, v in enumerate(victims):
                span = self._span(v)
                for m in span:
                    if reach[m] != inf:
                        continue
                    got[m] += int(self.static["cpus"][v]) if len(span) == 1 \
                        else self.g
                    if got[m] >= c:
                        reach[m] = r
            best = int(np.argmin(reach))
            if reach[best] == inf:
                return None
            cut, nodes = reach[best], range(best, best + 1)
        else:
            k = c // self.g
            rank = {v: r for r, v in enumerate(victims)}
            clear = [-1] * self.n_nodes
            for j in np.flatnonzero(self.state == RUNNING):
                for m in self._span(j):
                    clear[m] = max(clear[m], rank.get(int(j), inf))
            worst = [max(clear[s:s + k]) for s in range(self.n_nodes - k + 1)]
            best = int(np.argmin(worst)) if worst else 0
            if not worst or worst[best] == inf:
                return None
            cut, nodes = worst[best], range(best, best + k)
        chosen = [v for v in victims[:cut + 1]
                  if set(self._span(v)) & set(nodes)]
        return best, chosen

    def _omfs(self, t: int, cheap: bool) -> None:
        s = self.static
        usage, nonp_usage, busy = self._usage()
        free = self._free()
        for j in self._queue():
            u, c = int(s["user"][j]), int(s["cpus"][j])
            non_p = s["jclass"][j] == NONP
            idle = self.cpu_total - busy
            if non_p and nonp_usage[u] + c >= self.ent[u]:      # line 23
                continue
            place = self._fit(free, c) if idle > c else None     # line 26
            if place is None:
                if c > self.ent[u] - usage[u]:                   # line 28
                    continue
                plan = self._plan_nodes(free, self._victims(t, cheap), c)
                if plan is None:
                    continue
                place, planned = plan
                for v in planned:                                # 33-36
                    vc = int(s["cpus"][v])
                    usage[int(s["user"][v])] -= vc
                    if s["jclass"][v] == NONP:
                        nonp_usage[int(s["user"][v])] -= vc
                    busy -= vc
                    self._hold(free, v, +1)
                    self._evict(v, t)
            self._start(j, t)                                    # 37-38
            self.node[j] = place
            self._hold(free, j, -1)
            usage[u] += c
            if non_p:
                nonp_usage[u] += c
            busy += c

    def table(self) -> Dict[str, np.ndarray]:
        out = super().table()
        out["node"] = self.node[out["jid"]]
        return out


def mismatches(program: Dict[str, np.ndarray],
               reference: Dict[str, np.ndarray]) -> int:
    """`sched_ref.mismatches` over this reference's columns."""
    pj = np.asarray(program["jid"], np.int64)
    rj = np.asarray(reference["jid"], np.int64)
    common, pi, ri = np.intersect1d(pj, rj, return_indices=True)
    bad = (pj.size + rj.size - 2 * common.size) * len(COMPARED)
    for k in COMPARED:
        bad += int((np.asarray(program[k], np.int64)[pi]
                    != np.asarray(reference[k], np.int64)[ri]).sum())
    return bad
