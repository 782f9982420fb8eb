"""Plain reference of the scheduler's semantics, for the benchmark's check.

A straight transcription over numpy columns of the Python backend: the
tick protocol of `engine.tick_python`, Algorithm 1 of `core/omfs.py`
(`runner`, `_evict`, `_start`), backfill with C/R preemption of
`core/baselines.py`, the victim keys of `core/queues.py` and the integer
C/R cost model of `core/crcost.py`.  It adds what the timed entry points
add on top of that backend:

* the per-round queue depth (`pass_depth`, Slurm's
  ``default_queue_depth``): a round tries the first ``depth`` jobs of the
  sorted pending queue;
* the stream's fixed-capacity table (`engine.simulate_stream`): at each
  boundary the due arrivals fill the free rows in submit order, and those
  that find none wait for a later boundary.

It imports nothing from the program and takes nothing the program made.
Every job is one row, indexed by its id (ids are 0..N-1 in submit order).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from parts import shares_of

UNSUB, PENDING, RUNNING, DONE, KILLED = 0, 1, 2, 3, 4
NONP, PREEMPT, CKPT = 0, 1, 2
#: the columns a run is compared on, in the program's names
COMPARED = ("user", "cpus", "work", "priority", "jclass", "submit",
            "state_mib", "state", "progress", "run_start", "first_start",
            "finish", "n_preempt", "n_ckpt", "overhead", "backfilled",
            "ckpt_tier", "n_spill")
DEFAULT_CAP_TICKS = 1 << 20


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclass(frozen=True)
class TierCost:
    """One tier's integer save/restore cost (`crcost.CRCostModel`)."""

    save_mib_per_tick: int = 0
    restore_mib_per_tick: int = 0
    save_base: int = 0
    restore_base: int = 0
    compress_num: int = 1
    compress_den: int = 1
    save_tick_den: int = 1
    restore_tick_den: int = 1
    cap_ticks: int = DEFAULT_CAP_TICKS
    delta_num: int = 1
    delta_den: int = 1

    def _moved(self, mib: int) -> int:
        return _ceil_div(mib * self.compress_num, self.compress_den)

    def _cost(self, moved: int, per_tick: int, tick_den: int,
              base: int) -> int:
        var = _ceil_div(moved * tick_den, per_tick) if per_tick > 0 else 0
        return min(base + var, self.cap_ticks)

    def save(self, mib: int, recurrent: bool) -> int:
        moved = self._moved(mib)
        if recurrent:
            moved = _ceil_div(moved * self.delta_num, self.delta_den)
        return self._cost(moved, self.save_mib_per_tick, self.save_tick_den,
                          self.save_base)

    def restore(self, mib: int) -> int:
        return self._cost(self._moved(mib), self.restore_mib_per_tick,
                          self.restore_tick_den, self.restore_base)


class Costs:
    """The configuration's C/R costs: a flat term per save, plus one
    `TierCost` per tier where it has tiers (capacity < 0 is unbounded: the
    spill tier); without tiers saves and restores cost nothing more."""

    def __init__(self, config: dict):
        self.flat = int(config["cr_overhead"])
        tiers = config.get("cr_tiers") or [{"capacity_mib": -1}]
        self.tiers = [TierCost(**{k: v for k, v in t.items()
                                  if k != "capacity_mib"}) for t in tiers]
        self.caps = [int(t["capacity_mib"]) for t in tiers]
        self.tiered = len(tiers) > 1

    def save(self, mib: int, tier: int, recurrent: bool) -> int:
        return self.flat + self.tiers[tier].save(mib, recurrent)

    def restore(self, mib: int, tier: int) -> int:
        return self.tiers[tier].restore(mib)

    def choose_tier(self, mib: int, occ: Sequence[int],
                    recurrent: bool) -> int:
        """Cheapest tier with room, ties to the faster; else the last."""
        best = len(self.tiers) - 1
        best_cost = self.save(mib, best, recurrent)
        for k in range(len(self.tiers) - 1):
            if self.caps[k] >= 0 and occ[k] + mib > self.caps[k]:
                continue
            c = self.save(mib, k, recurrent)
            if c < best_cost:
                best, best_cost = k, c
        return best


class RefSim:
    """The reference scheduler over all of a run's jobs.

    ``cols`` holds the jobs' static columns (``user``, ``cpus``, ``work``,
    ``priority``, ``jclass``, ``submit``, ``state_mib``), indexed by id.
    ``ignore_quantum`` is the control: it breaks the configuration's
    quantum guarantee (a running job may be evicted before it has run
    ``quantum`` ticks)."""

    def __init__(self, cols: Dict[str, np.ndarray], config: dict,
                 policy: str, *, quantum: int, depth: int,
                 ignore_quantum: bool = False):
        if policy not in ("omfs", "omfs_cheap_victim", "backfill_cr"):
            raise NotImplementedError(f"no reference for policy {policy!r}")
        self.policy = policy
        self.cpu_total = int(config["cpu_total"])
        self.ent = [int(s / 100.0 * self.cpu_total)
                    for s in shares_of(config)]
        self.costs = Costs(config)
        self.quantum = 0 if ignore_quantum else int(quantum)
        self.depth = int(depth)
        n = cols["cpus"].shape[0]
        self.n = n
        self.static = {k: np.asarray(cols[k], np.int64) for k in
                       ("user", "cpus", "work", "priority", "jclass",
                        "submit", "state_mib")}
        self.in_table = np.zeros(n, bool)
        self.state = np.full(n, UNSUB, np.int64)
        self.progress = np.zeros(n, np.int64)
        self.run_start = np.full(n, -1, np.int64)
        self.first_start = np.full(n, -1, np.int64)
        self.finish = np.full(n, -1, np.int64)
        self.n_preempt = np.zeros(n, np.int64)
        self.n_ckpt = np.zeros(n, np.int64)
        self.overhead = np.zeros(n, np.int64)
        self.backfilled = np.zeros(n, np.int64)
        self.ckpt_tier = np.full(n, -1, np.int64)
        self.n_spill = np.zeros(n, np.int64)
        self.busy: List[int] = []

    # -- the tick protocol ------------------------------------------------
    def tick(self, t: int) -> None:
        s = self.static
        arrived = self.in_table & (self.state == UNSUB) & (s["submit"] <= t)
        self.state[arrived] = PENDING
        running = self.state == RUNNING
        self.progress[running] += 1
        done = running & (self.progress >= s["work"] + self.overhead)
        self.state[done] = DONE
        self.finish[done] = t
        if self.policy == "backfill_cr":
            self._backfill_cr(t)
        else:
            self._omfs(t, cheap=self.policy == "omfs_cheap_victim")
        run = self.state == RUNNING
        self.busy.append(int(s["cpus"][run].sum()))

    def _queue(self) -> np.ndarray:
        """Pending ids in (-priority, submit, id) order, first ``depth``."""
        s = self.static
        ids = np.flatnonzero(self.state == PENDING)
        order = np.lexsort((ids, s["submit"][ids], -s["priority"][ids]))
        return ids[order[:self.depth]]

    def _usage(self):
        s = self.static
        run = np.flatnonzero(self.state == RUNNING)
        usage = np.bincount(s["user"][run], s["cpus"][run],
                            len(self.ent)).astype(np.int64)
        nonp = run[s["jclass"][run] == NONP]
        nonp_usage = np.bincount(s["user"][nonp], s["cpus"][nonp],
                                 len(self.ent)).astype(np.int64)
        return usage, nonp_usage, int(s["cpus"][run].sum())

    def _victims(self, t: int, cheap: bool,
                 backfilled_only: bool = False) -> List[int]:
        """Evictable running jobs in victim order (`queues.sorted_victims`)."""
        s = self.static
        ids = np.flatnonzero((self.state == RUNNING) & (s["jclass"] != NONP)
                             & (t - self.run_start >= self.quantum))
        if backfilled_only:
            ids = ids[self.backfilled[ids] > 0]
        keys = [ids, self.run_start[ids], s["priority"][ids]]
        if cheap:
            keys.append(np.asarray(
                [self.costs.save(int(s["state_mib"][i]), 0,
                                 self.n_ckpt[i] > 0) for i in ids],
                np.int64))
        return [int(i) for i in ids[np.lexsort(keys)]]

    def _start(self, j: int, t: int) -> None:
        if self.n_ckpt[j] > 0:
            self.overhead[j] += self.costs.restore(
                int(self.static["state_mib"][j]), max(int(self.ckpt_tier[j]), 0))
        self.ckpt_tier[j] = -1
        self.state[j] = RUNNING
        self.run_start[j] = t
        if self.first_start[j] < 0:
            self.first_start[j] = t

    def _evict(self, v: int, t: int) -> None:
        s = self.static
        self.n_preempt[v] += 1
        if s["jclass"][v] == CKPT:
            recurrent = self.n_ckpt[v] > 0
            self.n_ckpt[v] += 1
            mib = int(s["state_mib"][v])
            tier = 0
            if self.costs.tiered:
                held = (self.state == PENDING) & (self.ckpt_tier >= 0)
                occ = np.bincount(self.ckpt_tier[held], s["state_mib"][held],
                                  len(self.costs.tiers))
                tier = self.costs.choose_tier(mib, occ, recurrent)
            self.ckpt_tier[v] = tier
            if tier > 0:
                self.n_spill[v] += 1
            self.overhead[v] += self.costs.save(mib, tier, recurrent)
            self.state[v] = PENDING
        else:
            # drop_killed: an evicted non-checkpointable job is dropped
            self.state[v] = KILLED
            self.finish[v] = t
        self.run_start[v] = -1

    def _plan(self, victims: List[int], idle: int, need: int) -> Optional[list]:
        """The paper's while-loop: the shortest victim prefix that fits."""
        s = self.static
        planned, freed = [], 0
        for v in victims:
            if idle + freed >= need:
                break
            planned.append(v)
            freed += int(s["cpus"][v])
        return planned if idle + freed >= need else None

    # -- Algorithm 1 ------------------------------------------------------
    def _omfs(self, t: int, cheap: bool) -> None:
        s = self.static
        usage, nonp_usage, busy = self._usage()
        for j in self._queue():
            u, c = int(s["user"][j]), int(s["cpus"][j])
            non_p = s["jclass"][j] == NONP
            idle = self.cpu_total - busy
            if non_p and nonp_usage[u] + c >= self.ent[u]:      # line 23
                continue
            if not idle > c:                                     # line 26
                if c > self.ent[u] - usage[u]:                   # line 28
                    continue
                planned = self._plan(self._victims(t, cheap), idle, c)
                if planned is None:
                    continue
                for v in planned:                                # 33-36
                    vc = int(s["cpus"][v])
                    usage[int(s["user"][v])] -= vc
                    if s["jclass"][v] == NONP:
                        nonp_usage[int(s["user"][v])] -= vc
                    busy -= vc
                    self._evict(v, t)
            self._start(j, t)                                    # 37-38
            usage[u] += c
            if non_p:
                nonp_usage[u] += c
            busy += c

    # -- backfill with C/R preemption (Niu et al.) ------------------------
    def _est(self, j) -> np.ndarray:
        return np.maximum(self.static["work"][j] + self.overhead[j]
                          - self.progress[j], 1)

    def _reservation(self, t: int, idle: int, head_cpus: int) -> int:
        s = self.static
        run = np.flatnonzero(self.state == RUNNING)
        est = self._est(run)
        order = np.lexsort((run, est))
        cum = idle + np.cumsum(s["cpus"][run][order])
        crossed = np.flatnonzero(cum >= head_cpus)
        if crossed.size:
            return t + int(est[order][crossed[0]])
        return t + int(est.sum()) + 1

    def _backfill_cr(self, t: int) -> None:
        s = self.static
        queue = self._queue()
        if queue.size == 0:
            return
        _, _, busy = self._usage()
        idle = self.cpu_total - busy
        head = int(queue[0])
        head_cpus = int(s["cpus"][head])
        head_start = None
        if idle >= head_cpus:
            self._start(head, t)
            busy += head_cpus
        else:
            planned = self._plan(self._victims(t, False, backfilled_only=True),
                                 idle, head_cpus)
            if planned is not None:
                for v in planned:
                    busy -= int(s["cpus"][v])
                    self._evict(v, t)
                self._start(head, t)
                busy += head_cpus
            else:
                head_start = self._reservation(t, idle, head_cpus)
        est = self._est(queue)
        for k in range(1, queue.size):
            j = int(queue[k])
            c = int(s["cpus"][j])
            idle = self.cpu_total - busy
            if idle < c:
                continue
            if (head_start is not None and t + int(est[k]) > head_start
                    and not idle - c >= head_cpus):
                continue
            self.backfilled[j] = 1
            self._start(j, t)
            busy += c

    # -- entry points -----------------------------------------------------
    def run_stream(self, horizon: int, capacity: int,
                   segment_len: int) -> Dict[str, int]:
        """`simulate_stream`'s boundaries: at each segment start the jobs
        due before its end fill free rows in id (= submit) order; the
        rest wait.  Returns the stream's counts."""
        s = self.static
        due: List[int] = []
        nxt = 0
        stats = {"inserted": 0, "deferrals": 0}
        for t0 in range(0, horizon, segment_len):
            seg = min(segment_len, horizon - t0)
            while nxt < self.n and s["submit"][nxt] < t0 + seg:
                due.append(nxt)
                nxt += 1
            live = int((self.in_table & (self.state != DONE)
                        & (self.state != KILLED)).sum())
            k = min(len(due), capacity - live)
            stats["deferrals"] += len(due) - k
            self.in_table[due[:k]] = True
            stats["inserted"] += k
            due = due[k:]
            for t in range(t0, t0 + seg):
                self.tick(t)
        stats["dropped"] = len(due)
        return stats

    def run_all(self, horizon: int) -> None:
        """A monolithic run (`simulate_batch`): every job is in the table."""
        self.in_table[:] = True
        for t in range(horizon):
            self.tick(t)

    def table(self) -> Dict[str, np.ndarray]:
        """The final columns of the jobs that entered the table, by id."""
        ids = np.flatnonzero(self.in_table)
        out = {"jid": ids}
        for k in COMPARED:
            col = self.static[k] if k in self.static else getattr(self, k)
            out[k] = col[ids]
        return out


def mismatches(program: Dict[str, np.ndarray],
               reference: Dict[str, np.ndarray]) -> int:
    """Number of (job, column) entries where the two tables differ; a job
    present on one side only counts once per compared column."""
    pj = np.asarray(program["jid"], np.int64)
    rj = np.asarray(reference["jid"], np.int64)
    common, pi, ri = np.intersect1d(pj, rj, return_indices=True)
    only = (pj.size - common.size) + (rj.size - common.size)
    bad = only * len(COMPARED)
    for k in COMPARED:
        bad += int((np.asarray(program[k], np.int64)[pi]
                    != np.asarray(reference[k], np.int64)[ri]).sum())
    return bad
