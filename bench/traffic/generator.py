"""Seeded scheduler traffic after the Lublin-Feitelson model of rigid
parallel batch jobs (U. Lublin and D. G. Feitelson, "The workload on
parallel supercomputers: modeling the characteristics of rigid jobs",
JPDC 63(11):1105-1122, 2003).

The configuration's ``model`` block holds the model's parameters:

* size: serial with probability ``serial_prob``; otherwise ``u`` is drawn
  from the two-stage uniform over ``[ulow, umed]`` (probability
  ``uprob``) or ``[umed, uhi]``, and the size is ``2**round(u)`` with
  probability ``pow2_prob``, else ``round(2**u)``; ``uhi`` is the log2
  of the machine size;
* runtime: ``exp`` of a hyper-gamma, ``Gamma(a1, b1)`` with probability
  ``p = pa * size + pb`` (clipped to [0, 1]), else ``Gamma(a2, b2)``, so
  that larger jobs run longer;
* inter-arrival time: ``exp`` of ``Gamma(aarr, barr)`` seconds.

Seconds become scheduler ticks of ``tick_s``.  A run starts from a centre
at steady state rather than from an empty machine: the standing queue
(``standing_fraction`` of the table, submitted at tick 0) opens with the
jobs that are running at that moment, which fill the machine and carry the
residual runtimes of a running set (runtimes length-biased, a uniform
share left), followed by the waiting backlog.  Arrivals follow from
tick 0.

The jobs and the arrival ticks are drawn once, from a fixed key; the seed
reorders the jobs within consecutive blocks of ``BLOCK``.  Every seed then
sends the same jobs to every stretch of the run, in another order, so that
the seed changes the schedule but not the amount of work.  The same seed
gives the same columns; ids are 0..N-1 in submit order, the standing queue
first.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: columns of a generated job set (every one int64, indexed by job id)
COLUMNS = ("user", "cpus", "work", "priority", "jclass", "submit",
           "state_mib")
#: `JobClass.CHECKPOINTABLE`: transparent C/R makes every job checkpointable
CHECKPOINTABLE = 2
#: the program's work column is int32
MAX_WORK = (1 << 31) - 1
#: the key the jobs and arrival ticks are drawn from, for every seed
POPULATION = 0
#: the seed reorders the jobs within consecutive blocks of this many
BLOCK = 16


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a sub-stream."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def draw_sizes(m: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Processors per job: the model's two-stage log-uniform sizes."""
    u = np.where(rng.random(n) < m["uprob"],
                 rng.uniform(m["ulow"], m["umed"], n),
                 rng.uniform(m["umed"], m["uhi"], n))
    size = np.where(rng.random(n) < m["pow2_prob"],
                    2.0 ** np.floor(u + 0.5), np.floor(2.0 ** u + 0.5))
    return np.where(rng.random(n) < m["serial_prob"], 1.0, size).astype(
        np.int64)


def draw_runtimes_s(m: dict, size: np.ndarray,
                    rng: np.random.Generator) -> np.ndarray:
    """Seconds per job: exp of the size-dependent hyper-gamma."""
    n = size.size
    p = np.clip(m["pa"] * size + m["pb"], 0.0, 1.0)
    x = np.where(rng.random(n) < p, rng.gamma(m["a1"], m["b1"], n),
                 rng.gamma(m["a2"], m["b2"], n))
    return np.exp(x)


def draw_jobs(config: dict, n: int, rng: np.random.Generator,
              running: bool = False) -> Dict[str, np.ndarray]:
    """``n`` jobs' columns but ``submit``.  With ``running`` they are the
    jobs running at a random moment: a pool drawn with probability in
    proportion to runtime, each left a uniform share of it, each kept in
    that order where it still fits the machine with a processor to spare
    (``n`` is the pool's size)."""
    m = config["model"]
    size = draw_sizes(m, n, rng)
    runtime = draw_runtimes_s(m, size, rng)
    if running:
        idx, used = [], 0
        for i in rng.choice(n, size=n, replace=False,
                            p=runtime / runtime.sum()):
            if used + size[i] < int(config["cpu_total"]):
                idx.append(i)
                used += int(size[i])
        idx = np.asarray(idx, np.int64)
        size = size[idx]
        runtime = runtime[idx] * rng.random(idx.size)
        n = idx.size
    work = np.clip(np.ceil(runtime / float(config["tick_s"])), 1, MAX_WORK)
    return {"user": rng.integers(0, int(config["tenants"]), n),
            "cpus": size, "work": work.astype(np.int64),
            "priority": np.zeros(n, np.int64),
            "jclass": np.full(n, CHECKPOINTABLE, np.int64),
            "state_mib": size * int(config["state_mib_per_cpu"])}


def arrival_ticks(config: dict, horizon: int,
                  rng: np.random.Generator) -> np.ndarray:
    """Submit ticks of the arrivals in ``[0, horizon)``, from the model's
    inter-arrival times."""
    m = config["model"]
    tick_s = float(config["tick_s"])
    # E[exp(X)] of X ~ Gamma(k, theta) is (1 - theta)**-k: draw twice the
    # horizon's mean count, then more if the draw falls short
    mean_s = (1.0 - m["barr"]) ** -m["aarr"]
    n = max(16, int(2 * horizon * tick_s / mean_s))
    times = np.cumsum(np.exp(rng.gamma(m["aarr"], m["barr"], n)))
    while times[-1] < horizon * tick_s:
        more = np.cumsum(np.exp(rng.gamma(m["aarr"], m["barr"], n)))
        times = np.concatenate([times, times[-1] + more])
    ticks = np.floor(times / tick_s).astype(np.int64)
    return ticks[ticks < horizon]


def reordered(cols: Dict[str, np.ndarray],
              rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """The jobs in a random order within consecutive blocks of ``BLOCK``."""
    n = cols["cpus"].size
    idx = np.lexsort((rng.random(n), np.arange(n) // BLOCK))
    return {k: v[idx] for k, v in cols.items()}


def generate(config: dict, mix: dict, seed: int, horizon: int,
             stream: int = 0) -> Dict[str, np.ndarray]:
    """The standing queue and the arrivals of ticks ``0..horizon-1``."""
    pop = rng_for(POPULATION, stream)
    n_standing = int(round(mix["standing_fraction"] * config["capacity"]))
    run = draw_jobs(config, 4 * int(config["cpu_total"]), pop, running=True)
    backlog = draw_jobs(config, n_standing - run["cpus"].size, pop)
    submit = arrival_ticks(config, horizon, pop)
    order = rng_for(seed, stream)
    parts = [reordered(p, order)
             for p in (run, backlog, draw_jobs(config, submit.size, pop))]
    cols = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
    cols["submit"] = np.concatenate(
        [np.zeros(cols["cpus"].size - submit.size, np.int64), submit])
    return cols
