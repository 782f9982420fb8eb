"""Seeded GPU-fleet traffic after the Helios study (Q. Hu et al.,
"Characterization and Prediction of Deep Learning Workloads in
Large-Scale GPU Datacenters", SC'21): gang training jobs on a fleet of
8-GPU nodes.

The configuration's ``jobs`` block holds the fitted shapes:

* GPUs per job: ``gpus``, a probability per power of two;
* runtime in ticks of a job of g GPUs: ``ceil(lognormal(ln(m (1 +
  log2 g)), sigma))`` with ``m = runtime_ticks_median_per_log2``, clipped
  to ``[1, runtime_max_ticks]``: heavy-tailed, longer for larger gangs;
* tenant (virtual cluster): in proportion to its share;
* arrivals: a Poisson process whose rate offers ``offered_load`` times
  the fleet's GPUs, from the mean GPU-ticks of a job.

A run starts from a fleet at steady state, as `generator.generate` does:
the standing queue (``standing_fraction`` of the table, submitted at tick
0) opens with the jobs running at a random moment (length-biased, a
uniform share of each runtime left), each kept where it still has a place
on the nodes with a GPU to spare, then the waiting backlog; arrivals
follow from tick 0.  Jobs and arrival ticks are drawn once from a fixed
key; the seed reorders the jobs within blocks of `generator.BLOCK`
(`generator.reordered`).  The same seed gives the same columns; ids are
0..N-1 in submit order, the standing queue first.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

from traffic.generator import CHECKPOINTABLE, POPULATION, reordered, rng_for


def _sizes(jobs: dict):
    sizes = np.asarray([int(g) for g in jobs["gpus"]], np.int64)
    probs = np.asarray([float(p) for p in jobs["gpus"].values()])
    return sizes, probs / probs.sum()


def _log_median(jobs: dict, gpus: np.ndarray) -> np.ndarray:
    return np.log(jobs["runtime_ticks_median_per_log2"]
                  * (1.0 + np.log2(gpus)))


def mean_gpu_ticks(jobs: dict) -> float:
    """E[GPUs x runtime] of one job: the clipped log-normal's mean,
    ``E[min(X, c)]``, per size (the ceiling adds under a tick)."""
    sizes, probs = _sizes(jobs)
    s = float(jobs["runtime_sigma"])
    lc = math.log(jobs["runtime_max_ticks"])
    phi = lambda z: 0.5 * (1.0 + math.erf(z / math.sqrt(2.0)))
    total = 0.0
    for g, p, mu in zip(sizes, probs, _log_median(jobs, sizes)):
        mean = (math.exp(mu + s * s / 2) * phi((lc - mu - s * s) / s)
                + jobs["runtime_max_ticks"] * (1.0 - phi((lc - mu) / s)))
        total += p * g * mean
    return total


def arrival_rate(config: dict) -> float:
    """Arrivals a tick that offer ``offered_load`` x the fleet's GPUs."""
    jobs = config["jobs"]
    return (jobs["offered_load"] * int(config["cpu_total"])
            / mean_gpu_ticks(jobs))


def draw_jobs(config: dict, n: int, rng: np.random.Generator,
              running: bool = False) -> Dict[str, np.ndarray]:
    """``n`` jobs' columns but ``submit``.  With ``running`` they are the
    jobs running at a random moment: a pool drawn with probability in
    proportion to runtime, each left a uniform share of it, each kept in
    that order where the nodes still hold it with a GPU to spare (``n`` is
    the pool's size)."""
    jobs = config["jobs"]
    sizes, probs = _sizes(jobs)
    gpus = rng.choice(sizes, size=n, p=probs)
    runtime = np.clip(np.ceil(rng.lognormal(_log_median(jobs, gpus),
                                            jobs["runtime_sigma"])),
                      1, jobs["runtime_max_ticks"])
    if running:
        keep = _placeable(config, gpus, rng.choice(
            n, size=n, replace=False, p=runtime / runtime.sum()))
        gpus = gpus[keep]
        runtime = np.maximum(np.ceil(runtime[keep] * rng.random(keep.size)),
                             1)
        n = keep.size
    shares = np.asarray(config["shares"], float)
    return {"user": rng.choice(shares.size, size=n, p=shares / shares.sum()),
            "cpus": gpus, "work": runtime.astype(np.int64),
            "priority": np.zeros(n, np.int64),
            "jclass": np.full(n, CHECKPOINTABLE, np.int64),
            "state_mib": gpus * int(config["state_mib_per_cpu"])}


def _placeable(config: dict, gpus: np.ndarray,
               order: np.ndarray) -> np.ndarray:
    """The jobs of ``order`` kept in turn where a node (one for a job of
    up to a node, contiguous whole nodes for a larger one) still has room
    and the fleet a GPU to spare."""
    g = int(config["scheduler"]["node_size"])
    free = np.full(int(config["cpu_total"]) // g, g, np.int64)
    used, keep = 0, []
    for i in order:
        c = int(gpus[i])
        if used + c >= int(config["cpu_total"]):
            continue
        if c <= g:
            fits = np.flatnonzero(free >= c)
            if fits.size == 0:
                continue
            free[fits[np.argmin(free[fits])]] -= c
        else:
            k = c // g
            whole = np.flatnonzero(np.convolve(free == g, np.ones(k, int),
                                               "valid") == k)
            if whole.size == 0:
                continue
            free[whole[0]:whole[0] + k] = 0
        used += c
        keep.append(i)
    return np.asarray(keep, np.int64)


def arrival_ticks(config: dict, horizon: int,
                  rng: np.random.Generator) -> np.ndarray:
    """Submit ticks of the Poisson arrivals in ``[0, horizon)``."""
    counts = rng.poisson(arrival_rate(config), horizon)
    return np.repeat(np.arange(horizon, dtype=np.int64), counts)


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
