"""What a cell's check runs the reference on: the same traffic, window
and knobs as the timed run, from the cell's files and the seed alone.
The traffic model and the reference are the configuration's own
(`parts`)."""
from __future__ import annotations

import itertools
import math

from parts import reference_of, traffic_of


def window_rounds(work: dict, seconds: float) -> int:
    """The window's fixed number of rounds: ``seconds`` at the cell's
    ``rounds_per_s`` (measured when the cell was defined), at least 2."""
    return max(2, math.ceil(seconds * float(work["rounds_per_s"])))


def sweep_cells(work: dict):
    """The knob grid: policy x quantum x pass depth, in a fixed order."""
    g = work["grid"]
    return list(itertools.product(g["policies"], g["quantum"],
                                  g["pass_depth"]))


def batch_draw(generate, config: dict, work: dict, seed: int,
               stream: int) -> dict:
    """The jobs of one `simulate_batch` call: the configuration's job
    table, ``capacity`` rows, filled by the standing queue and then the
    arrivals in submit order, drawn over the call's horizon, twice as many
    ticks, and so on until they fill it.  Every draw then has the table's
    shape; the arrivals due at or after the horizon never arrive in it."""
    rows = int(config["capacity"])
    ticks = int(work["horizon"])
    while True:
        cols = generate(config, work, seed, ticks, stream=stream)
        if cols["cpus"].size >= rows:
            return {k: v[:rows] for k, v in cols.items()}
        ticks *= 2


def references(config: dict, work: dict, seed: int, seconds: float,
               ignore_quantum: bool = False) -> list:
    """The reference runs a cell's check compares with, in the order of
    the program's outputs: ``[(RefSim, stream counts or None)]``.
    ``ignore_quantum`` gives the control instead (`RefSim`)."""
    rounds = window_rounds(work, seconds)
    generate = traffic_of(config)
    RefSim = reference_of(config).RefSim
    if work["entry"] == "stream":
        seg = int(work["segment_len"])
        horizon = seg * (1 + int(work["warm_rounds"]) + rounds)
        ref = RefSim(generate(config, work, seed, horizon), config,
                     work["policy"], quantum=int(config["quantum"]),
                     depth=int(config["pass_depth"]),
                     ignore_quantum=ignore_quantum)
        stats = ref.run_stream(horizon, int(config["capacity"]), seg)
        return [(ref, stats)]
    horizon = int(work["horizon"])
    out = []
    for r in range(rounds):
        cols = batch_draw(generate, config, work, seed, r)
        for p, q, d in sweep_cells(work):
            ref = RefSim(cols, config, p, quantum=q, depth=d,
                         ignore_quantum=ignore_quantum)
            ref.run_all(horizon)
            out.append((ref, None))
    return out
