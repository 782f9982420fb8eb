"""cell_ticks_per_s: `ticks_per_s` of the sweep, whose ticks are knob-cell
ticks (calls x knob cells x horizon): over the window's whole wall time,
from the first `simulate_batch` call's start to the last call's end."""
from run import read_metric


def read(ctx):
    return read_metric("ticks_per_s", ctx)
