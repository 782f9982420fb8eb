"""sweep.device_ms_per_cell_tick: device time of the traced
`simulate_batch` call, summed over the chips, per knob-cell tick the call
ran (knob cells x horizon).  The trace holds the window's last call whole
(`drive.run_batch` begins it as that call starts and stops it as it
ends), and nearly all of that device time is the sharded tick scan."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("round_s"):
        return None
    per_call = ctx["ticks"] / len(ctx["round_s"])
    return 1e3 * tr["busy_s"] * tr["devices"] / per_call
