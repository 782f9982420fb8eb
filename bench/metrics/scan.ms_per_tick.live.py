"""scan.ms_per_tick.live: `scan.ms_per_tick` in the live loop, where it
moves rounds_per_s: the stream's ``dispatch`` sections (the jitted tick
scan of a segment, ending in block_until_ready) over the window's rounds,
per tick dispatched."""


def read(ctx):
    if not ctx.get("boundary_s"):
        return None
    return 1e3 * sum(ctx["segment_s"]) / ctx["ticks"]
