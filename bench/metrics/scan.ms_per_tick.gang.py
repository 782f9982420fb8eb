"""scan.ms_per_tick.gang: `scan.ms_per_tick` on the GPU fleet, where each
tick's admissions search the nodes and its evictions take the node-aware
victim prefix: the stream's ``dispatch`` sections (the jitted tick scan of
a segment, ending in block_until_ready) over the window's rounds, per tick
dispatched."""


def read(ctx):
    if not ctx.get("boundary_s"):
        return None
    return 1e3 * sum(ctx["segment_s"]) / ctx["ticks"]
