"""evict.victims_per_tick.gang: the node-aware victim prefix's output on
the GPU fleet: preemptions counted in the program's result table (its
n_preempt column) over every tick the call ran, warm rounds included."""


def read(ctx):
    if not ctx.get("ticks_total"):
        return None
    return ctx["preemptions"] / ctx["ticks_total"]
