"""evict.victims_per_tick: preemptions counted in the program's result
table (its n_preempt column) over every tick the call ran."""


def read(ctx):
    if not ctx.get("ticks_total"):
        return None
    return ctx["preemptions"] / ctx["ticks_total"]
