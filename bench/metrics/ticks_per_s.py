"""ticks_per_s: scheduler ticks of the window's rounds over the window's
whole wall time (its first boundary's start to its last segment's end)."""


def read(ctx):
    if "ticks" not in ctx:
        return None
    return ctx["ticks"] / ctx["window_s"]
