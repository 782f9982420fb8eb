"""rounds_per_s: scheduling rounds of the window (boundary and segment)
over the window's whole wall time, from its first boundary's start to its
last segment's end."""


def read(ctx):
    if not ctx.get("round_s"):
        return None
    return len(ctx["round_s"]) / ctx["window_s"]
