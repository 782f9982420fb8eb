"""stream.boundary_ms: mean wall time of the stream's host boundary
(`simulate_stream`'s ``compaction`` section: table read-back, compaction,
arrival insert) over the window's rounds."""


def read(ctx):
    if not ctx.get("boundary_s"):
        return None
    return 1e3 * sum(ctx["boundary_s"]) / len(ctx["boundary_s"])
