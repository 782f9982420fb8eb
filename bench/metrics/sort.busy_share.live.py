"""sort.busy_share.live: `sort.busy_share` in the live loop, where it
moves rounds_per_s: device time in HLO sort operations (the queue and
victim lexsorts) as a share of device busy time, from the trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr.get("sort_s") is None:
        return None
    return 100.0 * tr["sort_s"] / tr["busy_s"]
