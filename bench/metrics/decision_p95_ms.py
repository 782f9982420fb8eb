"""decision_p95_ms: 95th percentile (nearest rank) over every round of the
window of the wall time from the round's boundary start, where the due
submissions are handed in, to the end of its segment, when the round's
decisions are complete on the device and returned to the host's control."""
import math


def read(ctx):
    if "ticks" not in ctx or not ctx["round_s"]:
        return None
    ranked = sorted(ctx["round_s"])
    return 1e3 * ranked[math.ceil(0.95 * len(ranked)) - 1]
