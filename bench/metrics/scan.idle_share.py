"""scan.idle_share: 1 - the union of device-op intervals over a traced
slice that lies inside a tick-scan segment, in percent: the device's idle
time within the scan itself, with no host boundary in the slice."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return 100.0 * tr["idle_share"]
