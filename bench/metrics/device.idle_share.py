"""device.idle_share: 1 - the union of device-op intervals over the traced
window of whole rounds, host boundaries included, in percent."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return 100.0 * tr["idle_share"]
