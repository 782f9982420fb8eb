"""sweep.idle_share: `device.idle_share` of the sweep's trace, which holds
the window's last `simulate_batch` call whole (`drive.run_batch` begins
the trace as that call starts): the host's table builds and argument
transfers against the sharded tick scan, averaged over the chips.  A
faster scan leaves the host's part as it was, so this share rises;
`sweep.device_ms_per_cell_tick` reads the scan itself."""
from run import read_metric


def read(ctx):
    return read_metric("device.idle_share", ctx)
