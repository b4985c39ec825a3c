"""List rebuilds + reconfigures + rollbacks the driver paid for inside
the window (a count)."""

import windows


def read(run):
    return windows.recoveries(run["events"])
