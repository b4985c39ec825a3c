"""Median per-step time of the window's clean check windows (no retrace,
reconfigure or rollback since the fetch before), from the driver's own
``window`` / ``step`` events."""

import windows


def read(run):
    m = windows.median(windows.clean_step_seconds(run["events"]))
    return None if m is None else 1e3 * m
