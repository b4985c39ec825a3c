"""Median wall of one restartable HDF5 dump (derived-field recompute +
device->host + write) over the dumps inside the window."""

import windows


def read(run):
    return windows.median(windows.span_durations(run["spans"], "dump"))
