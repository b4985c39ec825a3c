"""Median of the harness's spans round ``write_snapshot[_sharded]``
(device->host of the conserved fields + the HDF5 write to TMPDIR)."""

import windows


def read(run):
    return windows.median(windows.span_durations(run["spans"], "dump-write"))
