"""Median of the harness's spans round ``compute_output_fields``."""

import windows


def read(run):
    return windows.median(
        windows.span_durations(run["spans"], "dump-recompute"))
