"""Median over the clean check windows of the time the host was blocked on
the device in the window's batched fetch (``sphexa:fetch`` inside
``sphexa:flush``), per step of the window."""

import program_spans
import windows


def read(run):
    m = windows.median([w["fetch"] / w["steps"]
                        for w in program_spans.window_table(run["events"])])
    return None if m is None else 1e3 * m
