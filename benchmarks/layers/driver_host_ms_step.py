"""Median over the clean check windows of the host time spent with nothing
in flight (``sphexa:settle`` after the fetch + ``sphexa:pin`` before the
first launch), per step of the window: ``driver_gap_share`` measured from
inside."""

import program_spans
import windows


def read(run):
    m = windows.median([(w["settle"] + w["pin"]) / w["steps"]
                        for w in program_spans.window_table(run["events"])])
    return None if m is None else 1e3 * m
