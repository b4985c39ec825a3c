"""Median duration of one step's dispatch (the program's ``sphexa:launch``
span) over the window's clean check windows."""

import program_spans
import windows


def read(run):
    m = windows.median([d for w in program_spans.window_table(run["events"])
                        for d in w["launches"]])
    return None if m is None else 1e3 * m
