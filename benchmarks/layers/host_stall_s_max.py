"""The longest a clean check window's host path (``sphexa:pin`` + its
``sphexa:launch``es + ``sphexa:flush``, less any recovery inside) ran over
the median of the same: a stall of the host, as a number, in every run."""

import program_spans
import windows


def read(run):
    walls = [w["pin"] + w["launch"] + w["flush"] - w["recovery"]
             for w in program_spans.window_table(run["events"])]
    if not walls:
        return None
    return max(walls) - windows.median(walls)
