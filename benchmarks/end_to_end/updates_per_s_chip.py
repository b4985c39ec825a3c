"""Particles x steps completed (verified) in the window / the wall its
cycles took / chips, on the harness's clock. Cycles in which the driver
recovered nothing count as their median, so that a stall of a shared host
does not move the number; a cycle with a list rebuild, reconfigure or
rollback, and the one after it, count in full (windows.window_seconds)."""

import windows


def read(run):
    w = run["window"]
    seconds = windows.window_seconds(w["cycle_facts"])
    if not w["steps_completed"] or not seconds:
        return None
    return run["particles"] * w["steps_completed"] / seconds / run["chips"]
