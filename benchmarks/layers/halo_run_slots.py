"""Slots of the SPH halo's run axis, from the driver's ``exchange`` events of
the SPH stage (``run_slots``, schema v14): the sized high-water of live runs a
group, which the exchange's split, coverage and rewrite and the pair kernels'
range blocks are as wide as, where they were the window's W3 before. A count,
never a speed; nothing where the program reports no such field."""

import windows


def read(run):
    return windows.median([e["run_slots"] for e in run["events"]
                           if e["kind"] == "exchange"
                           and e.get("stage") == "sph" and "run_slots" in e])
