"""Median ``m2p_fill`` over the window's ``window`` / ``step`` events: live
slots of the tree solve's M2P lists over real lists x cap, the
fullest shard's (``compute_gravity``'s diagnostics, schema v13): how far the
block loop's width-following stages engage. A count, never a speed."""

import windows


def read(run):
    return windows.median([e["m2p_fill"] for e in run["events"]
                           if e["kind"] in ("window", "step")
                           and "m2p_fill" in e])
