"""Halo rows shipped per step and serve, from the driver's ``exchange``
events of the SPH stage (a count, never a speed)."""

import windows


def read(run):
    rows = [e["shipped_rows"] for e in run["events"]
            if e["kind"] == "exchange" and e.get("stage") == "sph"]
    return windows.median(rows)
