"""Slots of the near field's run axis on a mesh, from the driver's
``exchange`` events of the gravity stage (``run_slots``, schema v14): a
block's near-field leaves are merged into runs before the exchange and cut to
this sized high-water (``GravityConfig.p2p_run_cap``), where the exchange
worked on ``p2p_cap`` leaf slots a block before. A count, never a speed;
nothing where the program reports no such field."""

import windows


def read(run):
    return windows.median([e["run_slots"] for e in run["events"]
                           if e["kind"] == "exchange"
                           and e.get("stage") == "gravity"
                           and "run_slots" in e])
