"""Fullest group's live runs over the sized slots of the SPH halo's run axis
(``live_runs_max / run_slots`` of the driver's ``exchange`` events of the SPH
stage, schema v14), the fullest window's: how near the halo is to a sentinel
trip (1.0 is the last run that fits), and the share of the slots the fullest
group uses. A count, never a speed; nothing where the program reports no such
fields."""


def read(run):
    fills = [e["live_runs_max"] / e["run_slots"] for e in run["events"]
             if e["kind"] == "exchange" and e.get("stage") == "sph"
             and e.get("run_slots")]
    return max(fills, default=None)
