"""Fullest block's merged runs over the sized slots of the near field's run
axis (``live_runs_max / run_slots`` of the driver's ``exchange`` events of the
gravity stage, schema v14), the fullest window's: ``halo_run_fill`` of the
tree solve's leaf serve. A count, never a speed; nothing where the program
reports no such fields."""


def read(run):
    fills = [e["live_runs_max"] / e["run_slots"] for e in run["events"]
             if e["kind"] == "exchange" and e.get("stage") == "gravity"
             and e.get("run_slots")]
    return max(fills, default=None)
