"""Share of the rows a pass over the persistent pair lists fetches that a
lane is taken from: kept chunks over (runs x the rows a run's copy brings
in), ``chunks_live / (runs_live * run_rows)`` of the window's newest
``rebuild_lists`` event (schema v18). A run of the lists is a tile of at
most ``run_rows`` chunks and its copy is exactly that many rows, so 1.0 is
every fetched row read. A count, never a speed; nothing where the window
holds no rebuild or the program reports no such fields (before v18)."""

import list_lifecycle


def read(run):
    built = [e for e in list_lifecycle.rebuilds(run["events"])
             if e.get("runs_live") and e.get("run_rows")]
    if not built:
        return None
    e = built[-1]
    return e["chunks_live"] / (e["runs_live"] * e["run_rows"])
