"""Share of the rows a mesh step's global sort gathers that end on another
slab than they came from: ``migrant_rows / rows`` of the driver's ``exchange``
events of stage ``sort`` (schema v19: emitted where a step carries a
per-particle aux state, std-cooling's chemistry, through the sort on a mesh),
the median over the window's events. GSPMD ships every slab's rows to every
device for that gather (``shipped_rows``); this is how much of it is real
redistribution. A count, never a speed; nothing where the program reports no
such event (one chip, a step without an aux state, a program from before the
field)."""

import statistics


def read(run):
    shares = [e["migrant_rows"] / e["rows"] for e in run["events"]
              if e["kind"] == "exchange" and e.get("stage") == "sort"
              and e.get("rows") and e.get("migrant_rows") is not None]
    return statistics.median(shares) if shares else None
