"""Median over the window's ``numerics`` events of the last verified step's
``dt`` (the ``physics`` event of the same iteration) over the window's
``dt_cool_min`` (schema v15): how far the cooling-time limiter engages. 1.0 is
the cooling time setting the step; about 1e-8 in wind-shock's ramp from
``minDt``. A count, never a speed; nothing where the program reports no such
field."""

import windows


def read(run):
    dt = {e["it"]: e["dt"][-1] for e in run["events"]
          if e["kind"] == "physics" and e.get("dt")}
    return windows.median([dt[e["it"]] / e["dt_cool_min"]
                           for e in run["events"]
                           if e["kind"] == "numerics"
                           and e.get("dt_cool_min") and e["it"] in dt])
