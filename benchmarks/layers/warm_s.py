"""First launch to the end of warm-up (compiles or cache loads, the
warm-up steps, the warm-up dump where the traffic dumps)."""

import windows


def read(run):
    return windows.median(windows.span_durations(run["setup_spans"], "warm"))
