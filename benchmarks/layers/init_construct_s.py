"""The initialiser plus the ``Simulation`` constructor (host numpy ICs,
placement, construction-time sizing)."""

import windows


def read(run):
    return windows.median(
        windows.span_durations(run["setup_spans"], "init-construct"))
