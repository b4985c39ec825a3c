"""Seconds of tracing + lowering over the ``compile`` events before the
window: paid on a warm compile cache too."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "trace_lower_s")
