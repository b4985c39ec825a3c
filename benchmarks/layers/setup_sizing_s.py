"""Self time of ``sphexa:reconfigure`` and the ``sphexa:size-*`` passes
inside it, before the window: construction-time sizing running and being
waited for, less its compiles (the shared keygen + argsort is the
reconfigure's own)."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "sizing_s")
