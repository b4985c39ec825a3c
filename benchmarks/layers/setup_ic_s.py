"""Self time of ``sphexa:init-case``: the case function making the
particles (host numpy, the device placement it waits for), less the
compiles under it (startup_spans.py)."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "ic_s")
