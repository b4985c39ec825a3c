"""Self time of ``sphexa:fetch`` and ``sphexa:launch`` before the window: the
warm-up's steps running and being waited for. No start-up cost of the
program's: a cell with long steps pays it whatever start-up does."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "steps_s")
