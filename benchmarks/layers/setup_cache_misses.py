"""``compile`` events before the window with ``cache`` = ``miss`` and a
backend compile of a second or more: programs large enough to be in the
cache that were not found there. 0 in a warm start that is one."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "cache_misses")
