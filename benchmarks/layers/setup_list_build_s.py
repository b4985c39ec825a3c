"""Self time of ``sphexa:rebuild-lists`` before the window: the first list
build (and any the warm-up repeated), less its compiles."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "list_build_s")
