"""Seconds the persistent compile cache took to hand executables back
(``retrieval_s`` of the ``compile`` events with ``cache`` = ``hit``)."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "exe_load_s")
