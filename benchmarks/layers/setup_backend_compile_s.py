"""Seconds of backend compiles the cache did not hold (``backend_s`` where
``cache`` is not ``hit``): in a warm run the programs jax never stores
(under a second) plus any that was evicted or keyed anew."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "backend_compile_s")
