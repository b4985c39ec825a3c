"""Median wall of one whole traffic cycle that ends in a dump: its check
window, the flush, the dump and what the host does between them. What the
dump cell's throughput is made of; it falls with ``dump_s`` unless the
steps slow down under the dump."""

import windows


def read(run):
    return windows.median([c["wall_s"] for c in run["window"]["cycle_facts"]
                           if c["dumped"]])
