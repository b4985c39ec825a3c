"""Median ``compact_chunk_live`` over the window's ``window`` / ``step``
events: of the chunks of 128 slots the compaction kernel's main pass walks
(each block's row up to its superblock's own count), the share that holds a
live lane, the fullest shard's (``compute_gravity``'s diagnostics, schema
v17). The others cost the kernel a scalar test: with ``grav_compact_ms_step``
it prices a live and a dead chunk. A count, never a speed."""

import windows


def read(run):
    return windows.median([e["compact_chunk_live"] for e in run["events"]
                           if e["kind"] in ("window", "step")
                           and "compact_chunk_live" in e])
