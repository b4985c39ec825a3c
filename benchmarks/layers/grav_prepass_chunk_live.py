"""Median ``prepass_chunk_live`` over the window's ``window`` / ``step``
events: of the chunks of 128 slots the compaction kernel's pre-pass walks
(every chunk of the full tree, or of the LET list on a mesh, once for each
superblock), the share that holds a live lane, the fullest shard's
(``compute_gravity``'s diagnostics, schema v17). The others cost the kernel a
scalar test: with ``grav_prepass_ms_step`` it prices a live and a dead chunk.
A count, never a speed."""

import windows


def read(run):
    return windows.median([e["prepass_chunk_live"] for e in run["events"]
                           if e["kind"] in ("window", "step")
                           and "prepass_chunk_live" in e])
