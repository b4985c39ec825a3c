"""Device self time under the stage ``neighbors~cell-ranges`` per traced step,
on the slowest device (stage_times.py), whatever the first phase: the
cell-table lookups of every window slot, the cull, the compaction sorts and
the run merge of ``group_cell_ranges``. A program without the stage reports
nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, last="neighbors~cell-ranges")
