"""Device self time under the stage ``gravity-mac~compact`` per traced step, on
the slowest device (stage_times.py): the compaction of every block's two
interaction lists (the main-pass Mosaic kernel; the packed-key sort on the
other side of the 500k switch). A program without the stage reports nothing."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, last="gravity-mac~compact")
