"""Device self time under the stage ``gravity-mac~prepass`` per traced step, on
the slowest device (stage_times.py): the superblocks' candidate cut, its
classification and its compaction kernel over the full tree or the LET list.
A program without the stage reports nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, last="gravity-mac~prepass")
