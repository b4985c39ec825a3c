"""Device self time under the first phase ``halo-exchange`` and the stage
``~localize`` per traced step, on the slowest device (stage_times.py): the
split of runs at slab boundaries and their rewrite into j-buffer rows. A
program without the stage reports nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, first="halo-exchange",
                                   stages=("localize",))
