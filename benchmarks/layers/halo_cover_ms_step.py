"""Device self time under the first phase ``halo-exchange`` and the stages
``~cover`` and ``~table`` per traced step, on the slowest device
(stage_times.py): the slab's cell histogram and the coverage bitmap's
scatter-adds. A program without the stages reports nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, first="halo-exchange",
                                   stages=("cover", "table"))
