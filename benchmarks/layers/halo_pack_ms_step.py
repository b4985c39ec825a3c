"""Device self time under the first phase ``halo-exchange`` and the stages
``~pack`` and ``~jbuf`` per traced step, on the slowest device
(stage_times.py): every serve's packed layout, row indices and row gather,
and the annex's assembly. A program without the stages reports nothing."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, first="halo-exchange",
                                   stages=("pack", "jbuf"))
