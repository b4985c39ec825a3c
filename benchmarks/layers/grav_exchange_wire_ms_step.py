"""Device self time under the first phase ``gravity-exchange`` and the stages
``~wire`` and ``~psum`` per traced step, on the slowest device
(stage_times.py): the sharded tree solve's collectives alone (the upsweep's
three psums, the near field's coverage all_gather and ppermute rounds). A
program without the stages reports nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, first="gravity-exchange",
                                   stages=("wire", "psum"))
