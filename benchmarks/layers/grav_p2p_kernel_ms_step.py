"""Device self time under the stage ``gravity-p2p~kernel`` per traced step, on
the slowest device (stage_times.py): the near field's streamed pair kernel
with its blocked targets and packed j rows. A program without the stage
reports nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, last="gravity-p2p~kernel")
