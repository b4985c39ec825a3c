"""Device self time under the first phase ``halo-exchange`` and the stage
``~wire`` per traced step, on the slowest device (stage_times.py): the SPH
halo's collectives alone (table psum, coverage all_gather, ppermute rounds),
each an op issuing and an op waiting: the core's time in them, not the
link's. A program without the stage reports nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, first="halo-exchange", stages=("wire",))
