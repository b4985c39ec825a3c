"""Device self time under the stage ``sort~aux`` per traced step, on the
slowest device (stage_times.py): the row gather that carries an aux pytree
(the chemistry of a std-cooling step: seven float32 fields stacked) through
the per-step SFC sort, apart from the state's own gather (``sort~permute``).
Nothing where no step sorts an aux state, and from a program without the
stage."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, last="sort~aux")
