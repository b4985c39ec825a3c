"""Device self time under the first phase ``gravity-exchange`` outside the
stages ``~wire`` and ``~psum`` per traced step, on the slowest device
(stage_times.py): the near-field serve's index work (split, cover, localize,
pack, j-buffer). Nothing where the program has no such stage: the whole phase
would read as index work."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, first="gravity-exchange",
                                   but_stages=("wire", "psum"))
