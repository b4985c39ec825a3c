"""Device self time whose LAST scope is ``gravity-m2p`` per traced step, on the
slowest device (stage_times.py): the far field, wherever it is called from.
The block loop's M2P reads under the first phase ``gravity-mac`` (so
``gravity_ms_step`` holds it there); this reads it by its own name."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, last="gravity-m2p")
