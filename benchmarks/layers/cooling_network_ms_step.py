"""Device self time under the stage ``cooling~network`` per traced step, on the
slowest device (stage_times.py): ``cooling.cool_step``, the eight subcycles of
the species and the energy with their step-averaged source; what is left of
``cooling_ms_step`` is the limiter's pass. A program without the stage reports
nothing here."""

import stage_times


def read(run):
    return stage_times.ms_per_step(run, last="cooling~network")
