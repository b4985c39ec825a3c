"""Device self time under the ``sphexa/cooling`` scope per traced step, on the
slowest device (trace_reduce.py): the ``std-cooling`` step's cooling-time
limiter and its subcycled network (``cooling.cool_timestep`` and
``cooling.cool_step`` in ``propagator._step_hydro_std_cooling``). A trace
without the scope (a program that cools nothing) reports nothing here."""

import trace_reduce

PHASES = ('cooling',)


def read(run):
    trace = run["trace"]
    if not trace or PHASES[0] not in trace["phase_s_max"]:
        return None
    return trace_reduce.phase_ms_per_step(trace, PHASES)
