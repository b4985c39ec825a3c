"""Device self time under the ``sphexa/turbulence`` scope per traced step, on
the slowest device (trace_reduce.py): the stirred propagators' OU update,
Helmholtz projection and per-particle sum over the stirring modes
(``hydro_turb.drive_turbulence``). A trace without the scope (a program
that stirs nothing) reports nothing here."""

import trace_reduce

PHASES = ('turbulence',)


def read(run):
    trace = run["trace"]
    if not trace or PHASES[0] not in trace["phase_s_max"]:
        return None
    return trace_reduce.phase_ms_per_step(trace, PHASES)
