"""Device self time under the ``sphexa/<phase>`` scopes below per traced
step, on the slowest device (trace_reduce.py)."""

import trace_reduce

PHASES = ('density', 'iad', 'momentum-energy', 'xmass', 'gradh', 'divv-curlv', 'av-switches', 'eos')


def read(run):
    return trace_reduce.phase_ms_per_step(run["trace"], PHASES)
