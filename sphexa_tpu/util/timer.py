"""Wall-clock phase timing + profile series — thin adapters over the
telemetry registry (sphexa_tpu/telemetry/registry.py).

Counterpart of the reference's ``main/src/util/timer.hpp`` (per-substep
Timer printed each iteration, dumpable as a timing series with --profile,
ipropagator.hpp:80-119). The TPU step is one fused XLA program, so the
measurable phases are coarser: step (device compute incl. any recompile),
observables, output. The profile dump is an npz timeseries instead of the
reference's HDF5 group.

The implementations live on the registry (LapTimer / StepSeries): a
``ProfileRecorder`` given a ``Telemetry`` also emits every row as a
``phases`` event. These names stay for API stability.
"""

from sphexa_tpu.telemetry.registry import LapTimer, StepSeries


class Timer(LapTimer):
    """Accumulates named wall-clock laps within one iteration
    (``step(name)`` records since the last mark, timer.hpp:46)."""


class ProfileRecorder(StepSeries):
    """Per-iteration timing/metric rows; saved with --profile
    (ipropagator.hpp:83-87 writes the analogous HDF5 series).
    ``save`` returns whether a file was actually written."""
