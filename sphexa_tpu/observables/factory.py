"""Observable selection + constants.txt output.

Counterpart of the reference's ``main/src/observables/factory.hpp:46-70``
(observable chosen by test-case settings) and ``iobservables.hpp`` (one
row appended to constants.txt per iteration). The base row is
iteration, time, minDt, etot, ecin, eint, egrav; case-specific observables
append their own columns.
"""

import os
from typing import Dict, List, Optional

import numpy as np

from sphexa_tpu.init import split_case_spec
from sphexa_tpu.init.wind_shock import wind_shock_constants
from sphexa_tpu.observables.extras import (
    kh_growth_rate,
    mach_rms,
    wind_bubble_fraction,
)
from sphexa_tpu.sph.particles import ideal_gas_cv

BASE_COLUMNS = ["iteration", "time", "minDt", "etot", "ecin", "eint", "egrav"]


class TimeAndEnergy:
    """Default observable: energies only (time_energies.hpp)."""

    extra_columns: List[str] = []
    needs_fields = False

    def compute_extra(self, state, box, fields) -> List[float]:
        return []


class TimeEnergyGrowth:
    """KH growth-rate column (time_energy_growth.hpp)."""

    extra_columns = ["khGrowthRate"]
    needs_fields = True

    def compute_extra(self, state, box, fields) -> List[float]:
        vol = np.asarray(state.m) / fields["rho"]
        return [
            float(kh_growth_rate(state.x, state.y, state.vy, vol, box))
        ]


class TurbulenceMachRMS:
    """RMS Mach number column (turbulence_mach_rms.hpp)."""

    extra_columns = ["machRMS"]
    needs_fields = True

    def compute_extra(self, state, box, fields) -> List[float]:
        return [
            float(mach_rms(state.vx, state.vy, state.vz, fields["c"]))
        ]


class WindBubble:
    """Surviving cloud-mass fraction column (wind_bubble_fraction.hpp)."""

    extra_columns = ["survivorFraction"]
    needs_fields = True

    def __init__(self, settings: Dict[str, float]):
        cv = ideal_gas_cv(settings["mui"], settings["gamma"])
        self.rho_bubble = settings["rhoInt"]
        self.temp_wind = settings["uExt"] / cv
        self.initial_mass = (
            4.0 / 3.0 * np.pi * settings["rSphere"] ** 3 * settings["rhoInt"]
        )

    def compute_extra(self, state, box, fields) -> List[float]:
        return [
            float(
                wind_bubble_fraction(
                    fields["rho"], state.temp, state.m,
                    self.rho_bubble, self.temp_wind, self.initial_mass,
                )
            )
        ]


def make_observable(case: str, overrides: Optional[Dict[str, float]] = None):
    """Observable for a test case, keyed like the reference factory (which
    keys on the marker entries the init settings plant, factory.hpp:46-70:
    'kelvin-helmholtz', 'wind-shock', 'turbulence'). ``overrides`` are the
    case's settings-file overrides, so threshold-bearing observables match
    the actual setup."""
    case = split_case_spec(case)[0]  # callers may hand the whole run spec
    if case == "kelvin-helmholtz":
        return TimeEnergyGrowth()
    if case == "wind-shock":
        return WindBubble(dict(wind_shock_constants(), **(overrides or {})))
    if case == "turbulence":
        return TurbulenceMachRMS()
    return TimeAndEnergy()


class ConstantsWriter:
    """Append one observable row per iteration to constants.txt
    (iobservables.hpp / fileutils::writeColumns)."""

    def __init__(self, path: str, observable=None, restart_iteration=None):
        self.path = path
        self.observable = observable or TimeAndEnergy()
        # appending to an existing file (restart) must not inject a second
        # header line mid-file
        self._wrote_header = os.path.exists(path) and os.path.getsize(path) > 0
        if restart_iteration is not None and self._wrote_header:
            self._truncate_after(restart_iteration)

    def _truncate_after(self, iteration: int):
        """Drop rows with iteration > the restart point, so resuming from
        an older snapshot (--init dump.h5:-2) leaves a monotonic series
        instead of overlapping row ranges."""
        with open(self.path) as f:
            lines = f.readlines()
        kept = [
            ln for ln in lines
            if ln.startswith("#")
            or not ln.strip()
            or float(ln.split()[0]) <= iteration
        ]
        if len(kept) != len(lines):
            with open(self.path, "w") as f:
                f.writelines(kept)

    def write(
        self,
        iteration: int,
        state,
        box,
        energies: Dict[str, float],
        fields: Optional[Dict[str, np.ndarray]] = None,
    ) -> List[float]:
        row = [
            float(iteration), float(state.ttot), float(state.min_dt),
            float(energies["etot"]), float(energies["ecin"]),
            float(energies["eint"]), float(energies["egrav"]),
        ]
        row += self.observable.compute_extra(state, box, fields)
        return self.write_row(row)

    def write_row(self, values) -> List[float]:
        """Append one pre-computed row (the in-graph ledger path: the
        Simulation already fetched every scalar at its check/flush
        boundary, so this touches no state and triggers no device
        sync). Same header/format as ``write`` — byte-compatible."""
        row = [float(v) for v in values]
        with open(self.path, "a") as f:
            if not self._wrote_header:
                f.write("# " + " ".join(BASE_COLUMNS + self.observable.extra_columns) + "\n")
                self._wrote_header = True
            f.write(" ".join(f"{v:.10g}" for v in row) + "\n")
        return row
