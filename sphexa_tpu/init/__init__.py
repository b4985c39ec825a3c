"""Initial conditions for the built-in test cases.

Counterpart of the reference's ``main/src/init/``: each case is a settings
dict + coordinate generation + field initialization, producing a
ParticleState, a Box, and SimConstants. ``make_initializer`` is the factory
(init/factory.hpp:43-111) keyed by the same case names the reference CLI
accepts.
"""

import functools
from typing import Callable, Dict

from sphexa_tpu.init.evrard import (
    evrard_constants,
    init_evrard,
    init_evrard_cooling,
)
from sphexa_tpu.init.gresho_chan import gresho_chan_constants, init_gresho_chan
from sphexa_tpu.init.grid import regular_grid
from sphexa_tpu.init.isobaric_cube import (
    init_isobaric_cube,
    isobaric_cube_constants,
)
from sphexa_tpu.init.kelvin_helmholtz import (
    init_kelvin_helmholtz,
    kelvin_helmholtz_constants,
)
from sphexa_tpu.init.noh import init_noh, noh_constants
from sphexa_tpu.init.sedov import init_sedov, sedov_constants
from sphexa_tpu.init.turbulence import init_turbulence, turbulence_constants
from sphexa_tpu.init.wind_shock import init_wind_shock, wind_shock_constants
from sphexa_tpu.telemetry.registry import span

# case name -> init function; the name set matches the reference's --init
# choices (main/src/init/factory.hpp:59-100)
CASES: Dict[str, Callable] = {
    "sedov": init_sedov,
    "noh": init_noh,
    "evrard": init_evrard,
    "gresho-chan": init_gresho_chan,
    "isobaric-cube": init_isobaric_cube,
    "kelvin-helmholtz": init_kelvin_helmholtz,
    "wind-shock": init_wind_shock,
    "turbulence": init_turbulence,
    "evrard-cooling": init_evrard_cooling,
}


#: what a run spec may ask of the program by name ('noh+list-lifecycle'):
#: a program from before the capability refuses the spec where it parses
#: it, instead of failing deep in a run. ``list-lifecycle`` (PR 25): pair
#: lists sized for the relaxed h and rebuilt with the outgoing list let go
#: first; without it Noh at -n 128 runs out of device memory in the
#: rebuild of its first rolled-back window. ``mesh-gravity`` (PR 29): on a
#: mesh the SPH halo's caps are sized under shard_map as the step takes
#: its needs, and the tree solve's list caps from every block each slab
#: forms; without it Evrard at -n 200 on four v5e chips sizes its halo at
#: the 256-row floor, and its near-field list from a sample that misses
#: the fullest block, and dies after four re-sizes, six minutes in.
#: ``cooling-network`` (PR 36): std-cooling evolves the six-species
#: network and integrates its source without cancellation; without it
#: ``--prop std-cooling`` is the CIE table with pass-through fractions (a
#: cheaper program under the same name) and its source is (u' - u) / dt,
#: zero or one ulp of u over dt at a step's dt.
CAPABILITIES = frozenset({"list-lifecycle", "mesh-gravity",
                          "cooling-network"})


def split_case_spec(name: str):
    """'case[+need...][:settings.json]' -> (case, settings_path or None);
    anything else -> (name, None). SINGLE source of the spec grammar —
    main.py keys observables/dump metadata on the same parse. A ``need``
    this program lacks (CAPABILITIES) raises ValueError."""
    head, sep, settings_path = name.partition(":")
    case, *needs = head.split("+")
    if case not in CASES:
        return name, None
    missing = sorted(set(needs) - CAPABILITIES)
    if missing:
        raise ValueError(f"run spec '{name}' needs {missing}; this program "
                         f"has {sorted(CAPABILITIES)}")
    return case, settings_path if sep else None


def make_initializer(name: str) -> Callable:
    """Look up a test case by reference CLI name, or build a file-restart
    initializer for 'path[:step]' arguments (init/factory.hpp:43-111).

    ``case:settings.json`` appends a JSON settings file whose keys override
    the case defaults (the reference's ``--init sedov:my_settings`` path,
    factory.hpp:47-48); ``case+need`` asks for a program capability.

    The function returned runs under the host span ``sphexa:init-case``
    (handle-less: a caller that constructs its ``Simulation`` afterwards
    finds the span, and the initialiser's compiles, in that registry).
    """
    init = _case_function(name)
    case = split_case_spec(name)[0]

    @functools.wraps(init)
    def spanned(*args, **kwargs):
        with span("sphexa:init-case", case=case):
            return init(*args, **kwargs)

    return spanned


def _case_function(name: str) -> Callable:
    case, settings_path = split_case_spec(name)
    if case in CASES and settings_path is None:
        return CASES[case]
    if settings_path is not None:
        import json

        try:
            with open(settings_path) as f:
                overrides = json.load(f)
        except OSError as e:
            raise ValueError(f"cannot read settings file {settings_path}: {e}")
        except json.JSONDecodeError as e:
            raise ValueError(f"invalid JSON in {settings_path}: {e}")
        if not isinstance(overrides, dict):
            raise ValueError(f"{settings_path} must hold a JSON object")
        return functools.partial(CASES[case], overrides=overrides)

    from sphexa_tpu.init.file_init import (
        init_file_split,
        init_from_file,
        looks_like_file,
        parse_split_spec,
    )

    split = parse_split_spec(name)
    if split is not None and looks_like_file(split[0]):
        # 'path,N' particle-split up-sampling (factory.hpp:101)
        return functools.partial(init_file_split, split[0], split[1])
    if looks_like_file(name):
        return functools.partial(init_from_file, name)
    raise ValueError(
        f"unknown test case '{name}' (not a case name in {sorted(CASES)}, "
        "not 'case:settings.json', not 'file,N' splitting, and not an "
        "existing snapshot file)"
    )


__all__ = [
    "CAPABILITIES",
    "CASES",
    "make_initializer",
    "split_case_spec",
    "regular_grid",
    "init_sedov", "sedov_constants",
    "init_noh", "noh_constants",
    "init_evrard", "evrard_constants",
    "init_evrard_cooling",
    "init_gresho_chan", "gresho_chan_constants",
    "init_isobaric_cube", "isobaric_cube_constants",
    "init_kelvin_helmholtz", "kelvin_helmholtz_constants",
    "init_wind_shock", "wind_shock_constants",
    "init_turbulence", "turbulence_constants",
]
