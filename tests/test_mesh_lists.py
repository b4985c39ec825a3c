"""The ``std`` case of tests/mesh_list_cases.py (which see), and what the
mesh still streams."""

import pytest  # noqa: F401

CASE = "std"

from mesh_list_cases import *  # noqa: E402,F401,F403  (the case's tests)


def test_gravity_on_a_mesh_keeps_streaming():
    """The mesh's tree solve runs on the global sort's slabs: under
    self-gravity a mesh run is not eligible, whatever is asked for."""
    import jax

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    state, box, const = make_initializer("evrard")(8)
    keep = (state.n // P) * P
    state = jax.tree.map(
        lambda a: a[:keep] if getattr(a, "ndim", 0) >= 1
        and a.shape[0] == state.n else a, state)
    sim = Simulation(state, box, const, prop="ve", num_devices=P,
                     backend="pallas", use_lists=True)
    assert sim.gravity_on and sim._mesh is not None
    assert not sim._lists_eligible and not sim._use_lists
    assert sim._engine_facts()["lists"] is False
    assert not hasattr(sim._stepper, "rebuild")
