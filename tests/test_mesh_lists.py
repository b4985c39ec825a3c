"""The ``std`` case of tests/mesh_list_cases.py (which see), and what a
mesh under self-gravity still streams."""

import pytest  # noqa: F401

CASE = "std"

from mesh_list_cases import *  # noqa: E402,F401,F403  (the case's tests)


@pytest.mark.parametrize("prop,kw", [
    ("ve", {"halo_mode": "windowed"}),
    ("nbody", {}),
    ("ve", {"dt_bins": 2}),
    ("ve", {"use_lists": False}),
], ids=["windowed-halo", "nbody", "block-dt", "lists-off"])
def test_what_still_streams_on_a_mesh_under_gravity(prop, kw):
    """Under self-gravity a mesh run walks lists too (the solve sorts its
    own copy: tests/mesh_gravity_list_cases.py); what keeps the streamed
    step there: the windowed halo mode (no send layout to freeze),
    ``nbody`` (no pair stage), block time steps (their own fold-key sort)
    and ``use_lists=False``."""
    import jax

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    state, box, const = make_initializer("evrard")(8)
    keep = (state.n // P) * P
    state = jax.tree.map(
        lambda a: a[:keep] if getattr(a, "ndim", 0) >= 1
        and a.shape[0] == state.n else a, state)
    sim = Simulation(state, box, const, prop=prop, num_devices=P,
                     backend="pallas", **kw)
    assert sim.gravity_on and sim._mesh is not None
    assert not sim._use_lists and sim.pair_lists is None
    assert sim._engine_facts()["lists"] is False
    assert sim._cfg.list_slot_cap == 0
    assert not hasattr(sim._stepper, "rebuild")
