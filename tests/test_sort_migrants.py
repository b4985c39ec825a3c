"""The mesh sort's migrant counter (telemetry ``exchange`` stage ``sort``,
schema v19): of the rows a step's global SFC sort gathers on a mesh, how many
end on another slab than they came from. Counted inside ``sort~aux`` only
where an aux state (std-cooling's chemistry) rides the sort over a sharded
particle axis; every other step program lowers as before.

On the virtual CPU mesh of conftest.py, the sort alone: nothing here runs a
pair kernel.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sphexa_tpu.init import init_sedov
from sphexa_tpu.parallel import make_mesh, shard_state
from sphexa_tpu.physics.cooling import ChemistryData
from sphexa_tpu.propagator import _force_stage_prologue, _sort_by_keys
from sphexa_tpu.sfc.keys import compute_sfc_keys
from sphexa_tpu.simulation import make_propagator_config

SHARDS = 4


@pytest.fixture(scope="module")
def sorted_case():
    """A Sedov lattice already in key order, with a chemistry whose rows
    are numbered so that a misplaced one shows."""
    state, box, const = init_sedov(12)
    keys = compute_sfc_keys(state.x, state.y, state.z, box, curve="hilbert")
    order = jnp.argsort(keys)
    state = jax.tree.map(
        lambda a: a[order] if getattr(a, "ndim", 0) == 1 else a, state)
    chem = dataclasses.replace(
        ChemistryData.ionized(state.n),
        hi=jnp.arange(state.n, dtype=jnp.float32))
    assert state.n % SHARDS == 0
    return state, box, const, chem


def _swap_outer_slabs(tree, n):
    slab = n // SHARDS
    perm = np.arange(n)
    perm[:slab], perm[-slab:] = np.arange(n - slab, n), np.arange(slab)
    return jax.tree.map(
        lambda a: a[perm] if getattr(a, "ndim", 0) == 1 else a, tree)


@pytest.mark.parametrize("swapped, share", [(False, 0.0), (True, 0.5)],
                         ids=["in-key-order", "outer-slabs-swapped"])
def test_counts_rows_that_change_slab(sorted_case, swapped, share):
    state, box, _, chem = sorted_case
    if swapped:
        state, chem = (_swap_outer_slabs(t, state.n) for t in (state, chem))
    mesh = make_mesh(SHARDS)
    sstate, schem = shard_state(state, mesh), shard_state(chem, mesh)
    sort = jax.jit(lambda s, c: _sort_by_keys(s, box, "hilbert", aux=c,
                                              shards=SHARDS))
    new_state, keys, new_chem, migrants = sort(sstate, schem)
    assert int(migrants) == round(share * state.n)
    # the same sort as without the counter, the chemistry aligned with it
    ref_state, ref_keys, ref_chem = _sort_by_keys(state, box, "hilbert",
                                                  aux=chem)
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(ref_keys))
    np.testing.assert_array_equal(np.asarray(new_state.x),
                                  np.asarray(ref_state.x))
    np.testing.assert_array_equal(np.asarray(new_chem.hi),
                                  np.asarray(ref_chem.hi))
    if swapped:
        slab = state.n // SHARDS
        np.testing.assert_array_equal(np.asarray(new_chem.hi[:slab]),
                                      np.arange(slab))


@pytest.mark.parametrize("aux, on_mesh, counted", [
    (True, True, True), (False, True, False), (True, False, False)],
    ids=["aux-on-mesh", "no-aux", "one-device"])
def test_only_an_aux_state_on_a_mesh_is_counted(sorted_case, aux, on_mesh,
                                                counted):
    """The prologue's diagnostics: ``sort_migrant_rows`` where the step
    carries an aux state over a sharded axis, nothing otherwise (a step
    without aux, or on one device, emits no ``sort`` exchange event and
    keeps its lowering)."""
    state, box, const, chem = sorted_case
    cfg = make_propagator_config(state, box, const, backend="pallas")
    if on_mesh:
        cfg = dataclasses.replace(cfg, mesh=make_mesh(SHARDS),
                                  shard_axis="p")
    out = jax.eval_shape(
        lambda s, c: _force_stage_prologue(s, box, cfg, None, aux=c),
        state, chem if aux else None)
    ldiag = out[3]
    assert (ldiag is not None) == counted
    if counted:
        assert set(ldiag) == {"sort_migrant_rows"}
        assert ldiag["sort_migrant_rows"].shape == ()


def test_exchange_event_of_stage_sort():
    """Simulation._emit_distributed turns the fetched counter into one
    ``exchange`` event of stage ``sort``; without the counter none."""
    from types import SimpleNamespace

    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.registry import validate_event
    from sphexa_tpu.telemetry.sinks import MemorySink

    sink = MemorySink()
    sim = SimpleNamespace(
        _mesh=SimpleNamespace(size=SHARDS), telemetry=Telemetry(sinks=[sink]),
        state=SimpleNamespace(n=4000), iteration=8, _halo_info=None,
        _grav_halo_info=None, _imbalance_ratio=2.0)
    Simulation._emit_distributed(sim, {"sort_migrant_rows": np.int32(12)},
                                 steps=4)
    (e,) = [e for e in sink.events if e["kind"] == "exchange"]
    assert validate_event(e) == []
    assert (e["stage"], e["rows"], e["migrant_rows"], e["shipped_rows"],
            e["steps"]) == ("sort", 4000, 12, 3000, 4)
    Simulation._emit_distributed(sim, {}, steps=4)
    assert len([e for e in sink.events if e["kind"] == "exchange"]) == 1
