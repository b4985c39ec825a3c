"""The plain references against the program's portable engine
(``backend="xla"``) at a tiny size on the CPU."""

import numpy as np
import pytest

import reference


@pytest.fixture(scope="module")
def sedov():
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    state, box, const = make_initializer("sedov")(12)
    sim = Simulation(state, box, const, prop="std", backend="xla")
    for _ in range(3):  # off the regular lattice
        sim.step()
    return sim, const


def test_brute_force_density_matches_the_xla_engine(sedov):
    import jax.numpy as jnp

    from sphexa_tpu.analysis import compute_output_fields
    from sphexa_tpu.sfc.box import BoundaryType

    sim, const = sedov
    s, box = sim.state, sim.box
    rho = compute_output_fields(s, box, sim.active_cfg, pipeline="std")["rho"]
    targets = reference.seeded_targets(7, s.n, 256)
    ref = reference.brute_force_density(
        jnp.asarray(targets, jnp.int32), s.x, s.y, s.z, s.h, s.m,
        box.hi - box.lo, sinc_index=float(const.sinc_index),
        periodic=tuple(b == BoundaryType.periodic for b in box.boundaries))
    worst, _rms = reference.scalar_rel_error(rho[targets], ref)
    # f32 sums over ~100 neighbours in another order, and the engine
    # evaluates the kernel by its polynomial fit (3e-7 rounding floor)
    assert worst < 5e-6


def test_kernel_norm_is_the_programs(sedov):
    _sim, const = sedov
    assert reference.sinc_kernel_norm(const.sinc_index) == pytest.approx(
        const.K, rel=1e-9)


def test_direct_sum_matches_the_programs_direct_sum():
    import jax.numpy as jnp

    from sphexa_tpu.gravity.direct import direct_gravity_at
    from sphexa_tpu.init import make_initializer

    state, _box, const = make_initializer("evrard")(12)
    targets = reference.seeded_targets(3, state.n, 64)
    tj = jnp.asarray(targets, jnp.int32)
    args = (state.x, state.y, state.z, state.m, state.h)
    mine = reference.direct_sum_gravity(tj, *args, const.g)
    theirs = direct_gravity_at(tj, *args, G=const.g)[:3]
    rms, p99 = reference.vector_rel_error(mine, theirs)
    assert rms < 1e-5 and p99 < 1e-5


def test_error_norms():
    ref = [np.array([1.0, 0.0]), np.array([0.0, 2.0]), np.zeros(2)]
    got = [np.array([1.1, 0.0]), np.array([0.0, 2.0]), np.zeros(2)]
    rms, p99 = reference.vector_rel_error(got, ref)
    assert rms == pytest.approx(0.1 / np.sqrt(2)) and p99 < 0.1 + 1e-9
    assert reference.scalar_rel_error([2.0, 1.0], [1.0, 1.0]) == (
        pytest.approx(1.0), pytest.approx(np.sqrt(0.5)))
