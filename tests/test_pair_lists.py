"""Persistent-list engine (sph/pair_lists.py + the list-walk engine in
sph/pallas_pairs.py) equivalence vs the streaming engine, INTERPRET mode.

The list-walk path must reproduce the streaming engine's pair SET exactly
(the compaction only removes lanes outside the skin-inflated group bbox,
a superset of every 2h_i sphere), so results match up to f32 summation
order. Drift robustness: after particles move by less than skin/2 the
STALE lists must still produce results matching a fresh streaming pass
on the moved positions — the Verlet-skin contract the steady steps rely
on (cstone rebuilds per step, find_neighbors.cuh; lists amortize that)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sphexa_tpu.init import init_sedov, init_noh
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.simulation import make_propagator_config
from sphexa_tpu.sph import pallas_pairs as pp
from sphexa_tpu.sph.pair_lists import (
    build_pair_lists,
    estimate_slot_cap,
    lists_valid,
)


def _setup(init, side):
    state, box, const = init(side)
    cfg = make_propagator_config(state, box, const, block=4096,
                                 backend="pallas")
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    return ss, keys, box, const, cfg.nbr


# noh 16^3: open boundaries, real (non-fold) shift path.
# sedov 30^3: periodic with a real grid (fold mode would reject lists).
CASES = [(init_noh, 16), (init_sedov, 30)]


@pytest.fixture(scope="module", params=CASES, ids=["noh", "sedov"])
def case(request):
    init, side = request.param
    return _setup(init, side)


@pytest.fixture(scope="module")
def built(case):
    ss, keys, box, const, nbr = case
    skin = 0.2 * float(jnp.max(ss.h))
    scap = estimate_slot_cap(ss.x, ss.y, ss.z, ss.h, keys, box, nbr, skin)
    lists = build_pair_lists(
        ss.x, ss.y, ss.z, ss.h, keys, box, nbr, skin, scap, interpret=True
    )
    return lists, skin, scap


def test_build_structure(case, built):
    ss, keys, box, const, nbr = case
    lists, skin, scap = built
    assert int(lists.overflow) == 0
    # the compacted lane total must be bounded by the streamed lanes and
    # must cover at least every true neighbor pair
    cnt = np.asarray(lists.cnt)
    assert (cnt >= 0).all() and (cnt <= 128).all()
    assert bool(lists_valid(ss.x, ss.y, ss.z, ss.h, lists))
    # staging bookkeeping is self-consistent
    csum = np.cumsum(cnt, axis=1)
    np.testing.assert_array_equal(np.asarray(lists.tail), csum[:, -1] % 128)


def test_density_lists_match_streaming(case, built):
    ss, keys, box, const, nbr = case
    lists, _, _ = built
    rho0, nc0, _ = pp.pallas_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, keys, box, const, nbr, interpret=True
    )
    rho1, nc1, _ = pp.pallas_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, None, box, const, nbr,
        interpret=True, lists=lists,
    )
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc0))
    np.testing.assert_allclose(np.asarray(rho1), np.asarray(rho0),
                               rtol=2e-6)


def test_momentum_std_lists_match_streaming(case, built):
    ss, keys, box, const, nbr = case
    lists, _, _ = built
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    rho, _, _ = pp.pallas_density(x, y, z, h, m, keys, box, const, nbr,
                                  interpret=True)
    from sphexa_tpu.sph.hydro_std import compute_eos_std

    p, c = compute_eos_std(ss.temp, rho, const)
    cs, _ = pp.pallas_iad(x, y, z, h, m / rho, keys, box, const, nbr,
                          interpret=True)
    args = (x, y, z, ss.vx, ss.vy, ss.vz, h, m, rho, p, c, *cs)
    ax0, ay0, az0, du0, dt0, _ = pp.pallas_momentum_energy_std(
        *args, keys, box, const, nbr, interpret=True
    )
    cs1, _ = pp.pallas_iad(x, y, z, h, m / rho, None, box, const, nbr,
                           interpret=True, lists=lists)
    # off-diagonal components are ~0 on near-uniform lattices (pure
    # cancellation noise), so the atol scales with the TENSOR magnitude
    csc = max(float(np.abs(np.asarray(b)).max()) for b in cs)
    for a, b in zip(cs1, cs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6 * csc)
    ax1, ay1, az1, du1, dt1, _ = pp.pallas_momentum_energy_std(
        *args, None, box, const, nbr, interpret=True, lists=lists
    )
    scale = float(jnp.max(jnp.abs(ax0)))
    for a, b in zip((ax1, ay1, az1), (ax0, ay0, az0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(du1), np.asarray(du0), rtol=1e-4,
                               atol=1e-6 * float(jnp.max(jnp.abs(du0))))
    np.testing.assert_allclose(float(dt1), float(dt0), rtol=1e-5)


def test_momentum_ve_lists_match_streaming(case, built):
    ss, keys, box, const, nbr = case
    lists, _, _ = built
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    xm, nc, _ = pp.pallas_xmass(x, y, z, h, m, keys, box, const, nbr,
                                interpret=True)
    (kx, gradh), _ = pp.pallas_ve_def_gradh(
        x, y, z, h, m, xm, keys, box, const, nbr, interpret=True
    )
    from sphexa_tpu.sph.hydro_ve import compute_eos_ve

    prho, c, rho, p = compute_eos_ve(ss.temp, m, kx, xm, gradh, const)
    cs, _ = pp.pallas_iad(x, y, z, h, xm / kx, keys, box, const, nbr,
                          interpret=True)
    alpha = ss.alpha
    args = (x, y, z, ss.vx, ss.vy, ss.vz, h, m, prho, c, kx, xm, alpha,
            *cs)
    ax0, ay0, az0, du0, dt0, _ = pp.pallas_momentum_energy_ve(
        *args, keys, box, const, nbr, nc=nc, interpret=True
    )
    # list path for xmass/gradh/divv/av too (full VE op coverage)
    xm1, nc1, _ = pp.pallas_xmass(x, y, z, h, m, None, box, const, nbr,
                                  interpret=True, lists=lists)
    np.testing.assert_allclose(np.asarray(xm1), np.asarray(xm), rtol=2e-6)
    (kx1, gradh1), _ = pp.pallas_ve_def_gradh(
        x, y, z, h, m, xm, None, box, const, nbr, interpret=True,
        lists=lists,
    )
    np.testing.assert_allclose(np.asarray(kx1), np.asarray(kx), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(gradh1), np.asarray(gradh),
                               rtol=2e-4, atol=2e-6)
    dv0, _ = pp.pallas_iad_divv_curlv(
        x, y, z, ss.vx, ss.vy, ss.vz, h, kx, xm, *cs, keys, box, const,
        nbr, interpret=True,
    )
    dv1, _ = pp.pallas_iad_divv_curlv(
        x, y, z, ss.vx, ss.vy, ss.vz, h, kx, xm, *cs, None, box, const,
        nbr, interpret=True, lists=lists,
    )
    sc = float(jnp.max(jnp.abs(dv0[0])))
    np.testing.assert_allclose(np.asarray(dv1[0]), np.asarray(dv0[0]),
                               rtol=1e-4, atol=1e-5 * sc)
    a0, _ = pp.pallas_av_switches(
        x, y, z, ss.vx, ss.vy, ss.vz, h, c, kx, xm, dv0[0], alpha, *cs,
        keys, box, ss.min_dt, const, nbr, interpret=True,
    )
    a1, _ = pp.pallas_av_switches(
        x, y, z, ss.vx, ss.vy, ss.vz, h, c, kx, xm, dv0[0], alpha, *cs,
        None, box, ss.min_dt, const, nbr, interpret=True, lists=lists,
    )
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0), rtol=1e-4,
                               atol=1e-6)
    ax1, ay1, az1, du1, dt1, _ = pp.pallas_momentum_energy_ve(
        *args, None, box, const, nbr, nc=nc, interpret=True, lists=lists
    )
    scale = float(jnp.max(jnp.abs(ax0)))
    for a, b in zip((ax1, ay1, az1), (ax0, ay0, az0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(du1), np.asarray(du0), rtol=1e-4,
                               atol=1e-6 * float(jnp.max(jnp.abs(du0))))


def test_stale_lists_cover_drifted_positions(case, built):
    """Verlet contract: after drift < skin/2 the STALE lists still yield
    the same density as a FRESH streaming pass on the moved positions."""
    ss, keys, box, const, nbr = case
    lists, skin, _ = built
    rng = np.random.RandomState(3)
    amp = 0.45 * skin / np.sqrt(3.0)
    dx = jnp.asarray(rng.uniform(-amp, amp, ss.n), jnp.float32)
    dy = jnp.asarray(rng.uniform(-amp, amp, ss.n), jnp.float32)
    dz = jnp.asarray(rng.uniform(-amp, amp, ss.n), jnp.float32)
    x2, y2, z2 = ss.x + dx, ss.y + dy, ss.z + dz
    assert bool(lists_valid(x2, y2, z2, ss.h, lists))

    # fresh streaming pass: new sort + ranges on the moved positions
    from sphexa_tpu.sfc.keys import compute_sfc_keys

    keys2 = compute_sfc_keys(x2, y2, z2, box, curve="hilbert")
    order = jnp.argsort(keys2)
    rho0, nc0, _ = pp.pallas_density(
        x2[order], y2[order], z2[order], ss.h[order], ss.m[order],
        keys2[order], box, const, nbr, interpret=True,
    )
    inv = jnp.argsort(order)
    rho0, nc0 = rho0[inv], nc0[inv]

    # stale lists on the frozen build order
    rho1, nc1, _ = pp.pallas_density(
        x2, y2, z2, ss.h, ss.m, None, box, const, nbr,
        interpret=True, lists=lists,
    )
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc0))
    np.testing.assert_allclose(np.asarray(rho1), np.asarray(rho0),
                               rtol=2e-5)


def test_validity_detects_excess_drift(case, built):
    ss, keys, box, const, nbr = case
    lists, skin, _ = built
    x2 = ss.x.at[0].add(0.6 * skin)
    assert not bool(lists_valid(x2, ss.y, ss.z, ss.h, lists))
    h2 = ss.h.at[0].mul(1.0 + skin)  # h growth alone must also trip it
    assert not bool(lists_valid(ss.x, ss.y, ss.z, h2 + 0.51 * skin, lists))


def _run_sim(use_lists: bool, steps: int, check_every: int = 1):
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_noh(14)
    sim = Simulation(state, box, const, prop="std", block=4096,
                     backend="pallas", use_lists=use_lists,
                     check_every=check_every)
    diags = [sim.step() for _ in range(steps)]
    sim.flush()
    return sim, diags


def test_simulation_list_mode_matches_streaming():
    """Full Simulation in list mode vs per-step streaming: identical
    physics trajectory (physical quantities match after re-ordering; the
    list mode freezes the sort order between rebuilds)."""
    sim0, _ = _run_sim(False, 4)
    sim1, d1 = _run_sim(True, 4)
    assert sim1._use_lists and sim1._lists is not None
    assert any("list_slack" in d for d in d1)
    s0, s1 = sim0.state, sim1.state
    np.testing.assert_allclose(float(s0.ttot), float(s1.ttot), rtol=1e-6)
    # order-insensitive per-particle comparison: sort both by position
    for a, b, tol in ((s0.x, s1.x, 2e-6), (s0.temp, s1.temp, 1e-4),
                      (s0.vx, s1.vx, 1e-4)):
        np.testing.assert_allclose(np.sort(np.asarray(a)),
                                   np.sort(np.asarray(b)), rtol=tol,
                                   atol=1e-7)


def test_simulation_list_rebuild_on_expiry():
    """Drive enough steps that drift eats the skin: the driver must
    rebuild (proactively or by discard) and keep stepping correctly."""
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_noh(14)
    # a tiny skin forces frequent expiry, exercising both the proactive
    # and the discard-and-replay recovery paths
    sim = Simulation(state, box, const, prop="std", block=4096,
                     backend="pallas", use_lists=True, check_every=3,
                     list_skin_rel=0.05)
    rebuilds = 0
    orig = sim._rebuild_lists

    def counting(*args, **kw):
        nonlocal rebuilds
        rebuilds += 1
        orig(*args, **kw)

    sim._rebuild_lists = counting
    diags = [sim.step() for _ in range(12)]
    sim.flush()
    assert sim._lists is not None
    slacks = [d.get("list_slack") for d in diags if "list_slack" in d]
    assert slacks, "no list diagnostics surfaced"
    # noh piston flow drifts ~0.2 h_min/step: a 0.05*2h skin cannot
    # survive 12 steps — the rebuild machinery must actually have fired
    # beyond the initial build
    assert rebuilds >= 2, f"expected expiry rebuilds, got {rebuilds}"
    # and the run stayed physical
    assert np.isfinite(float(sim.state.ttot))
    assert float(sim.state.ttot) > 0


def test_slot_cap_overflow_sentinel(case):
    ss, keys, box, const, nbr = case
    skin = 0.2 * float(jnp.max(ss.h))
    lists = build_pair_lists(
        ss.x, ss.y, ss.z, ss.h, keys, box, nbr, skin, slot_cap=2,
        interpret=True,
    )
    assert int(lists.overflow) == 1
