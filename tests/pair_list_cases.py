"""The persistent-list engine against the streaming engine on ONE case
(INTERPRET mode): the fixtures and the tests that tests/test_pair_lists.py
(``noh``) and tests/test_pair_lists_sedov.py (``sedov``) each run under
their own ``CASE``. Not collected itself; one file held both cases until
PR 41 (one worker's 668 s under ``--dist loadfile``).

The list-walk path must reproduce the streaming engine's pair SET exactly
(the compaction only removes lanes outside the skin-inflated group bbox,
a superset of every 2h_i sphere), so results match up to f32 summation
order. Drift robustness: after particles move by less than skin/2 the
STALE lists must still produce results matching a fresh streaming pass
on the moved positions — the Verlet-skin contract the steady steps rely
on (cstone rebuilds per step, find_neighbors.cuh; lists amortize that)."""

import numpy as np
import pytest
import jax.numpy as jnp

from sphexa_tpu.init import init_sedov, init_noh
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.simulation import make_propagator_config
from sphexa_tpu.sph import pallas_pairs as pp
from sphexa_tpu.sph.pair_lists import (
    build_pair_lists,
    estimate_list_caps,
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
CASES = {"noh": (init_noh, 16), "sedov": (init_sedov, 30)}


#: conftest.pytest_generate_tests: the importing module's ``CASE`` is the
#: one parameter of this fixture (ids ``[noh]`` / ``[sedov]``, as when one
#: module held both)
CASE_FIXTURE = "case"


@pytest.fixture(scope="module")
def case(request):
    return _setup(*CASES[request.param])


def _build(case):
    """The case's lists at the estimated caps: (lists, skin, slot cap)."""
    ss, keys, box, const, nbr = case
    skin = 0.2 * float(jnp.max(ss.h))
    scap, rows = estimate_list_caps(ss.x, ss.y, ss.z, ss.h, keys, box, nbr,
                                    skin)
    lists = build_pair_lists(
        ss.x, ss.y, ss.z, ss.h, keys, box, nbr, skin, scap, rows,
        interpret=True,
    )
    return lists, skin, scap


@pytest.fixture(scope="module")
def built(case):
    return _build(case)


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


@pytest.fixture(scope="module")
def streamed_std(case):
    """(rho, nc, c11..c33) of the streamed engine: what the walk's cheap
    ops are held to."""
    ss, keys, box, const, nbr = case
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    rho, nc, _ = pp.pallas_density(x, y, z, h, m, keys, box, const, nbr,
                                   interpret=True)
    cs, _ = pp.pallas_iad(x, y, z, h, m / rho, keys, box, const, nbr,
                          interpret=True)
    return rho, nc, cs


def test_density_lists_match_streaming(case, built, streamed_std):
    ss, keys, box, const, nbr = case
    lists, _, _ = built
    rho0, nc0, _ = streamed_std
    rho1, nc1, _ = pp.pallas_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, None, box, const, nbr,
        interpret=True, lists=lists,
    )
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc0))
    np.testing.assert_allclose(np.asarray(rho1), np.asarray(rho0),
                               rtol=2e-6)


def _tensor_scale(cs):
    """Off-diagonal components of C are ~0 on near-uniform lattices (pure
    cancellation noise), so an atol scales with the TENSOR magnitude."""
    return max(float(np.abs(np.asarray(b)).max()) for b in cs)


def _iad_on(case, lists, rho):
    ss, keys, box, const, nbr = case
    return pp.pallas_iad(ss.x, ss.y, ss.z, ss.h, ss.m / rho, None, box,
                         const, nbr, interpret=True, lists=lists)[0]


@pytest.fixture(scope="module")
def iad_lists(case, built, streamed_std):
    return _iad_on(case, built[0], streamed_std[0])


IAD_C = ["c11", "c12", "c13", "c22", "c23", "c33"]


@pytest.mark.parametrize("comp", range(6), ids=IAD_C)
def test_iad_lists_match_streaming(streamed_std, iad_lists, comp):
    """Each component of ``pallas_iad``'s C on the walk against the
    streamed engine."""
    cs = streamed_std[2]
    np.testing.assert_allclose(np.asarray(iad_lists[comp]),
                               np.asarray(cs[comp]), rtol=2e-5,
                               atol=1e-6 * _tensor_scale(cs))


# -- the walk's flush, on groups the cases' own lists do not hold: a group
# that keeps no chunk, one whose kept lanes end exactly on a staged chunk
# (``tail`` 0 after an emit) and one a lane past it. The marks of three
# groups are edited on the host and the staging bookkeeping redone from them

EDGES = {"no-chunk": 1, "ends-on-a-chunk": 2, "one-lane-past": 3}


def _spare_lanes(case, lists, bits, cands, runs, g):
    """Marked lanes of group ``g`` that no target of the group reaches
    (inside the skin-inflated bbox, outside every 2 h_i sphere): taking
    one off the list drops no pair. ``(slot_cap, 128)`` bool."""
    ss, _, _, _, nbr = case
    G, n = nbr.group, ss.x.shape[0]
    ii = np.minimum(np.arange(g * G, (g + 1) * G), n - 1)
    rg = lists.ranges
    d2 = np.zeros((G,) + bits[g].shape, np.float64)
    for a, sh in zip((ss.x, ss.y, ss.z),
                     (rg.shift_x, rg.shift_y, rg.shift_z)):
        a = np.asarray(a, np.float64)
        j = a[np.minimum(cands[g], n - 1)] + np.asarray(sh)[g][runs[g]][
            :, None]
        d2 += (a[ii][:, None, None] - j[None]) ** 2
    h2 = 4.0 * np.asarray(ss.h, np.float64)[ii] ** 2
    reached = (d2 < 1.0001 * h2[:, None, None]).any(0)
    return (bits[g] > 0) & ~reached


@pytest.fixture(scope="module")
def edge_lists(case, built):
    """The case's lists with group ``EDGES["no-chunk"]`` keeping
    nothing, the next group's spare lanes thinned until its kept lanes are
    a whole number of staged chunks, the third's until one lane more. Every
    kept chunk keeps a lane, so runs and segments stand."""
    ss, keys, box, const, nbr = case
    lists, skin, _ = built
    bits, cands, runs = _mark_bits(ss, nbr, lists, skin)
    np.testing.assert_array_equal(bits.sum(-1), np.asarray(lists.cnt))
    for g, past in ((EDGES["ends-on-a-chunk"], 0),
                    (EDGES["one-lane-past"], 1)):
        spare = _spare_lanes(case, lists, bits, cands, runs, g)
        drop = (bits[g].sum() - past) % 128
        for k, l in zip(*np.nonzero(spare)):
            if drop and bits[g, k].sum() > 1:
                bits[g, k, l] = 0
                drop -= 1
        assert drop == 0, "too few spare lanes"
    none = EDGES["no-chunk"]
    bits[none] = 0
    rot, cnt, fill = _dense_table(bits)
    gidx, seg = np.array(lists.gidx), np.asarray(lists.seg)
    for g in EDGES.values():
        kept = int((cnt[g] > 0).sum())
        gidx[8 * seg[g]:8 * seg[g] + kept] = rot[g, :kept]
    ranges = lists.ranges._replace(
        ncells=lists.ranges.ncells.at[none].set(0))
    return lists._replace(
        ranges=ranges, gidx=jnp.asarray(gidx), cnt=jnp.asarray(cnt),
        fill=jnp.asarray(fill),
        emit=jnp.asarray((fill + cnt >= 128).astype(np.int32)),
        tail=jnp.asarray(cnt.sum(1) % 128))


@pytest.fixture(scope="module")
def edge_outputs(case, built, edge_lists, streamed_std):
    """density and iad on the walk over the edited and the case's own
    lists."""
    ss, keys, box, const, nbr = case
    out = []
    for ls in (edge_lists, built[0]):
        rho, nc, _ = pp.pallas_density(
            ss.x, ss.y, ss.z, ss.h, ss.m, None, box, const, nbr,
            interpret=True, lists=ls)
        out.append((rho, nc) + tuple(_iad_on(case, ls, streamed_std[0])))
    return [[np.asarray(a) for a in o] for o in out]


@pytest.mark.parametrize("edge", list(EDGES))
def test_walk_flush_on_edge_groups(case, edge_lists, edge_outputs,
                                   streamed_std, edge):
    ss, keys, box, const, nbr = case
    g, G = EDGES[edge], nbr.group
    rows = slice(g * G, (g + 1) * G)
    cnt, emit = np.asarray(edge_lists.cnt)[g], np.asarray(edge_lists.emit)[g]
    kept = int((cnt > 0).sum())
    tail = int(edge_lists.tail[g])
    got, own = edge_outputs
    rho0, nc0, cs0 = streamed_std
    if edge == "no-chunk":
        assert kept == 0 and tail == 0
        assert int(edge_lists.ranges.ncells[g]) == 0
        # nothing walked, nothing flushed: the self term alone
        np.testing.assert_array_equal(got[1][rows], 0)
        np.testing.assert_allclose(
            got[0][rows], np.asarray(const.K * ss.m / ss.h**3)[rows],
            rtol=1e-6)
    else:
        assert kept > 0 and cnt.sum() > 128
        if edge == "ends-on-a-chunk":
            # the last kept chunk completes a staged chunk: no flush
            assert tail == 0 and emit[kept - 1] == 1
        else:
            assert tail == 1
        np.testing.assert_array_equal(got[1][rows], np.asarray(nc0)[rows])
        np.testing.assert_allclose(got[0][rows], np.asarray(rho0)[rows],
                                   rtol=2e-6)
        for a, b in zip(got[2:], cs0):
            np.testing.assert_allclose(a[rows], np.asarray(b)[rows],
                                       rtol=2e-5,
                                       atol=1e-6 * _tensor_scale(cs0))
    # the groups whose marks were not edited: the same rows in the same
    # order, bit for bit
    rest = np.ones(got[0].shape[0], bool)
    for e in EDGES.values():
        rest[e * G:(e + 1) * G] = False
    for a, b in zip(got, own):
        np.testing.assert_array_equal(a[rest], b[rest])


def test_momentum_std_lists_match_streaming(case, built, streamed_std):
    ss, keys, box, const, nbr = case
    lists, _, _ = built
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    rho, _, cs = streamed_std
    from sphexa_tpu.sph.hydro_std import compute_eos_std

    p, c = compute_eos_std(ss.temp, rho, const)
    args = (x, y, z, ss.vx, ss.vy, ss.vz, h, m, rho, p, c, *cs)
    ax0, ay0, az0, du0, dt0, _ = pp.pallas_momentum_energy_std(
        *args, keys, box, const, nbr, interpret=True
    )
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
    cs0, dv0, _ = pp.pallas_iad_divv_curlv(
        x, y, z, ss.vx, ss.vy, ss.vz, h, kx, xm, keys, box, const,
        nbr, interpret=True,
    )
    cs1, dv1, _ = pp.pallas_iad_divv_curlv(
        x, y, z, ss.vx, ss.vy, ss.vz, h, kx, xm, None, box, const,
        nbr, interpret=True, lists=lists,
    )
    sc = float(jnp.max(jnp.abs(dv0[0])))
    np.testing.assert_allclose(np.asarray(dv1[0]), np.asarray(dv0[0]),
                               rtol=1e-4, atol=1e-5 * sc)
    # the fused op's C is pallas_iad's: the same moments in the same
    # order, the same inverse (to the bit on one engine)
    csc = _tensor_scale(cs)
    for a0, a1, b in zip(cs0, cs1, cs):
        np.testing.assert_array_equal(np.asarray(a0), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a1), np.asarray(b),
                                   rtol=2e-5, atol=1e-6 * csc)
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


@pytest.fixture(scope="module")
def ve_reference(case):
    """The XLA reference of the fused op's two halves (hydro_std.compute_iad
    + hydro_ve.compute_iad_divv_curlv) under a smooth velocity field: the
    initial conditions' own give divv = 0 (Sedov) by construction."""
    from sphexa_tpu.neighbors.cell_list import find_neighbors
    from sphexa_tpu.sph import hydro_std, hydro_ve

    ss, keys, box, const, nbr = case
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    tau = 2.0 * np.pi / box.lengths
    px, py, pz = tau[0] * x, tau[1] * y, tau[2] * z
    v = (jnp.sin(px) + 0.5 * jnp.sin(py),
         0.7 * jnp.cos(pz) + 0.3 * jnp.sin(py),
         jnp.sin(px + pz))
    # searched under the portable backend's own config: the same lists in
    # the same order as under the engine's (cell cap 1536 for the DMA
    # runs), whose 17 x candidates made this fixture the Sedov file's
    # longest item (108 CPU-seconds of search against 10)
    xnbr = make_propagator_config(ss, box, const, block=4096,
                                  backend="xla").nbr
    nidx, nmask, _, occ = find_neighbors(x, y, z, h, keys, box, xnbr)
    assert int(occ) <= xnbr.cap
    xm = hydro_ve.compute_xmass(x, y, z, h, m, nidx, nmask, box, const, 4096)
    kx, _ = hydro_ve.compute_ve_def_gradh(x, y, z, h, m, xm, nidx, nmask,
                                          box, const, 4096)
    cs = hydro_std.compute_iad(x, y, z, h, xm / kx, nidx, nmask, box, const,
                               4096)
    dv = hydro_ve.compute_iad_divv_curlv(
        x, y, z, *v, h, kx, xm, *cs, nidx, nmask, box, const, 4096,
        with_gradv=True,
    )
    return v, kx, xm, cs, dv


@pytest.mark.parametrize("with_gradv", [False, True], ids=["plain", "gradv"])
@pytest.mark.parametrize("engine", ["streamed", "walk"])
def test_fused_iad_divv_matches_xla(case, built, ve_reference, engine,
                                    with_gradv):
    """c11..c33, divv, curlv and the six gradv of the ONE-pass op against
    the two XLA reference ops, on each engine the op is built on
    (pallas_pairs.PAIR_OP_ENGINE: the walk on lists)."""
    ss, keys, box, const, nbr = case
    lists, _, _ = built
    v, kx, xm, cs0, dv0 = ve_reference
    assert pp.PAIR_OP_ENGINE["divv-curlv"][0] == "walk"
    kw = {} if engine == "streamed" else dict(lists=lists)
    cs1, dv1, _ = pp.pallas_iad_divv_curlv(
        ss.x, ss.y, ss.z, *v, ss.h, kx, xm,
        keys if engine == "streamed" else None, box, const, nbr,
        with_gradv=with_gradv, interpret=True, **kw,
    )
    assert len(dv1) == (8 if with_gradv else 2)
    scale = float(jnp.max(jnp.abs(cs0[0])))
    for a, b in zip(cs1, cs0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5 * scale)
    assert float(jnp.max(jnp.abs(dv0[0]))) > 1.0  # a field with a gradient
    for a, b in zip(dv1, dv0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=5e-4)


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


def test_slot_cap_overflow_sentinel(case):
    ss, keys, box, const, nbr = case
    skin = 0.2 * float(jnp.max(ss.h))
    lists = build_pair_lists(
        ss.x, ss.y, ss.z, ss.h, keys, box, nbr, skin, slot_cap=2,
        slots_cap=1 << 16, interpret=True,
    )
    assert int(lists.overflow) == 1


# -- the flat lane table (PR 28): one row per KEPT chunk, each group's rows
# contiguous from an 8-row tile boundary, sized by the sum over groups ------


def _mark_bits(ss, nbr, lists, skin):
    """``(bits, cand, run)``: ``(groups, slot_cap, 128)`` 0/1, the build's
    mark test redone in numpy over the list's (pruned) runs; each lane's
    sorted-array index; ``(groups, slot_cap)`` the run a slot came from."""
    f32 = np.float32
    x, y, z, h = (np.asarray(a, f32) for a in (ss.x, ss.y, ss.z, ss.h))
    n, G = x.shape[0], nbr.group
    rg = lists.ranges
    starts, lens, ncells = (np.asarray(a) for a in
                            (rg.starts, rg.lens, rg.ncells))
    shifts = [np.asarray(a, f32) for a in
              (rg.shift_x, rg.shift_y, rg.shift_z)]
    ng, scap = np.asarray(lists.cnt).shape
    bits = np.zeros((ng, scap, 128), np.int32)
    cands = np.zeros((ng, scap, 128), np.int32)
    runs = np.zeros((ng, scap), np.int32)
    pad = lambda a: np.concatenate([a, np.zeros(128 + nbr.dma_cap, f32)])
    xp, yp, zp = pad(x), pad(y), pad(z)
    lane = np.arange(128)
    for g in range(ng):
        ii = np.minimum(np.arange(g * G, (g + 1) * G), n - 1)
        r = f32(2.0) * h[ii].max() + f32(skin)
        lo = [a[ii].min() - r for a in (x, y, z)]
        hi = [a[ii].max() + r for a in (x, y, z)]
        slot = 0
        for w in range(ncells[g]):
            s, ln = starts[g, w], lens[g, w]
            row0 = s // 128
            for t in range((s - row0 * 128 + ln + 127) // 128):
                cand = (row0 + t) * 128 + lane
                m = (cand >= s) & (cand < s + ln)
                for jp, sh, a, b in zip((xp, yp, zp), shifts, lo, hi):
                    j = jp[cand] + sh[g, w]
                    m &= (j >= a) & (j <= b)
                if slot < scap:
                    bits[g, slot], cands[g, slot], runs[g, slot] = m, cand, w
                slot += 1
    return bits, cands, runs


def _dense_table(bits):
    """The dense ``(groups, slot_cap, 128)`` table the flat one replaced,
    from the mark ``bits``: the old post-passes verbatim (cnt, fill,
    cumsum, dst, 128-wide sort)."""
    lane = np.arange(128)
    cnt = bits.sum(-1)
    csum = np.cumsum(cnt, axis=1)
    fill = (csum - cnt) % 128
    lanes = np.broadcast_to(lane, bits.shape)
    rank1 = np.cumsum(bits, axis=2) - bits
    dst = np.where(bits > 0, fill[:, :, None] + rank1,
                   fill[:, :, None] + cnt[:, :, None] + lanes - rank1) % 128
    return np.argsort(dst, axis=2, kind="stable").astype(np.int32), cnt, fill


def _assert_flat_is_dense(ss, nbr, lists, skin):
    rot, cnt, fill = _dense_table(_mark_bits(ss, nbr, lists, skin)[0])
    np.testing.assert_array_equal(np.asarray(lists.cnt), cnt)
    np.testing.assert_array_equal(np.asarray(lists.fill), fill)
    gidx, seg = np.asarray(lists.gidx), np.asarray(lists.seg)
    kept = (cnt > 0).sum(1)
    tiles = (kept + 7) // 8
    # segments: whole tiles, contiguous, in group order, none shared
    np.testing.assert_array_equal(seg, np.cumsum(tiles) - tiles)
    assert int(lists.slots_live) == 8 * tiles.sum() <= lists.slots_cap
    assert gidx.shape == (lists.slots_cap + -(-lists.slot_cap // 8) * 8, 128)
    for g in range(len(kept)):
        # kept chunks are compacted to the front of the dense arrays, so
        # row k of the group's segment is dense slot k, lane for lane
        assert (cnt[g, :kept[g]] > 0).all()
        np.testing.assert_array_equal(
            gidx[8 * seg[g]:8 * seg[g] + kept[g]], rot[g, :kept[g]],
            err_msg=f"group {g}")
    return kept


def test_flat_table_reproduces_the_dense_one_row_for_row(case, built):
    ss, keys, box, const, nbr = case
    lists, skin, _ = built
    kept = _assert_flat_is_dense(ss, nbr, lists, skin)
    # what the layout is for: it stores the sum, not groups x slot_cap
    assert int(lists.slots_live) < kept.shape[0] * lists.slot_cap


def test_slots_cap_overflow_sentinel(case):
    ss, keys, box, const, nbr = case
    skin = 0.2 * float(jnp.max(ss.h))
    scap, _ = estimate_list_caps(ss.x, ss.y, ss.z, ss.h, keys, box, nbr,
                                 skin)
    lists = build_pair_lists(
        ss.x, ss.y, ss.z, ss.h, keys, box, nbr, skin, scap, slots_cap=8,
        interpret=True,
    )
    # the budget is taken up to one post-pass tile; sedov 30^3 needs more
    need = int(lists.slots_live)
    assert int(lists.overflow) == int(need > lists.slots_cap)
    assert int(lists.slot_need) <= scap


def _prune_reference(starts, lens, shifts, ncells, cnt, run_rows):
    """``_prune_empty_chunks`` in plain loops: stretches of kept chunks
    within one candidate run, cut at every ``run_rows``-th chunk, with
    exact particle bounds."""
    ng, scap = cnt.shape
    out = {k: np.zeros((ng, scap), starts.dtype if k in "sl" else np.float32)
           for k in ("s", "l", "x", "y", "z")}
    heads = np.zeros(ng, np.int32)
    perm = np.zeros((ng, scap), np.int32)
    for g in range(ng):
        slot, runs, kept_slots = 0, [], []
        for w in range(starts.shape[1]):
            s, ln = int(starts[g, w]), int(lens[g, w])
            if ln <= 0:
                continue
            row0, cur = s // 128, None
            for c in range((s % 128 + ln + 127) // 128):
                row = row0 + c
                if slot < scap and cnt[g, slot] > 0:
                    kept_slots.append(slot)
                    hi = min(s + ln, (row + 1) * 128)
                    if cur is None or cur[3] == run_rows:
                        cur = [max(s, row * 128), hi, w, 1]
                        runs.append(cur)
                    else:
                        cur[1], cur[3] = hi, cur[3] + 1
                else:
                    cur = None
                slot += 1
        heads[g] = len(runs)
        for k, (lo, hi, w, _) in enumerate(runs):
            out["s"][g, k], out["l"][g, k] = lo, hi - lo
            for key, sh in zip("xyz", shifts):
                out[key][g, k] = sh[g, w]
        rest = [s for s in range(scap) if s not in set(kept_slots)]
        perm[g] = kept_slots + rest
    return out, heads, perm


#: run tiles the prune is held to: 13 = the un-cut runs' own width at these
#: sizes (no stretch of kept chunks is longer), the shipped tile, and two
#: that cut most stretches
RUN_ROWS = [13, pp.LIST_RUN_ROWS, 2, 1]


@pytest.mark.parametrize("run_rows", RUN_ROWS)
@pytest.mark.parametrize("thin", [False, True], ids=["marked", "thinned"])
def test_prune_matches_a_plain_loop(case, built, thin, run_rows):
    """The prune's slot -> run lookups (masked sums over the runs since
    PR 28, gathers before) and its cut of a stretch into tiles of
    ``run_rows`` chunks against loops over runs and chunks; ``thinned``
    also drops every third kept chunk, so stretches break inside runs."""
    from sphexa_tpu.sph.pair_lists import _prune_empty_chunks, _run_chunks

    ss, keys, box, const, nbr = case
    _, skin, scap = built
    ranges = pp.group_cell_ranges(ss.x, ss.y, ss.z, ss.h, keys, box, nbr,
                                  radius_pad=skin)
    starts, lens = np.asarray(ranges.starts), np.asarray(ranges.lens)
    nch = np.where(lens > 0, (starts % 128 + lens + 127) // 128, 0).sum(1)
    rng = np.random.default_rng(7)
    cnt = rng.integers(0, 3, (starts.shape[0], scap)).astype(np.int32)
    cnt *= (np.arange(scap)[None, :] < nch[:, None])
    if thin:
        cnt[:, ::3] = 0
    new, packed = _prune_empty_chunks(ranges, jnp.asarray(cnt), scap,
                                      run_rows)
    shifts = [np.asarray(a) for a in
              (ranges.shift_x, ranges.shift_y, ranges.shift_z)]
    ref, heads, ref_perm = _prune_reference(
        starts, lens, shifts, np.asarray(ranges.ncells), cnt, run_rows)
    assert _run_chunks(new.starts, new.lens).max() <= run_rows
    np.testing.assert_array_equal(np.asarray(new.ncells), heads)
    np.testing.assert_array_equal(np.asarray(new.starts), ref["s"])
    np.testing.assert_array_equal(np.asarray(new.lens), ref["l"])
    for got, key in zip((new.shift_x, new.shift_y, new.shift_z), "xyz"):
        np.testing.assert_array_equal(np.asarray(got), ref[key])
    # the kept slots' counts, compacted to the front in their order
    kept_first = np.take_along_axis(cnt, ref_perm, axis=1)
    live = (kept_first > 0).sum(1)
    assert (np.diff((kept_first > 0).astype(int), axis=1) <= 0).all()
    assert live.max() > 0
    np.testing.assert_array_equal(np.asarray(packed), kept_first)
