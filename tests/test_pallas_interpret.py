"""Pallas engine equivalence vs the XLA gather path in INTERPRET mode.

Runs on the plain CPU test mesh on every suite run, so the engine's
cell-range/DMA-offset/masking logic is exercised without TPU hardware
(the device tier, tests/test_pallas_tpu.py, stays the Mosaic-lowering
check). Mirrors the reference's CPU/GPU equivalence strategy
(domain/test/unit_cuda/) with ``interpret=True`` standing in for the GPU.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from sphexa_tpu.init import init_sedov, init_noh
from sphexa_tpu.neighbors.cell_list import find_neighbors
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.simulation import make_propagator_config
from sphexa_tpu.sph import hydro_std
from sphexa_tpu.sph import pallas_pairs as pp


def _setup(init, side):
    state, box, const = init(side)
    cfg = make_propagator_config(state, box, const, block=4096, backend="pallas")
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    return ss, keys, box, const, cfg.nbr


# sedov 14^3 is periodic+tiny -> exercises the per-pair fold path;
# noh has open boundaries -> exercises the per-cell shift path + window
# sliding at the grid edge
CASES = [(init_sedov, 14), (init_noh, 12)]


@pytest.fixture(scope="module", params=CASES, ids=["sedov", "noh"])
def case(request):
    init, side = request.param
    return _setup(init, side)


def test_density_matches_xla_interpret(case):
    ss, keys, box, const, nbr = case
    nidx, nmask, nc0, _ = find_neighbors(ss.x, ss.y, ss.z, ss.h, keys, box, nbr)
    rho0 = hydro_std.compute_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, nidx, nmask, box, const, 4096
    )
    rho1, nc1, occ = pp.pallas_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, keys, box, const, nbr, interpret=True
    )
    assert int(occ) <= nbr.cap
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc0))
    np.testing.assert_allclose(np.asarray(rho1), np.asarray(rho0), rtol=1e-5)


@pytest.mark.slow
def test_pipeline_matches_xla_interpret(case):
    ss, keys, box, const, nbr = case
    nidx, nmask, _, _ = find_neighbors(ss.x, ss.y, ss.z, ss.h, keys, box, nbr)
    rho = hydro_std.compute_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, nidx, nmask, box, const, 4096
    )
    p, c = hydro_std.compute_eos_std(ss.temp, rho, const)
    cs0 = hydro_std.compute_iad(
        ss.x, ss.y, ss.z, ss.h, ss.m / rho, nidx, nmask, box, const, 4096
    )
    cs1, _ = pp.pallas_iad(
        ss.x, ss.y, ss.z, ss.h, ss.m / rho, keys, box, const, nbr,
        interpret=True,
    )
    # IAD diagonals match relatively; off-diagonals are ~0 on the lattice
    # (catastrophic cancellation), so compare on the diagonal scale — same
    # criterion as the TPU device tier
    scale = float(jnp.max(jnp.abs(cs0[0])))
    for a, b in zip(cs1, cs0):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5 * scale
        )

    out0 = hydro_std.compute_momentum_energy_std(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, rho, p, c,
        *cs0, nidx, nmask, box, const, 4096,
    )
    out1 = pp.pallas_momentum_energy_std(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, rho, p, c,
        *cs0, keys, box, const, nbr, interpret=True,
    )
    names = ["ax", "ay", "az", "du"]
    for name, a, b in zip(names, out1[:4], out0[:4]):
        s = float(jnp.max(jnp.abs(np.asarray(b)))) + 1e-12
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-6 * s,
            err_msg=name,
        )
    assert float(out1[4]) == pytest.approx(float(out0[4]), rel=1e-5)


@pytest.mark.parametrize("av_clean", [False, True], ids=["plain", "avclean"])
@pytest.mark.slow
def test_ve_pipeline_matches_xla_interpret(case, av_clean):
    from sphexa_tpu.sph import hydro_ve

    ss, keys, box, const, nbr = case
    nidx, nmask, nc, _ = find_neighbors(ss.x, ss.y, ss.z, ss.h, keys, box, nbr)
    args = (ss.x, ss.y, ss.z, ss.h, ss.m)

    xm0 = hydro_ve.compute_xmass(*args, nidx, nmask, box, const, 4096)
    xm1, nc1, _ = pp.pallas_xmass(*args, keys, box, const, nbr, interpret=True)
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc))
    np.testing.assert_allclose(np.asarray(xm1), np.asarray(xm0), rtol=1e-5)

    kx0, gradh0 = hydro_ve.compute_ve_def_gradh(
        *args, xm0, nidx, nmask, box, const, 4096
    )
    (kx1, gradh1), _ = pp.pallas_ve_def_gradh(
        *args, xm0, keys, box, const, nbr, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kx1), np.asarray(kx0), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gradh1), np.asarray(gradh0), rtol=5e-4, atol=1e-5
    )

    prho, c, rho, p = hydro_ve.compute_eos_ve(
        ss.temp, ss.m, kx0, xm0, gradh0, const
    )
    cs = hydro_std.compute_iad(
        ss.x, ss.y, ss.z, ss.h, xm0 / kx0, nidx, nmask, box, const, 4096
    )

    dv0 = hydro_ve.compute_iad_divv_curlv(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, kx0, xm0, *cs,
        nidx, nmask, box, const, 4096, with_gradv=av_clean,
    )
    cs1, dv1, _ = pp.pallas_iad_divv_curlv(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, kx0, xm0,
        keys, box, const, nbr, with_gradv=av_clean, interpret=True,
    )
    # the fused op's IAD, on the diagonal scale like the std pipeline's
    scale = float(jnp.max(jnp.abs(cs[0])))
    for a, b in zip(cs1, cs):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5 * scale
        )
    # divv/curlv are ~0 on the initial lattice (cancellation): absolute
    # tolerance on the kernel-sum scale
    for a, b in zip(dv1, dv0):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-4
        )
    divv = dv0[0]
    gradv = tuple(dv0[2:]) if av_clean else None

    alpha0 = hydro_ve.compute_av_switches(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, c, kx0, xm0, divv,
        ss.alpha, *cs, nidx, nmask, box, ss.min_dt, const, 4096,
    )
    alpha1, _ = pp.pallas_av_switches(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, c, kx0, xm0, divv,
        ss.alpha, *cs, keys, box, ss.min_dt, const, nbr, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(alpha1), np.asarray(alpha0), rtol=1e-4, atol=1e-6
    )

    me0 = hydro_ve.compute_momentum_energy_ve(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, prho, c,
        kx0, xm0, alpha0, *cs, nidx, nmask, nc, box, const, 4096,
        gradv=gradv,
    )
    *me1, _ = pp.pallas_momentum_energy_ve(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, prho, c,
        kx0, xm0, alpha0, *cs, keys, box, const, nbr, nc=nc,
        gradv=gradv, interpret=True,
    )
    for name, a, b in zip(["ax", "ay", "az", "du"], me1[:4], me0[:4]):
        s = float(np.max(np.abs(np.asarray(b)))) + 1e-12
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5 * s,
            err_msg=name,
        )
    assert float(me1[4]) == pytest.approx(float(me0[4]), rel=1e-4)


def test_gravity_compact_kernel_interpret():
    """Bitmask+popcount-rank compaction kernel (gravity/pallas_compact.py)
    vs a numpy reference: candidate-order lists, true (unclipped) counts,
    cap truncation, 128-lane staging wrap, and tail padding — the
    interpret-mode smoke that rides the tier-1 CPU gate."""
    from sphexa_tpu.gravity import pallas_compact as pc

    rng = np.random.default_rng(7)
    # (B, C, cap0, cap1): non-multiple-of-128 caps/widths exercise the
    # pad/trim paths; cap < count exercises truncation + the unclipped
    # count contract; C < 128 exercises the single-chunk tail
    for B, C, cap0, cap1 in ((4, 1000, 192, 64), (1, 90, 8, 8),
                             (3, 513, 256, 48)):
        cls = rng.integers(0, 3, size=(B, C))
        vals = rng.integers(0, 1 << 20, size=(B, C))
        packed = jnp.asarray((cls << pc.IDX_BITS) | vals, jnp.int32)
        l0, n0, l1, n1 = pc.compact_class_lists(
            packed, cap0, cap1, interpret=True
        )
        for b in range(B):
            for lst, cnt, cap, k in ((l0, n0, cap0, 0), (l1, n1, cap1, 1)):
                exp = vals[b][cls[b] == k]
                assert int(cnt[b]) == len(exp)
                kept = min(len(exp), cap)
                np.testing.assert_array_equal(
                    np.asarray(lst[b][:kept]), exp[:kept]
                )
                # slots beyond the count stay zeroed (masked by callers)
                assert np.all(np.asarray(lst[b][kept:]) == 0)


def test_gravity_p2p_pallas_matches_xla_interpret():
    """Streamed near-field P2P (gravity/traversal._pallas_p2p) vs the XLA
    gather formulation, both through compute_gravity."""
    import dataclasses

    from sphexa_tpu.gravity.traversal import (
        GravityConfig,
        compute_gravity,
        estimate_gravity_caps,
    )
    from sphexa_tpu.gravity.tree import build_gravity_tree
    from sphexa_tpu.init import init_evrard
    from sphexa_tpu.sfc.box import make_global_box

    state, box, const = init_evrard(16)
    box = make_global_box(state.x, state.y, state.z, box)
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    gtree, meta = build_gravity_tree(np.asarray(keys), bucket_size=64)
    cfg0 = estimate_gravity_caps(
        ss.x, ss.y, ss.z, ss.m, keys, box, gtree, meta,
        GravityConfig(theta=0.5, G=1.0),
    )
    out0 = compute_gravity(
        ss.x, ss.y, ss.z, ss.m, ss.h, keys, box, gtree, meta, cfg0
    )
    cfg1 = dataclasses.replace(cfg0, use_pallas=True)
    out1 = compute_gravity(
        ss.x, ss.y, ss.z, ss.m, ss.h, keys, box, gtree, meta, cfg1
    )
    for name, a, b in zip(("ax", "ay", "az", "egrav"), out1[:4], out0[:4]):
        sa, sb = np.asarray(a), np.asarray(b)
        scale = np.max(np.abs(sb)) + 1e-12
        np.testing.assert_allclose(sa, sb, atol=1e-6 * scale, rtol=1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("C", [1000, 1024])
@pytest.mark.parametrize("live", [0, 1, 127, 128, 129, None])
def test_gravity_compact_kernel_live_bound_is_the_full_scan(C, live):
    """``compact_class_lists(live=...)``: the chunk walk bounded by each
    row's live count gives the full scan's lists and UNCLIPPED counts bit
    for bit, with overflow past a cap, for a width that is no multiple of
    128 too; and it reads nothing past the last live chunk (garbage
    there, which the full scan would compact, changes nothing)."""
    from sphexa_tpu.gravity import pallas_compact as pc

    live = C if live is None else live
    rng = np.random.default_rng(100 * C + live)
    B, cap0, cap1 = 3, 192, 64  # live = C overflows both (~C/3 a class)
    cls = rng.integers(0, 3, size=(B, C))
    cls[:, live:] = 2
    vals = rng.integers(0, 1 << 20, size=(B, C))
    packed = (cls << pc.IDX_BITS) | vals
    full = pc.compact_class_lists(jnp.asarray(packed, jnp.int32), cap0, cap1,
                                  interpret=True)
    past = -(-live // 128) * 128
    packed[:, past:] = 7  # class 0, value 7: never read
    bounded = pc.compact_class_lists(
        jnp.asarray(packed, jnp.int32), cap0, cap1, interpret=True,
        live=jnp.full((B,), live, jnp.int32))
    for name, a, b in zip(("list0", "n0", "list1", "n1"), full, bounded):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    if live == C:
        assert int(full[1].min()) > cap0 and int(full[3].min()) > cap1
    for b in range(B):
        assert int(full[1][b]) == int((cls[b] == 0).sum())
        assert int(full[3][b]) == int((cls[b] == 1).sum())


def _compact_reference(cls, vals, cap0, cap1):
    """What ``compact_class_lists`` returns, in numpy: each class's values
    in candidate order cut at its cap (zeros past the kept), the counts
    unclipped."""
    out = []
    for k, cap in ((0, cap0), (1, cap1)):
        lst = np.zeros((len(cls), cap), np.int32)
        for b in range(len(cls)):
            kept = vals[b][cls[b] == k][:cap]
            lst[b, :len(kept)] = kept
        out += [lst, (cls == k).sum(axis=1).astype(np.int32)]
    return out


def _compact_case(name, rng):
    """(cls, cap0, cap1, live) of one case of the test below: three rows
    of classes 0 / 1 / 2 (2 = dropped)."""
    C, cap0, cap1, live = 1024, 192, 64, None
    if name == "all-dead":
        cls = np.full((3, C), 2)
    elif name == "all-live":  # every chunk, both classes: overflows both
        cls = rng.integers(0, 2, size=(3, C))
    elif name == "one-lane-in-the-last-chunk":
        cls = np.full((3, C), 2)
        cls[0, C - 1], cls[1, C - 128], cls[2, C - 77] = 0, 1, 0
    elif name == "no-class-1":  # the pre-pass: 0 or 2, bands of live chunks
        cls = np.full((3, C), 2)
        cls[:, 128:300] = 0
        cls[:, 640:700] = np.where(rng.random((3, 60)) < 0.3, 0, 2)
    elif name == "overflow":
        cls = rng.integers(0, 3, size=(3, C))
    elif name == "ragged-width":  # C no multiple of 128, sparse classes
        C = 1000
        cls = np.where(rng.random((3, C)) < 0.02,
                       rng.integers(0, 2, size=(3, C)), 2)
    else:  # "live-<n>": the walk bounded by the rows' live count
        live = C if name == "live-C" else int(name.split("-")[1])
        cls = rng.integers(0, 3, size=(3, C))
        cls[:, live:] = 2
    return cls, cap0, cap1, live


@pytest.mark.parametrize("case", [
    "all-dead", "all-live", "one-lane-in-the-last-chunk", "no-class-1",
    "overflow", "ragged-width", "live-0", "live-1", "live-127", "live-128",
    "live-129", "live-C"])
def test_gravity_compact_kernel_smem_counts_equal_the_reference(case):
    """The kernel reads each chunk's class counts from SMEM and skips a
    chunk whose two counts are zero: lists, UNCLIPPED counts and the
    truncation past a cap are the numpy reference's bit for bit, whatever
    the share of dead chunks; under ``live=`` with garbage past the last
    live chunk, whose counts (taken over the whole row) are then never
    read. ``live_chunks`` is the number of chunks the walk does not
    skip."""
    from sphexa_tpu.gravity import pallas_compact as pc

    rng = np.random.default_rng(sum(case.encode()))
    cls, cap0, cap1, live = _compact_case(case, rng)
    B, C = cls.shape
    vals = rng.integers(0, 1 << 20, size=(B, C))
    packed = (cls << pc.IDX_BITS) | vals
    chunk_any = np.pad(cls < 2, ((0, 0), (0, -C % 128))).reshape(
        B, -1, 128).any(axis=2)
    np.testing.assert_array_equal(
        np.asarray(pc.live_chunks(jnp.asarray(packed, jnp.int32))),
        chunk_any.sum(axis=1))
    kw = {}
    if live is not None:
        packed[:, -(-live // 128) * 128:] = 7  # class 0, value 7: unread
        kw = dict(live=jnp.full((B,), live, jnp.int32))
    got = pc.compact_class_lists(jnp.asarray(packed, jnp.int32), cap0, cap1,
                                 interpret=True, **kw)
    want = _compact_reference(cls, vals, cap0, cap1)
    for name, a, b in zip(("list0", "n0", "list1", "n1"), got, want):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)
    if case in ("all-live", "overflow", "live-C"):
        assert want[1].min() > cap0 and want[3].min() > cap1
    if case == "all-dead":
        assert not chunk_any.any() and not want[1].any()
    if case == "no-class-1":
        assert not want[3].any() and 0 < chunk_any.mean() < 0.5
