"""Pallas engine vs XLA path equivalence — runs only on real TPU hardware
(the Mosaic kernels don't lower on the CPU test mesh). The CPU suite
covers the XLA path; this file is the device-equivalence tier, mirroring
the reference's CPU/GPU equivalence tests (domain/test/unit_cuda/).

Run manually on TPU:  python -m pytest tests/test_pallas_tpu.py -q
"""

import numpy as np
import pytest

import jax

if jax.default_backend() != "tpu":  # pragma: no cover
    pytest.skip("pallas TPU kernels need real TPU hardware", allow_module_level=True)

import jax.numpy as jnp

from sphexa_tpu.init import init_sedov
from sphexa_tpu.neighbors.cell_list import find_neighbors
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.simulation import make_propagator_config
from sphexa_tpu.sph import hydro_std
from sphexa_tpu.sph.pallas_pairs import (
    group_cell_ranges,
    pallas_density,
    pallas_iad,
    pallas_momentum_energy_std,
)


@pytest.fixture(scope="module")
def case():
    state, box, const = init_sedov(20)
    cfg = make_propagator_config(state, box, const, block=4096, backend="pallas")
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    return ss, keys, box, const, cfg


def test_density_matches_xla(case):
    ss, keys, box, const, cfg = case
    nidx, nmask, nc0, _ = find_neighbors(ss.x, ss.y, ss.z, ss.h, keys, box, cfg.nbr)
    rho0 = hydro_std.compute_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, nidx, nmask, box, const, 4096
    )
    rho1, nc1, occ = pallas_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, keys, box, const, cfg.nbr
    )
    assert int(occ) <= cfg.nbr.cap
    np.testing.assert_allclose(np.asarray(rho1), np.asarray(rho0), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc0))


def test_full_pipeline_matches_xla(case):
    ss, keys, box, const, cfg = case
    nidx, nmask, _, _ = find_neighbors(ss.x, ss.y, ss.z, ss.h, keys, box, cfg.nbr)
    rho = hydro_std.compute_density(
        ss.x, ss.y, ss.z, ss.h, ss.m, nidx, nmask, box, const, 4096
    )
    p, c = hydro_std.compute_eos_std(ss.temp, rho, const)
    cs0 = hydro_std.compute_iad(
        ss.x, ss.y, ss.z, ss.h, ss.m / rho, nidx, nmask, box, const, 4096
    )
    me0 = hydro_std.compute_momentum_energy_std(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, rho, p, c,
        *cs0, nidx, nmask, box, const, 4096,
    )

    ranges = group_cell_ranges(ss.x, ss.y, ss.z, ss.h, keys, box, cfg.nbr)
    cs1, _ = pallas_iad(
        ss.x, ss.y, ss.z, ss.h, ss.m / rho, keys, box, const, cfg.nbr,
        ranges=ranges,
    )
    *me1, _ = pallas_momentum_energy_std(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, rho, p, c,
        *cs1, keys, box, const, cfg.nbr, ranges=ranges,
    )
    # IAD diagonal terms match relatively; off-diagonals are ~0 so compare
    # on the diagonal scale
    scale = float(jnp.max(jnp.abs(cs0[0])))
    for a, b in zip(cs0, cs1):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-5 * scale, rtol=1e-4
        )
    for a, b in zip(me0[:4], me1[:4]):
        s = float(jnp.max(jnp.abs(a))) + 1e-12
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-6 * s, rtol=1e-4
        )
    assert float(me1[4]) == pytest.approx(float(me0[4]), rel=1e-5)


@pytest.mark.parametrize("av_clean", [False, True], ids=["plain", "avclean"])
def test_ve_pipeline_matches_xla_tpu(case, av_clean):
    """Mosaic-lowering check for the five VE engine ops (the interpret tier
    covers the logic; this tier covers the TPU compile + execution),
    including the fused IAD + divv/curlv op's 15 accumulators and the
    avClean momentum kernel's nf_pad=32 packing."""
    from sphexa_tpu.sph import hydro_ve
    from sphexa_tpu.sph.pallas_pairs import (
        pallas_av_switches,
        pallas_iad_divv_curlv,
        pallas_momentum_energy_ve,
        pallas_ve_def_gradh,
        pallas_xmass,
    )

    ss, keys, box, const, cfg = case
    nbr = cfg.nbr
    nidx, nmask, nc, _ = find_neighbors(ss.x, ss.y, ss.z, ss.h, keys, box, nbr)
    args = (ss.x, ss.y, ss.z, ss.h, ss.m)

    xm0 = hydro_ve.compute_xmass(*args, nidx, nmask, box, const, 4096)
    xm1, nc1, _ = pallas_xmass(*args, keys, box, const, nbr)
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc))
    np.testing.assert_allclose(np.asarray(xm1), np.asarray(xm0), rtol=1e-5)

    kx0, gradh0 = hydro_ve.compute_ve_def_gradh(
        *args, xm0, nidx, nmask, box, const, 4096
    )
    (kx1, gradh1), _ = pallas_ve_def_gradh(*args, xm0, keys, box, const, nbr)
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
    cs1, dv1, _ = pallas_iad_divv_curlv(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, kx0, xm0,
        keys, box, const, nbr, with_gradv=av_clean,
    )
    scale = float(jnp.max(jnp.abs(cs[0])))
    for a, b in zip(cs1, cs):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5 * scale
        )
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
    alpha1, _ = pallas_av_switches(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, c, kx0, xm0, divv,
        ss.alpha, *cs, keys, box, ss.min_dt, const, nbr,
    )
    np.testing.assert_allclose(
        np.asarray(alpha1), np.asarray(alpha0), rtol=1e-4, atol=1e-6
    )

    me0 = hydro_ve.compute_momentum_energy_ve(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, prho, c,
        kx0, xm0, alpha0, *cs, nidx, nmask, nc, box, const, 4096,
        gradv=gradv,
    )
    *me1, _ = pallas_momentum_energy_ve(
        ss.x, ss.y, ss.z, ss.vx, ss.vy, ss.vz, ss.h, ss.m, prho, c,
        kx0, xm0, alpha0, *cs, keys, box, const, nbr, nc=nc, gradv=gradv,
    )
    for name, a, b in zip(["ax", "ay", "az", "du"], me1[:4], me0[:4]):
        s = float(np.max(np.abs(np.asarray(b)))) + 1e-12
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5 * s,
            err_msg=name,
        )
