"""The fused IAD + divv/curlv pair op on fields the initial lattices cannot
give (there divv ~ 0 by cancellation): a jittered lattice under a linear
velocity field v = A x.

The IAD gradient is exact for linear fields up to the variation of kx over
a neighbourhood, so divv -> tr A, curlv -> |rot v| and gradv -> the
symmetrized A. The fused op contracts C with the summed moments
(C sum_j r_j v_j) where the two-pass form sums (C r_j) v_j: identical in
exact arithmetic. Against a float64 numpy evaluation of the same sums the
fused f32 result may be no further off than the two-pass f32 result (the
XLA reference ops) by more than 1.5 x, also where the lattice is squeezed
8 x along one axis and the moment matrix is ill-conditioned.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from sphexa_tpu.init import init_sedov
from sphexa_tpu.init.utils import build_state, sphere_h_init
from sphexa_tpu.neighbors.cell_list import find_neighbors
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.sfc.box import Box
from sphexa_tpu.simulation import make_propagator_config
from sphexa_tpu.sph import hydro_std, hydro_ve
from sphexa_tpu.sph import pallas_pairs as pp
from sphexa_tpu.sph.kernels import kernel_poly_coeffs

SIDE = 14

FIELDS = {
    # simple shear dvx/dy: no divergence, |rot v| = the shear rate
    "shear": np.array([[0.0, 1.5, 0.0], [0.0, 0.0, 0.0], [0.0, 0.0, 0.0]]),
    "compression": np.diag([-1.0, -2.0, -0.5]),
    # v = omega x r, omega = (0.3, -0.5, 1.0)
    "rotation": np.array([[0.0, -1.0, -0.5], [1.0, 0.0, -0.3],
                          [0.5, 0.3, 0.0]]),
}


def _lattice(squeeze: float):
    """Jittered SIDE^3 lattice in an open box, squeezed along z; sorted,
    with the VE volume elements of the f32 pipeline."""
    state, _, const = init_sedov(SIDE)
    rng = np.random.default_rng(11)
    dx = 1.0 / SIDE
    x, y, z = (
        np.asarray(a) + rng.uniform(-0.2 * dx, 0.2 * dx, state.n)
        for a in (state.x, state.y, state.z)
    )
    z = z / squeeze
    box = Box.create(-0.5, 0.5, -0.5, 0.5, -0.5 / squeeze, 0.5 / squeeze)
    # ng0 40 on the squeezed lattice: 2h ~ 1.06 dx, so the x / y moments
    # rest on the lattice's nearest columns at the support's edge
    h = sphere_h_init(100.0 if squeeze == 1.0 else 40.0, 1.0 / squeeze,
                      state.n)
    base = build_state(x, y, z, 0.0, 0.0, 0.0, h, 1.0 / state.n, 1.0,
                       1e-6, const.alphamin)
    cfg = make_propagator_config(base, box, const, block=4096,
                                 backend="pallas")
    ss, keys, _ = _sort_by_keys(base, box, "hilbert")
    nidx, nmask, _, occ = find_neighbors(ss.x, ss.y, ss.z, ss.h, keys, box,
                                         cfg.nbr)
    assert int(occ) <= cfg.nbr.cap
    xm = hydro_ve.compute_xmass(ss.x, ss.y, ss.z, ss.h, ss.m, nidx, nmask,
                                box, const, 4096)
    # kx = sum_j xm_j W_ij is ~1 everywhere but at the open faces; held at 1
    # the IAD gradient of a linear field is exact at EVERY target, faces
    # included, whatever the jitter does to the volume elements xm_j
    kx = jnp.ones_like(xm)
    return ss, keys, box, const, cfg.nbr, nidx, nmask, xm, kx


@pytest.fixture(scope="module", params=[1.0, 8.0], ids=["cubic", "squeezed"])
def lattice(request):
    return _lattice(request.param)


def _outputs(divv, curlv, dv):
    """(n, 8): divv, curlv and the symmetrized gradient as the ops emit it."""
    return np.stack([
        divv, curlv, dv[0][0], dv[0][1] + dv[1][0], dv[0][2] + dv[2][0],
        dv[1][1], dv[1][2] + dv[2][1], dv[2][2],
    ], axis=-1)


def _reference_f64(pos, vel, h, kx, xm, const):
    """The two ops' sums over every pair inside 2 h_i, in float64."""
    coeffs = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)
    r = pos[:, None, :] - pos[None, :, :]              # (n, n, 3) r_ij
    d2 = np.sum(r * r, axis=-1)
    mask = (d2 < 4.0 * (h * h)[:, None]) & ~np.eye(len(h), dtype=bool)
    s = np.clip(d2 / (h * h)[:, None] * 0.5 - 1.0, -1.0, 1.0)
    w = np.full_like(s, coeffs[-1])
    for c in coeffs[-2::-1]:
        w = w * s + c
    w = np.where(mask, np.maximum(w, 0.0), 0.0)
    tau = np.einsum("ij,j,ija,ijb->iab", w, xm / kx, r, r)
    cmat = np.linalg.inv(tau) * (h**3 / K)[:, None, None]
    v_ji = vel[None, :, :] - vel[:, None, :]
    ta = -np.einsum("ibc,ijc->ijb", cmat, r) * w[..., None]
    dv = np.einsum("j,ija,ijb->iab", xm, v_ji, ta)
    dv = dv * (K / (h**3 * kx))[:, None, None]
    curl = np.stack([dv[:, 2, 1] - dv[:, 1, 2], dv[:, 0, 2] - dv[:, 2, 0],
                     dv[:, 1, 0] - dv[:, 0, 1]], axis=1)
    out = _outputs(np.trace(dv, axis1=1, axis2=2),
                   np.linalg.norm(curl, axis=1),
                   [[dv[:, a, b] for b in range(3)] for a in range(3)])
    return out, cmat, np.linalg.cond(tau)


@pytest.mark.parametrize("field", sorted(FIELDS))
def test_fused_linear_field(lattice, field):
    ss, keys, box, const, nbr, nidx, nmask, xm, kx = lattice
    A = FIELDS[field]
    pos = np.stack([np.asarray(a, np.float64) for a in (ss.x, ss.y, ss.z)], 1)
    vx, vy, vz = (jnp.asarray(v, jnp.float32) for v in (pos @ A.T).T)
    vel = np.stack([np.asarray(v, np.float64) for v in (vx, vy, vz)], 1)
    args = (ss.x, ss.y, ss.z, vx, vy, vz, ss.h, kx, xm)

    cs2 = hydro_std.compute_iad(ss.x, ss.y, ss.z, ss.h, xm / kx, nidx, nmask,
                                box, const, 4096)
    two = hydro_ve.compute_iad_divv_curlv(
        *args, *cs2, nidx, nmask, box, const, 4096, with_gradv=True)
    cs1, fused, _ = pp.pallas_iad_divv_curlv(
        *args, keys, box, const, nbr, with_gradv=True, interpret=True)
    two = np.stack([np.asarray(a, np.float64) for a in two], 1)
    fused = np.stack([np.asarray(a, np.float64) for a in fused], 1)

    ref, cmat, cond = _reference_f64(
        pos, vel, *(np.asarray(a, np.float64) for a in (ss.h, kx, xm)), const)
    squeezed = float(box.lengths[2]) < 0.5
    # the squeezed lattice is the ill-conditioned case by construction
    # (cubic: median 1.3, max 4.8; squeezed: median 10, max 364)
    assert (np.percentile(cond, 90) > 20.0) == squeezed

    # C itself: the epilogue inverts pallas_iad's moments (to the bit,
    # tests/test_pair_lists.py); against float64 it reads 3.2e-7 of the
    # largest component on the cubic lattice and 1.6e-5 on the squeezed
    # one (the two-pass reference's C: 5.6e-7 and 2.0e-5)
    c64 = np.stack([cmat[:, 0, 0], cmat[:, 0, 1], cmat[:, 0, 2],
                    cmat[:, 1, 1], cmat[:, 1, 2], cmat[:, 2, 2]], 1)
    c1 = np.stack([np.asarray(a, np.float64) for a in cs1], 1)
    assert np.abs(c1 - c64).max() <= (
        (2e-4 if squeezed else 3e-6) * np.abs(c64).max())

    # exactness for linear fields at every target (kx = 1, see _lattice):
    # divv -> tr A, curlv -> |rot v|, gradv -> A + A^T off the diagonal
    exact = _outputs(
        np.trace(A), np.linalg.norm([A[2, 1] - A[1, 2], A[0, 2] - A[2, 0],
                                     A[1, 0] - A[0, 1]]), A)
    scale = np.abs(A).max()
    # measured 1.2e-6 (cubic) and 4.9e-5 (squeezed) at worst
    tol = (5e-4 if squeezed else 1e-5) * scale
    np.testing.assert_allclose(fused, np.broadcast_to(exact, fused.shape),
                               rtol=0.0, atol=tol)

    # f32 association: fused no further from the float64 sums than two-pass
    # (measured: rms ratio 0.89-1.08, max ratio 0.82-1.06 over the six
    # cases; the max is one target's, and swings by +-0.5 with the jitter's
    # seed on the squeezed lattice)
    err_f, err_t = fused - ref, two - ref
    rms = lambda e: np.sqrt(np.mean(e * e))
    assert rms(err_f) <= 1.5 * rms(err_t)
    assert np.abs(err_f).max() <= 1.5 * np.abs(err_t).max()
