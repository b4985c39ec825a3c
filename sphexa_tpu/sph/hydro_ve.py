"""Generalized volume-element (VE) SPH pipeline.

Physics-equivalent of the reference's ``sph/hydro_ve/`` kernel family
(xmass_kern.hpp, ve_def_gradh_kern.hpp, iad_kern.hpp, divv_curlv_kern.hpp,
av_switches_kern.hpp, momentum_energy_kern.hpp): the SPHYNX volume-element
formulation with grad-h terms, per-particle artificial-viscosity switches,
and the Atwood-number crossed/uncrossed momentum ramp. Each op is a masked
vectorized j-reduction; the IAD tensor op is shared with the std pipeline
(sph/hydro_std.py compute_iad with vol_j = xm/kx).
"""

from typing import Tuple

import jax.numpy as jnp

from sphexa_tpu.sfc.box import Box
from sphexa_tpu.sph.kernels import (
    artificial_viscosity,
    sinc_dterh_u,
    sinc_kernel_u,
    ts_k_courant,
)
from sphexa_tpu.sph.pairs import iad_project, mmax, msum, pair_geometry
from sphexa_tpu.sph.particles import SimConstants
from sphexa_tpu.util.blocking import blocked_map
from sphexa_tpu.util.phases import named_phase


@named_phase("xmass")
def compute_xmass(x, y, z, h, m, nidx, nmask, box: Box, const: SimConstants, block=2048):
    """Generalized volume element xm_i = m_i / rho0_i (xmass_kern.hpp:50-79),
    rho0 the standard kernel-summed density estimate."""
    n = x.shape[0]

    def body(idx):
        g = pair_geometry(idx, x, y, z, h, nidx, nmask, box)
        w = sinc_kernel_u(g.v1 * g.v1, const.sinc_index, const.kernel_choice)
        rho0 = m[idx] + msum(g.mask, m[g.nj] * w)
        h_i = h[idx]
        return m[idx] / (rho0 * const.K / (h_i * h_i * h_i))

    return blocked_map(body, n, block)


@named_phase("gradh")
def compute_ve_def_gradh(
    x, y, z, h, m, xm, nidx, nmask, box: Box, const: SimConstants, block=2048
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """VE normalization kx and grad-h correction (ve_def_gradh_kern.hpp:43-90).

    kx_i = K h^-3 sum_j xm_j W; gradh from the h-derivative terms
    dW/dh = -(3 W + v dW/dv)/h summed over both xm and m weights.
    """
    n = x.shape[0]

    def body(idx):
        g = pair_geometry(idx, x, y, z, h, nidx, nmask, box)
        w = sinc_kernel_u(g.v1 * g.v1, const.sinc_index, const.kernel_choice)
        dterh = sinc_dterh_u(g.v1 * g.v1, const.sinc_index, const.kernel_choice)

        xm_i = xm[idx]
        m_i = m[idx]
        kx = xm_i + msum(g.mask, xm[g.nj] * w)
        whomega = -3.0 * xm_i + msum(g.mask, xm[g.nj] * dterh)
        wrho0 = -3.0 * m_i + msum(g.mask, m[g.nj] * dterh)

        h_i = h[idx]
        h3inv = 1.0 / (h_i * h_i * h_i)
        kx = kx * const.K * h3inv
        whomega = whomega * const.K * h3inv / h_i
        wrho0 = wrho0 * const.K * h3inv / h_i

        whomega = whomega * m_i / xm_i + (kx - const.K * xm_i * h3inv) * wrho0
        rho = kx * m_i / xm_i
        dhdrho = -h_i / (rho * 3.0)
        gradh = 1.0 - dhdrho * whomega
        return kx, gradh

    return blocked_map(body, n, block)


@named_phase("eos")
def compute_eos_ve(temp, m, kx, xm, gradh, const: SimConstants):
    """VE ideal-gas EOS (hydro_ve/eos.hpp:52-77): returns (prho, c, rho, p).

    prho = p / (kx m^2 gradh) is the quantity entering the momentum sum.
    """
    rho = kx * m / xm
    tmp = const.cv * temp * (const.gamma - 1.0)
    p = rho * tmp
    c = jnp.sqrt(tmp)
    prho = p / (kx * m * m * gradh)
    return prho, c, rho, p


@named_phase("divv-curlv")
def compute_iad_divv_curlv(
    x, y, z, vx, vy, vz, h, kx, xm,
    c11, c12, c13, c22, c23, c33,
    nidx, nmask, box: Box, const: SimConstants, block=2048, with_gradv=False,
):
    """Velocity divergence/curl through the IAD gradient (divv_curlv_kern.hpp
    :43-120); optionally the full symmetrized velocity-gradient tensor for
    the avClean momentum correction. The reference fuses IAD+divv+curlv in
    one pass (iad_divv_curlv.hpp); here IAD comes from hydro_std.compute_iad
    and this op consumes its output — XLA's fusion takes the place of the
    hand-fused kernel. The pair engine's op IS fused
    (pallas_pairs.pallas_iad_divv_curlv, one neighbour pass); this two-op
    form stays as the independent reference its tests compare against.
    """
    n = x.shape[0]

    def body(idx):
        g = pair_geometry(idx, x, y, z, h, nidx, nmask, box)
        w = sinc_kernel_u(g.v1 * g.v1, const.sinc_index, const.kernel_choice)

        tA1, tA2, tA3 = iad_project(
            c11[idx][:, None], c12[idx][:, None], c13[idx][:, None],
            c22[idx][:, None], c23[idx][:, None], c33[idx][:, None],
            g.rx, g.ry, g.rz, w,
        )

        vx_ji = vx[g.nj] - vx[idx][:, None]
        vy_ji = vy[g.nj] - vy[idx][:, None]
        vz_ji = vz[g.nj] - vz[idx][:, None]
        xm_j = xm[g.nj]

        dvx = (msum(g.mask, vx_ji * xm_j * tA1), msum(g.mask, vx_ji * xm_j * tA2),
               msum(g.mask, vx_ji * xm_j * tA3))
        dvy = (msum(g.mask, vy_ji * xm_j * tA1), msum(g.mask, vy_ji * xm_j * tA2),
               msum(g.mask, vy_ji * xm_j * tA3))
        dvz = (msum(g.mask, vz_ji * xm_j * tA1), msum(g.mask, vz_ji * xm_j * tA2),
               msum(g.mask, vz_ji * xm_j * tA3))

        h_i = h[idx]
        norm_kxi = const.K / (h_i * h_i * h_i) / kx[idx]
        divv = norm_kxi * (dvx[0] + dvy[1] + dvz[2])
        curl = (dvz[1] - dvy[2], dvx[2] - dvz[0], dvy[0] - dvx[1])
        curlv = norm_kxi * jnp.sqrt(curl[0] ** 2 + curl[1] ** 2 + curl[2] ** 2)

        if with_gradv:
            dv11 = norm_kxi * dvx[0]
            dv12 = norm_kxi * (dvx[1] + dvy[0])
            dv13 = norm_kxi * (dvx[2] + dvz[0])
            dv22 = norm_kxi * dvy[1]
            dv23 = norm_kxi * (dvy[2] + dvz[1])
            dv33 = norm_kxi * dvz[2]
            return divv, curlv, dv11, dv12, dv13, dv22, dv23, dv33
        return divv, curlv

    return blocked_map(body, n, block)


@named_phase("av-switches")
def compute_av_switches(
    x, y, z, vx, vy, vz, h, c, kx, xm, divv, alpha,
    c11, c12, c13, c22, c23, c33,
    nidx, nmask, box: Box, dt, const: SimConstants, block=2048,
):
    """Per-particle viscosity switch evolution (av_switches_kern.hpp:43-137):
    alpha grows toward alphamax in converging flow with strong grad(divv),
    decays toward alphamin on the signal-velocity time scale otherwise."""
    n = x.shape[0]

    def body(idx):
        g = pair_geometry(idx, x, y, z, h, nidx, nmask, box)
        h_i = h[idx]
        w = const.K / (h_i * h_i * h_i)[:, None] * sinc_kernel_u(g.v1 * g.v1, const.sinc_index, const.kernel_choice)

        vx_ij = vx[idx][:, None] - vx[g.nj]
        vy_ij = vy[idx][:, None] - vy[g.nj]
        vz_ij = vz[idx][:, None] - vz[g.nj]
        rv = g.rx * vx_ij + g.ry * vy_ij + g.rz * vz_ij

        c_i = c[idx][:, None]
        vijsignal_pair = jnp.where(
            rv < 0.0, c_i + c[g.nj] - 3.0 * rv / g.dist, 0.0
        )
        vijsignal = jnp.maximum(mmax(g.mask, vijsignal_pair), 1e-40 * c[idx])

        tA1, tA2, tA3 = iad_project(
            c11[idx][:, None], c12[idx][:, None], c13[idx][:, None],
            c22[idx][:, None], c23[idx][:, None], c33[idx][:, None],
            g.rx, g.ry, g.rz, w,
        )

        vol_j = xm[g.nj] / kx[g.nj]
        factor = vol_j * (divv[idx][:, None] - divv[g.nj])
        gdx = msum(g.mask, factor * tA1)
        gdy = msum(g.mask, factor * tA2)
        gdz = msum(g.mask, factor * tA3)
        graddivv = jnp.sqrt(gdx * gdx + gdy * gdy + gdz * gdz)

        divv_i = divv[idx]
        a_const = h_i * h_i * graddivv
        alphaloc = jnp.where(
            divv_i < 0.0,
            const.alphamax * a_const / (a_const + h_i * jnp.abs(divv_i) + 0.05 * c[idx]),
            0.0,
        )

        alpha_i = alpha[idx]
        decay = h_i / (const.decay_constant * vijsignal)
        target = jnp.where(alphaloc >= const.alphamin, alphaloc, const.alphamin)
        alphadot = (target - alpha_i) / decay
        alpha_decayed = alpha_i + alphadot * dt
        return jnp.where(alphaloc >= alpha_i, alphaloc, alpha_decayed)

    return blocked_map(body, n, block)


def _av_rv_correction(rx, ry, rz, eta_ab, eta_crit, gv_i, gv_j):
    """avClean correction to the projected pair velocity
    (momentum_energy_kern.hpp avRvCorrection:43-63)."""
    sym_dot = lambda gv, rx, ry, rz: (
        rx * (gv[0] * rx + gv[1] * ry + gv[2] * rz)
        + ry * (gv[3] * ry + gv[4] * rz)
        + rz * (gv[5] * rz)
    )
    d1 = sym_dot(gv_i, rx, ry, rz)
    d2 = sym_dot(gv_j, rx, ry, rz)
    eta_diff = 5.0 * (eta_ab - eta_crit)
    d3 = jnp.where(eta_ab < eta_crit, jnp.exp(-(eta_diff**2)), 1.0)
    A = jnp.where(d2 != 0.0, d1 / d2, 0.0)
    Ap1 = 1.0 + A
    phi = 0.5 * d3 * jnp.clip(4.0 * A / (Ap1 * Ap1), 0.0, 1.0)
    return -phi * (d1 + d2)


@named_phase("momentum-energy")
def compute_momentum_energy_ve(
    x, y, z, vx, vy, vz, h, m, prho, c, kx, xm, alpha,
    c11, c12, c13, c22, c23, c33,
    nidx, nmask, nc, box: Box, const: SimConstants, block=1024,
    gradv=None,
):
    """VE momentum + energy (momentum_energy_kern.hpp:65-222): Atwood-ramped
    crossed/uncrossed volume elements, per-particle alpha viscosity, signal
    velocity 0.5(ci+cj) - 2 w_ij; optional avClean gradV correction when
    ``gradv`` (6-tuple of dV arrays) is given.

    Returns (ax, ay, az, du, min_dt_courant).
    """
    n = x.shape[0]
    av_clean = gradv is not None

    def body(idx):
        g = pair_geometry(idx, x, y, z, h, nidx, nmask, box)
        h_i = h[idx][:, None]
        h_j = h[g.nj]
        if getattr(const, "sym_pairs", True):
            # min-h symmetric cutoff: exact pairwise antisymmetry (see
            # SimConstants.sym_pairs; matches the engine's sym_jf mask)
            g = g._replace(mask=g.mask & (g.dist < 2.0 * h_j))
        hi3 = h_i * h_i * h_i
        hj3 = h_j * h_j * h_j
        w_i = sinc_kernel_u(g.v1 * g.v1, const.sinc_index, const.kernel_choice) / hi3
        v2 = g.dist / h_j
        w_j = sinc_kernel_u(v2 * v2, const.sinc_index, const.kernel_choice) / hj3

        vx_ij = vx[idx][:, None] - vx[g.nj]
        vy_ij = vy[idx][:, None] - vy[g.nj]
        vz_ij = vz[idx][:, None] - vz[g.nj]
        rv = g.rx * vx_ij + g.ry * vy_ij + g.rz * vz_ij

        if av_clean:
            eta_crit = jnp.cbrt(32.0 * jnp.pi / 3.0 / (nc[idx].astype(jnp.float32) + 1.0))
            gv_i = tuple(a[idx][:, None] for a in gradv)
            gv_j = tuple(a[g.nj] for a in gradv)
            rv = rv + _av_rv_correction(
                g.rx, g.ry, g.rz, jnp.minimum(g.v1, v2), eta_crit[:, None], gv_i, gv_j
            )

        w_ij = rv / g.dist
        c_i = c[idx][:, None]
        c_j = c[g.nj]
        visc = artificial_viscosity(alpha[idx][:, None], alpha[g.nj], c_i, c_j, w_ij)

        vijsignal = 0.5 * (c_i + c_j) - 2.0 * w_ij
        maxvsignal = mmax(g.mask, vijsignal)

        tA1_i, tA2_i, tA3_i = iad_project(
            c11[idx][:, None], c12[idx][:, None], c13[idx][:, None],
            c22[idx][:, None], c23[idx][:, None], c33[idx][:, None],
            g.rx, g.ry, g.rz, w_i,
        )
        tA1_j, tA2_j, tA3_j = iad_project(
            c11[g.nj], c12[g.nj], c13[g.nj], c22[g.nj], c23[g.nj], c33[g.nj],
            g.rx, g.ry, g.rz, w_j,
        )

        m_i = m[idx][:, None]
        m_j = m[g.nj]
        xm_i = xm[idx][:, None]
        xm_j = xm[g.nj]
        rho_i = kx[idx][:, None] * m_i / xm_i
        rho_j = kx[g.nj] * m_j / xm_j

        # Atwood-number ramp between uncrossed (xm_i^2, xm_j^2) and crossed
        # (xm_i xm_j) volume-element weightings
        atwood = jnp.abs(rho_i - rho_j) / (rho_i + rho_j)
        sigma = const.ramp * (atwood - const.at_min)
        a_uncrossed, b_uncrossed = xm_i * xm_i, xm_j * xm_j
        crossed = xm_i * xm_j
        a_ramp = xm_i ** (2.0 - sigma) * xm_j**sigma
        b_ramp = xm_j ** (2.0 - sigma) * xm_i**sigma
        a_mom = jnp.where(atwood < const.at_min, a_uncrossed,
                          jnp.where(atwood > const.at_max, crossed, a_ramp))
        b_mom = jnp.where(atwood < const.at_min, b_uncrossed,
                          jnp.where(atwood > const.at_max, crossed, b_ramp))

        a_visc = m_j / rho_i * visc
        b_visc = m_j / rho_j * visc
        a_visc_x = 0.5 * (a_visc * tA1_i + b_visc * tA1_j)
        a_visc_y = 0.5 * (a_visc * tA2_i + b_visc * tA2_j)
        a_visc_z = 0.5 * (a_visc * tA3_i + b_visc * tA3_j)
        a_visc_energy = msum(
            g.mask, a_visc_x * vx_ij + a_visc_y * vy_ij + a_visc_z * vz_ij
        )

        prho_i = prho[idx][:, None]
        energy = msum(
            g.mask,
            m_j * a_mom * (vx_ij * tA1_i + vy_ij * tA2_i + vz_ij * tA3_i),
        )
        mom_i = m_j * prho_i * a_mom
        mom_j = m_j * prho[g.nj] * b_mom
        mom_x = msum(g.mask, mom_i * tA1_i + mom_j * tA1_j + a_visc_x)
        mom_y = msum(g.mask, mom_i * tA2_i + mom_j * tA2_j + a_visc_y)
        mom_z = msum(g.mask, mom_i * tA3_i + mom_j * tA3_j + a_visc_z)

        a_visc_energy = jnp.maximum(a_visc_energy, 0.0)
        du = const.K * (prho[idx] * energy + 0.5 * a_visc_energy)

        dt_i = ts_k_courant(maxvsignal, h[idx], c[idx], const.k_cour)
        return (-const.K * mom_x, -const.K * mom_y, -const.K * mom_z, du, dt_i)

    ax, ay, az, du, dt = blocked_map(body, n, block)
    return ax, ay, az, du, jnp.min(dt)
