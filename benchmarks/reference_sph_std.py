"""Plain reference of the std SPH formulation's operators, by all-pairs sums.

    rho_i, then the IAD tensor C_i, then (ax, ay, az, du)_i with the
    artificial viscosity, at a seeded sample of targets i.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, from ``x, y, z, vx, vy, vz, h,
m, temp`` and four constants only. Every sum runs over ALL particles with a
distance mask: no cells, no neighbour lists, no kernels of the program, no
polynomial kernel fit, open box (no image shifts). A target's forces need
its neighbours' rho, p, c and C, and those C need *their* neighbours' rho,
so the work goes ring by ring (all found by all-pairs distance tests):

    A  = targets and everything within 2 h_i of a target i
    B  = A and everything within 2 h_j of a j in A
    rho on B  ->  p, c, C on A  ->  (ax, ay, az, du) on the targets

in blocks of ``block`` rows against all N sources, so 256 targets x 1.1M
sources fit beside the state. Quantities outside their ring are NaN, so a
ring that was too small shows as a non-finite result, not as a small error.

The equations are upstream SPH-EXA's hydro_std (SURVEY.md 2b: density.hpp,
eos.hpp, iad_kern.hpp, momentum_energy_kern.hpp), written with
W_i(r) = K h_i^-3 sinc(pi |r| / (2 h_i))^n on |r| < 2 h_i and r_ij = r_i - r_j:

    rho_i  = sum_j m_j W_i(r_ij)                                   (self included)
    p_i    = (gamma - 1) cv T_i rho_i,   c_i = sqrt((gamma - 1) cv T_i)
    C_i    = [ sum_j (m_j / rho_j) W_i(r_ij) r_ij (x) r_ij ]^-1
    w_ij   = v_ij . r_ij / |r_ij|
    Pi_ij  = 1/2 * ( -(1/2 (c_i + c_j) - 2 w_ij) w_ij  if w_ij < 0 else 0 )
    a_i    = sum_j   W_i (m_j p_i / rho_i^2 + Pi_ij m_i / rho_i) C_i r_ij
                   + W_j (m_j / rho_j) (p_j / rho_j + Pi_ij)     C_j r_ij
    du_i   = -1/2 sum_j v_ij . [ W_i (2 m_j p_i / rho_i^2 + Pi_ij m_i / rho_i) C_i r_ij
                               + W_j (m_j / rho_j) Pi_ij         C_j r_ij ]

(constant alpha = 1 and beta = 2 in the viscosity, halved per pair).
Departures from the published form, each because the program defines the
quantity so:
- the momentum and energy sums keep a pair only where |r_ij| < 2 min(h_i,
  h_j) (``SimConstants.sym_pairs``: exact pairwise antisymmetry; upstream
  keeps |r_ij| < 2 h_i);
- the 3x3 inverse is the adjugate over the determinant, with no exponent
  conditioning (the program's frexp/ldexp trick cancels exactly);
- the kernel is the analytic sinc^n with K by Simpson in float64
  (reference.sinc_kernel_norm); the program evaluates a polynomial fit.

``product_dtype`` rounds every kernel value to that dtype before it is
used: what a lower-precision pass would give. The comparison's limits have
to refuse it (tests/test_noh_lists_reference.py, PERF.md).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference


def _pad_blocks(idx, block):
    """(nb, block) int32 rows of ``idx``, the tail padded with its last
    entry (a repeated row changes no OR and is cut from every output)."""
    return reference._blocks(jnp.asarray(idx, jnp.int32), block)


def _displacements(bi, x, y, z):
    rx = x[bi][:, None] - x[None, :]
    ry = y[bi][:, None] - y[None, :]
    rz = z[bi][:, None] - z[None, :]
    return rx, ry, rz, jnp.sqrt(rx * rx + ry * ry + rz * rz)


def _kernel(dist, h, sinc_index, product_dtype):
    """sinc(pi v / 2)^n on v = dist / h < 2, without K h^-3."""
    v = dist / h
    pv = (0.5 * jnp.pi) * v
    sinc = jnp.where(v > 0.0, jnp.sin(pv) / jnp.where(v > 0.0, pv, 1.0), 1.0)
    w = jnp.where(v < 2.0, sinc ** sinc_index, 0.0)
    if product_dtype is not None:
        w = w.astype(product_dtype).astype(jnp.float32)
    return w


@functools.partial(jax.jit, static_argnames=("block",))
def _within_reach(idx, x, y, z, h, block):
    """Bool (N,): particle k lies within 2 h_i of some i in ``idx``."""

    def one_block(seen, bi):
        _, _, _, dist = _displacements(bi, x, y, z)
        return seen | jnp.any(dist < 2.0 * h[bi][:, None], axis=0), None

    seen, _ = jax.lax.scan(one_block, jnp.zeros(x.shape, bool),
                           _pad_blocks(idx, block))
    return seen


def _ring(idx, x, y, z, h, block):
    """Sorted indices of ``idx`` and everything within reach of it."""
    seen = np.array(_within_reach(idx, x, y, z, h, block))
    seen[np.asarray(idx)] = True
    return np.nonzero(seen)[0]


@functools.partial(jax.jit, static_argnames=("sinc_index", "block",
                                             "product_dtype"))
def _density(idx, x, y, z, h, m, k_norm, sinc_index, block, product_dtype):
    def one_block(bi):
        _, _, _, dist = _displacements(bi, x, y, z)
        hi = h[bi][:, None]
        w = _kernel(dist, hi, sinc_index, product_dtype)
        return k_norm * jnp.sum(m[None, :] * w, axis=1) / hi[:, 0] ** 3

    out = jax.lax.map(one_block, _pad_blocks(idx, block))
    return out.reshape(-1)[: idx.shape[0]]


@functools.partial(jax.jit, static_argnames=("sinc_index", "block",
                                             "product_dtype"))
def _iad(idx, x, y, z, h, vol, k_norm, sinc_index, block, product_dtype):
    """The six components (c11, c12, c13, c22, c23, c33) of C at ``idx``."""

    def one_block(bi):
        rx, ry, rz, dist = _displacements(bi, x, y, z)
        hi = h[bi][:, None]
        w = _kernel(dist, hi, sinc_index, product_dtype) * (k_norm / hi ** 3)
        # vol is NaN outside ring B: only pairs inside the support count
        vw = jnp.where(dist < 2.0 * hi, vol[None, :] * w, 0.0)
        t11 = jnp.sum(rx * rx * vw, axis=1)
        t12 = jnp.sum(rx * ry * vw, axis=1)
        t13 = jnp.sum(rx * rz * vw, axis=1)
        t22 = jnp.sum(ry * ry * vw, axis=1)
        t23 = jnp.sum(ry * rz * vw, axis=1)
        t33 = jnp.sum(rz * rz * vw, axis=1)
        det = (t11 * t22 * t33 + 2.0 * t12 * t23 * t13
               - t11 * t23 * t23 - t22 * t13 * t13 - t33 * t12 * t12)
        return ((t22 * t33 - t23 * t23) / det, (t13 * t23 - t33 * t12) / det,
                (t12 * t23 - t22 * t13) / det, (t11 * t33 - t13 * t13) / det,
                (t13 * t12 - t11 * t23) / det, (t11 * t22 - t12 * t12) / det)

    out = jax.lax.map(one_block, _pad_blocks(idx, block))
    return tuple(a.reshape(-1)[: idx.shape[0]] for a in out)


@functools.partial(jax.jit, static_argnames=("sinc_index", "block",
                                             "product_dtype"))
def _momentum_energy(idx, x, y, z, vx, vy, vz, h, m, rho, p, c, cs, k_norm,
                     sinc_index, block, product_dtype):
    c11, c12, c13, c22, c23, c33 = cs
    n = x.shape[0]

    def project(rx, ry, rz, t):
        a11, a12, a13, a22, a23, a33 = t
        return (a11 * rx + a12 * ry + a13 * rz,
                a12 * rx + a22 * ry + a23 * rz,
                a13 * rx + a23 * ry + a33 * rz)

    def one_block(bi):
        rx, ry, rz, dist = _displacements(bi, x, y, z)
        hi, hj = h[bi][:, None], h[None, :]
        other = jnp.arange(n, dtype=jnp.int32)[None, :] != bi[:, None]
        pair = other & (dist < 2.0 * hi) & (dist < 2.0 * hj)
        w_i = _kernel(dist, hi, sinc_index, product_dtype) * (k_norm / hi ** 3)
        w_j = _kernel(dist, hj, sinc_index, product_dtype) * (k_norm / hj ** 3)
        vxij = vx[bi][:, None] - vx[None, :]
        vyij = vy[bi][:, None] - vy[None, :]
        vzij = vz[bi][:, None] - vz[None, :]
        w_ij = (rx * vxij + ry * vyij + rz * vzij) / jnp.where(pair, dist, 1.0)
        v_signal = 0.5 * (c[bi][:, None] + c[None, :]) - 2.0 * w_ij
        visc = 0.5 * jnp.where(w_ij < 0.0, -v_signal * w_ij, 0.0)

        ti = project(rx, ry, rz, [a[bi][:, None] for a in
                                  (c11, c12, c13, c22, c23, c33)])
        tj = project(rx, ry, rz, [a[None, :] for a in
                                  (c11, c12, c13, c22, c23, c33)])
        rho_i, p_i = rho[bi][:, None], p[bi][:, None]
        mj_pro_i = m[None, :] * p_i / (rho_i * rho_i)
        mi_roi = (m[bi] / rho[bi])[:, None]
        mj_roj_wj = m[None, :] / rho[None, :] * w_j
        a = w_i * (mj_pro_i + visc * mi_roi)
        b = mj_roj_wj * (p[None, :] / rho[None, :] + visc)
        a_e = w_i * (2.0 * mj_pro_i + visc * mi_roi)
        b_e = visc * mj_roj_wj
        # rho, p, c, C are NaN outside ring A: only real pairs count
        psum = lambda terms: jnp.sum(jnp.where(pair, terms, 0.0), axis=1)
        energy = psum(vxij * (a_e * ti[0] + b_e * tj[0])
                      + vyij * (a_e * ti[1] + b_e * tj[1])
                      + vzij * (a_e * ti[2] + b_e * tj[2]))
        return (psum(a * ti[0] + b * tj[0]), psum(a * ti[1] + b * tj[1]),
                psum(a * ti[2] + b * tj[2]), -0.5 * energy)

    out = jax.lax.map(one_block, _pad_blocks(idx, block))
    return tuple(a.reshape(-1)[: idx.shape[0]] for a in out)


def _spread(n, idx, values):
    """(N,) float32, ``values`` at ``idx`` and NaN elsewhere."""
    return jnp.full((n,), jnp.nan, jnp.float32).at[jnp.asarray(idx)].set(values)


def std_forces(targets, x, y, z, vx, vy, vz, h, m, temp, *, gamma, cv,
               sinc_index, block=64, product_dtype=None):
    """``{"rho", "ax", "ay", "az", "du"}`` at ``targets`` (numpy float32)
    plus the ring sizes ``{"ring_a", "ring_b"}``, from the whole particle
    set. ``gamma``, ``cv`` and ``sinc_index`` are the case's constants."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        x, y, z, vx, vy, vz, h, m, temp = map(
            f32, (x, y, z, vx, vy, vz, h, m, temp))
        n = x.shape[0]
        targets = np.asarray(targets)
        k_norm = reference.sinc_kernel_norm(sinc_index)
        kw = dict(sinc_index=float(sinc_index), block=block,
                  product_dtype=product_dtype)

        ring_a = _ring(targets, x, y, z, h, block)
        ring_b = _ring(ring_a, x, y, z, h, block)
        rho = _spread(n, ring_b, _density(jnp.asarray(ring_b), x, y, z, h, m,
                                          k_norm, **kw))
        tmp = (gamma - 1.0) * cv * temp
        p, c = rho * tmp, jnp.sqrt(tmp)
        cs = _iad(jnp.asarray(ring_a), x, y, z, h, m / rho, k_norm, **kw)
        cs = tuple(_spread(n, ring_a, a) for a in cs)
        ax, ay, az, du = _momentum_energy(
            jnp.asarray(targets), x, y, z, vx, vy, vz, h, m, rho, p, c, cs,
            k_norm, **kw)
        out = {"rho": rho[jnp.asarray(targets)], "ax": ax, "ay": ay, "az": az,
               "du": du}
        out = {k: np.asarray(v) for k, v in out.items()}
    out["ring_a"], out["ring_b"] = len(ring_a), len(ring_b)
    return out


def errors(got, ref):
    """How far the system's ``got`` is from the reference ``ref`` (both
    ``{"rho", "ax", "ay", "az", "du"}`` at the same targets):
    ``rho_rel_max``; ``acc_rel_rms`` / ``acc_rel_max``, the error of the
    acceleration vector over the sample's rms |a| (a target near a force
    balance has a small |a| of its own, and an error that is not small
    beside it); ``du_rel_max`` likewise over the sample's rms |du|."""
    g = {k: np.asarray(got[k], np.float64) for k in ("rho", "ax", "ay", "az", "du")}
    r = {k: np.asarray(ref[k], np.float64) for k in g}
    acc_err = np.sqrt(sum((g[k] - r[k]) ** 2 for k in ("ax", "ay", "az")))
    acc_scale = np.sqrt(np.mean(sum(r[k] ** 2 for k in ("ax", "ay", "az"))))
    du_scale = np.sqrt(np.mean(r["du"] ** 2))
    return {
        "rho_rel_max": float(np.max(np.abs(g["rho"] - r["rho"]) / r["rho"])),
        "acc_rel_rms": float(np.sqrt(np.mean(acc_err ** 2)) / acc_scale),
        "acc_rel_max": float(np.max(acc_err) / acc_scale),
        "du_rel_max": float(np.max(np.abs(g["du"] - r["du"])) / du_scale),
    }
