"""Plain reference of the VE (volume-element) SPH formulation's force stage,
by all-pairs sums, in a box that may be periodic.

    xm_i -> kx_i, gradh_i -> prho_i, c_i, rho_i -> C_i, divv_i, curlv_i
         -> alpha_i -> (ax, ay, az, du)_i        at a seeded sample of targets i.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, from ``x, y, z, vx, vy, vz, h,
m, temp``, the viscosity switches ``alpha`` of the last step, the last
step's ``dt``, the box's edge lengths and a handful of constants only. Every
sum runs over ALL particles with a distance mask: no cells, no neighbour
lists, no kernels of the program, no polynomial kernel fit; on a periodic
axis the displacement is the minimum image ``r - L round(r / L)``. A
target's forces need its neighbours' prho, c, kx, xm, alpha and C; those
alpha need *their* neighbours' divv; those divv need C, whose sum needs the
neighbours' kx; and kx needs the neighbours' xm. So the work goes ring by
ring, one ring deeper than the std stage's (all found by all-pairs distance
tests; N(S) = everything within 2 h_i of an i in S):

    A = T + N(T),  B = A + N(A),  C = B + N(B),  D = C + N(C)
    xm on D -> kx, gradh, prho, c, rho on C -> C_i, divv, curlv on B
            -> alpha on A -> (ax, ay, az, du) on the targets T

in blocks of ``block`` rows against all N sources. Quantities outside their
ring are NaN, so a ring that was too small shows as a non-finite result, not
as a small error.

The equations are upstream SPH-EXA's hydro_ve (SURVEY.md 2b:
xmass_kern.hpp:50-79, ve_def_gradh_kern.hpp:43-90, hydro_ve/eos.hpp:52-77,
iad_kern.hpp + divv_curlv_kern.hpp, av_switches_kern.hpp:43-137,
momentum_energy_kern.hpp:65-222), written with w(v) = sinc(pi v / 2)^n on
v < 2, W_i(r) = K h_i^-3 w(|r| / h_i), r_ij = r_i - r_j, v_ij = v_i - v_j:

    xm_i    = m_i / sum_j m_j W_i(r_ij)                            (self included)
    kx_i    = sum_j xm_j W_i(r_ij)                                 (self included)
    rho_i   = kx_i m_i / xm_i
    d(v)    = -(3 w + v dw/dv),   D_i(r) = K h_i^-4 d(|r| / h_i)   (d(0) = -3)
    gradh_i = 1 + h_i / (3 rho_i) [ (m_i / xm_i) sum_j xm_j D_i
                                    + (kx_i - K xm_i h_i^-3) sum_j m_j D_i ]
    p_i     = (gamma - 1) cv T_i rho_i,  c_i = sqrt((gamma - 1) cv T_i),
    prho_i  = p_i / (kx_i m_i^2 gradh_i)
    C_i     = [ sum_j (xm_j / kx_j) W_i(r_ij) r_ij (x) r_ij ]^-1
    G_i[f]  = sum_j xm_j (f_i - f_j) W_i(r_ij) C_i r_ij / kx_i     (IAD gradient)
    divv_i  = tr G_i[v],   curlv_i = | rot G_i[v] |
    alpha_i : av_switches_kern.hpp's switch (``_av_switches`` below) from
              grad divv_i = sum_j (xm_j / kx_j) (divv_i - divv_j) W_i C_i r_ij,
              the largest c_i + c_j - 3 w_ij over approaching pairs, the last
              step's alpha_i and dt
    w_ij    = v_ij . r_ij / |r_ij|
    Pi_ij   = -(1/4 (alpha_i + alpha_j)(c_i + c_j) - 2 w_ij) w_ij  if w_ij < 0 else 0
    At_ij   = |rho_i - rho_j| / (rho_i + rho_j),  sigma = (At_ij - 0.1) / 0.1
    (a, b)  = (xm_i^2, xm_j^2)             if At_ij < 0.1   (uncrossed)
              (xm_i xm_j, xm_i xm_j)       if At_ij > 0.2   (crossed)
              (xm_i^(2-sigma) xm_j^sigma, xm_j^(2-sigma) xm_i^sigma)  between
    a_i     = sum_j m_j [ prho_i a W_i C_i r_ij + prho_j b W_j C_j r_ij ]
                  + 1/2 m_j Pi_ij [ W_i C_i r_ij / rho_i + W_j C_j r_ij / rho_j ]
    du_i    = -prho_i sum_j m_j a W_i v_ij . C_i r_ij
              + 1/2 max(0, -sum_j 1/2 m_j Pi_ij v_ij . [ W_i C_i r_ij / rho_i
                                                         + W_j C_j r_ij / rho_j ])

Departures from the published form, each because the program defines the
quantity so (the same list as reference_sph_std.py's, plus VE's own):
- the momentum and energy sums keep a pair only where |r_ij| < 2 min(h_i,
  h_j) (``SimConstants.sym_pairs``: exact pairwise antisymmetry; upstream
  keeps |r_ij| < 2 h_i); the other sums keep |r_ij| < 2 h_i as upstream;
- the 3x3 inverse is the adjugate over the determinant, with no exponent
  conditioning;
- the kernel is the analytic sinc^n with K by Simpson in float64
  (reference.sinc_kernel_norm) and d(v) its analytic derivative; the program
  evaluates a polynomial fit of w and that fit's derivative; upstream
  interpolates a 20,000-point table of both;
- ``avClean`` off (no velocity-gradient correction of w_ij), as the cells
  run;
- the switch's signal velocity is floored at 1e-40 c_i as in the program
  (upstream floors it the same way): from rest no pair approaches, the
  floor is a float32 denormal and alpha stays what it was.

``product_dtype`` (``"bfloat16"`` or None) rounds every kernel value, w and
d, to that precision before it is used: what a lower-precision pass would
give. The rounding is done on the BITS (round to nearest even, then the low
mantissa bits masked off): an ``astype`` round trip is taken out by the
chip's compiler as excess precision and the control then reads the sound
numbers (PR 38). The comparison's limits have to refuse it
(tests/test_ve_reference.py, PERF.md).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference_sph_std import _pad_blocks, _spread

#: the Atwood ramp's ends and the switch's constants (particles_data.hpp's
#: defaults; ``ve_forces`` takes others by keyword)
AT_MIN, AT_MAX = 0.1, 0.2
ALPHA_MIN, ALPHA_MAX, DECAY_CONSTANT = 0.05, 1.0, 0.2

#: low mantissa bits a float32 loses when it is rounded to the named type
_DROPPED_BITS = {"bfloat16": 16}


def _round_bits(a, product_dtype):
    """float32 ``a`` rounded to ``product_dtype``'s mantissa (nearest, ties
    to even) by integer arithmetic on its bits, still float32."""
    if product_dtype is None:
        return a
    drop = _DROPPED_BITS[product_dtype]
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    half = jnp.uint32((1 << (drop - 1)) - 1)
    bits = bits + half + ((bits >> drop) & jnp.uint32(1))
    bits = bits & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _displacements(bi, x, y, z, lengths, periodic):
    """r_i - r_j of the block's rows against all sources, minimum image on
    the periodic axes, and its length."""
    out = []
    for a, (c, per) in enumerate(zip((x, y, z), periodic)):
        r = c[bi][:, None] - c[None, :]
        if per:
            r = r - lengths[a] * jnp.round(r / lengths[a])
        out.append(r)
    rx, ry, rz = out
    return rx, ry, rz, jnp.sqrt(rx * rx + ry * ry + rz * rz)


def _sinc_pow(s, n):
    return s ** int(n) if float(n).is_integer() else s ** n


def _kernel(dist, h, sinc_index, product_dtype, with_dterh=False):
    """w = sinc(pi v / 2)^n on v = dist / h < 2, without K h^-3; with
    ``with_dterh`` also d = -(3 w + v dw/dv) = -(3 s^n + n s^(n-1) (cos(pi v
    / 2) - s)), both 0 outside the support."""
    v = dist / h
    pv = (0.5 * jnp.pi) * v
    s = jnp.where(v > 0.0, jnp.sin(pv) / jnp.where(v > 0.0, pv, 1.0), 1.0)
    inside = v < 2.0
    w = _round_bits(jnp.where(inside, _sinc_pow(s, sinc_index), 0.0),
                    product_dtype)
    if not with_dterh:
        return w
    d = -(3.0 * _sinc_pow(s, sinc_index)
          + sinc_index * _sinc_pow(s, sinc_index - 1.0) * (jnp.cos(pv) - s))
    return w, _round_bits(jnp.where(inside, d, 0.0), product_dtype)


@functools.partial(jax.jit, static_argnames=("periodic", "block"))
def _within_reach(idx, x, y, z, h, lengths, periodic, block):
    """Bool (N,): particle k lies within 2 h_i of some i in ``idx``."""

    def one_block(seen, bi):
        dist = _displacements(bi, x, y, z, lengths, periodic)[3]
        return seen | jnp.any(dist < 2.0 * h[bi][:, None], axis=0), None

    seen, _ = jax.lax.scan(one_block, jnp.zeros(x.shape, bool),
                           _pad_blocks(idx, block))
    return seen


def _ring(idx, x, y, z, h, lengths, periodic, block):
    """Sorted indices of ``idx`` and everything within reach of it."""
    seen = np.array(_within_reach(jnp.asarray(idx), x, y, z, h, lengths,
                                  periodic, block))
    seen[np.asarray(idx)] = True
    return np.nonzero(seen)[0]


def _rows(fn, idx, block):
    """``fn`` over the blocks of ``idx``; each output cut to idx's rows."""
    out = jax.lax.map(fn, _pad_blocks(idx, block))
    return tuple(a.reshape(-1)[: idx.shape[0]] for a in out)


_STATIC = ("sinc_index", "periodic", "block", "product_dtype")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _xmass(idx, x, y, z, h, m, lengths, k_norm, sinc_index, periodic, block,
           product_dtype):
    def one_block(bi):
        dist = _displacements(bi, x, y, z, lengths, periodic)[3]
        hi = h[bi][:, None]
        w = _kernel(dist, hi, sinc_index, product_dtype)
        rho0 = k_norm * jnp.sum(m[None, :] * w, axis=1) / hi[:, 0] ** 3
        return (m[bi] / rho0,)

    return _rows(one_block, idx, block)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kx_gradh(idx, x, y, z, h, m, xm, lengths, k_norm, sinc_index, periodic,
              block, product_dtype):
    """(kx, gradh) at ``idx``; ``xm`` is NaN outside ring D."""

    def one_block(bi):
        dist = _displacements(bi, x, y, z, lengths, periodic)[3]
        hi = h[bi][:, None]
        near = dist < 2.0 * hi
        w, d = _kernel(dist, hi, sinc_index, product_dtype, with_dterh=True)
        ssum = lambda terms: jnp.sum(jnp.where(near, terms, 0.0), axis=1)
        h_i, m_i, xm_i = h[bi], m[bi], xm[bi]
        kx = k_norm * ssum(xm[None, :] * w) / h_i ** 3
        whomega = k_norm * ssum(xm[None, :] * d) / h_i ** 4
        wrho0 = k_norm * ssum(m[None, :] * d) / h_i ** 4
        whomega = (whomega * m_i / xm_i
                   + (kx - k_norm * xm_i / h_i ** 3) * wrho0)
        rho = kx * m_i / xm_i
        return kx, 1.0 + h_i / (3.0 * rho) * whomega

    return _rows(one_block, idx, block)


def _project(cs, rx, ry, rz):
    c11, c12, c13, c22, c23, c33 = cs
    return (c11 * rx + c12 * ry + c13 * rz,
            c12 * rx + c22 * ry + c23 * rz,
            c13 * rx + c23 * ry + c33 * rz)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _iad_divv_curlv(idx, x, y, z, vx, vy, vz, h, xm, kx, lengths, k_norm,
                    sinc_index, periodic, block, product_dtype):
    """(c11, c12, c13, c22, c23, c33, divv, curlv) at ``idx``; ``xm`` and
    ``kx`` are NaN outside ring C."""

    def one_block(bi):
        rx, ry, rz, dist = _displacements(bi, x, y, z, lengths, periodic)
        hi = h[bi][:, None]
        near = dist < 2.0 * hi
        w = _kernel(dist, hi, sinc_index, product_dtype) * (k_norm / hi ** 3)
        ssum = lambda terms: jnp.sum(jnp.where(near, terms, 0.0), axis=1)
        vw = (xm / kx)[None, :] * w
        t11, t12, t13 = ssum(rx * rx * vw), ssum(rx * ry * vw), ssum(rx * rz * vw)
        t22, t23, t33 = ssum(ry * ry * vw), ssum(ry * rz * vw), ssum(rz * rz * vw)
        det = (t11 * t22 * t33 + 2.0 * t12 * t23 * t13
               - t11 * t23 * t23 - t22 * t13 * t13 - t33 * t12 * t12)
        cs = ((t22 * t33 - t23 * t23) / det, (t13 * t23 - t33 * t12) / det,
              (t12 * t23 - t22 * t13) / det, (t11 * t33 - t13 * t13) / det,
              (t13 * t12 - t11 * t23) / det, (t11 * t22 - t12 * t12) / det)

        # G[f]_k = sum_j xm_j (f_i - f_j) W_i (C_i r_ij)_k / kx_i
        g1, g2, g3 = _project([a[:, None] for a in cs], rx, ry, rz)
        xw = xm[None, :] * w / kx[bi][:, None]
        grad = lambda f: [ssum((f[bi][:, None] - f[None, :]) * xw * g)
                          for g in (g1, g2, g3)]
        dvx, dvy, dvz = grad(vx), grad(vy), grad(vz)
        divv = dvx[0] + dvy[1] + dvz[2]
        curl = (dvz[1] - dvy[2], dvx[2] - dvz[0], dvy[0] - dvx[1])
        curlv = jnp.sqrt(curl[0] ** 2 + curl[1] ** 2 + curl[2] ** 2)
        return (*cs, divv, curlv)

    return _rows(one_block, idx, block)


@functools.partial(jax.jit, static_argnames=_STATIC + ("alphamin", "alphamax",
                                                       "decay_constant"))
def _av_switches(idx, x, y, z, vx, vy, vz, h, c, xm, kx, divv, alpha0, cs, dt,
                 lengths, k_norm, sinc_index, periodic, block, product_dtype,
                 alphamin, alphamax, decay_constant):
    """alpha at ``idx`` (av_switches_kern.hpp:43-137); ``c``, ``xm``, ``kx``
    and ``divv`` are NaN outside ring B, ``cs`` outside ring B too."""
    n = x.shape[0]

    def one_block(bi):
        rx, ry, rz, dist = _displacements(bi, x, y, z, lengths, periodic)
        hi = h[bi][:, None]
        other = jnp.arange(n, dtype=jnp.int32)[None, :] != bi[:, None]
        near = other & (dist < 2.0 * hi)
        w = _kernel(dist, hi, sinc_index, product_dtype) * (k_norm / hi ** 3)
        ssum = lambda terms: jnp.sum(jnp.where(near, terms, 0.0), axis=1)
        rv = (rx * (vx[bi][:, None] - vx[None, :])
              + ry * (vy[bi][:, None] - vy[None, :])
              + rz * (vz[bi][:, None] - vz[None, :]))
        h_i, c_i, divv_i = h[bi], c[bi], divv[bi]
        signal = jnp.where(near & (rv < 0.0),
                           c_i[:, None] + c[None, :]
                           - 3.0 * rv / jnp.where(near, dist, 1.0), 0.0)
        vsignal = jnp.maximum(jnp.max(signal, axis=1), 1e-40 * c_i)

        g1, g2, g3 = _project([a[bi][:, None] for a in cs], rx, ry, rz)
        factor = (xm / kx)[None, :] * (divv_i[:, None] - divv[None, :]) * w
        gdx, gdy, gdz = ssum(factor * g1), ssum(factor * g2), ssum(factor * g3)
        graddivv = jnp.sqrt(gdx * gdx + gdy * gdy + gdz * gdz)

        a_const = h_i * h_i * graddivv
        alphaloc = jnp.where(
            divv_i < 0.0,
            alphamax * a_const / (a_const + h_i * jnp.abs(divv_i)
                                  + 0.05 * c_i), 0.0)
        alpha_i = alpha0[bi]
        decay = h_i / (decay_constant * vsignal)
        goal = jnp.where(alphaloc >= alphamin, alphaloc, alphamin)
        decayed = alpha_i + (goal - alpha_i) / decay * dt
        return (jnp.where(alphaloc >= alpha_i, alphaloc, decayed),)

    return _rows(one_block, idx, block)[0]


@functools.partial(jax.jit, static_argnames=_STATIC + ("at_min", "at_max"))
def _momentum_energy(idx, x, y, z, vx, vy, vz, h, m, prho, c, kx, xm, alpha,
                     cs, lengths, k_norm, sinc_index, periodic, block,
                     product_dtype, at_min, at_max):
    """(ax, ay, az, du) at ``idx`` (momentum_energy_kern.hpp:65-222);
    every j-side field is NaN outside ring A."""
    n = x.shape[0]
    ramp = 1.0 / (at_max - at_min)

    def one_block(bi):
        rx, ry, rz, dist = _displacements(bi, x, y, z, lengths, periodic)
        hi, hj = h[bi][:, None], h[None, :]
        other = jnp.arange(n, dtype=jnp.int32)[None, :] != bi[:, None]
        pair = other & (dist < 2.0 * hi) & (dist < 2.0 * hj)
        w_i = _kernel(dist, hi, sinc_index, product_dtype) * (k_norm / hi ** 3)
        w_j = _kernel(dist, hj, sinc_index, product_dtype) * (k_norm / hj ** 3)
        vxij = vx[bi][:, None] - vx[None, :]
        vyij = vy[bi][:, None] - vy[None, :]
        vzij = vz[bi][:, None] - vz[None, :]
        w_ij = (rx * vxij + ry * vyij + rz * vzij) / jnp.where(pair, dist, 1.0)
        c_i, c_j = c[bi][:, None], c[None, :]
        v_signal = (0.25 * (alpha[bi][:, None] + alpha[None, :]) * (c_i + c_j)
                    - 2.0 * w_ij)
        visc = jnp.where(w_ij < 0.0, -v_signal * w_ij, 0.0)

        gi = [g * w_i for g in
              _project([a[bi][:, None] for a in cs], rx, ry, rz)]
        gj = [g * w_j for g in
              _project([a[None, :] for a in cs], rx, ry, rz)]

        m_i, m_j = m[bi][:, None], m[None, :]
        xm_i, xm_j = xm[bi][:, None], xm[None, :]
        rho_i = kx[bi][:, None] * m_i / xm_i
        rho_j = kx[None, :] * m_j / xm_j
        atwood = jnp.abs(rho_i - rho_j) / (rho_i + rho_j)
        sigma = ramp * (atwood - at_min)
        crossed = xm_i * xm_j
        a_mom = jnp.where(atwood < at_min, xm_i * xm_i, jnp.where(
            atwood > at_max, crossed, xm_i ** (2.0 - sigma) * xm_j ** sigma))
        b_mom = jnp.where(atwood < at_min, xm_j * xm_j, jnp.where(
            atwood > at_max, crossed, xm_j ** (2.0 - sigma) * xm_i ** sigma))

        prho_i = prho[bi][:, None]
        p_i, p_j = m_j * prho_i * a_mom, m_j * prho[None, :] * b_mom
        q_i, q_j = 0.5 * m_j * visc / rho_i, 0.5 * m_j * visc / rho_j
        # rho, prho, c, alpha, C are NaN outside ring A: only real pairs count
        psum = lambda terms: jnp.sum(jnp.where(pair, terms, 0.0), axis=1)
        acc = [psum((p_i + q_i) * a + (p_j + q_j) * b) for a, b in zip(gi, gj)]
        work = psum(m_j * a_mom * (vxij * gi[0] + vyij * gi[1] + vzij * gi[2]))
        heat = -psum(vxij * (q_i * gi[0] + q_j * gj[0])
                     + vyij * (q_i * gi[1] + q_j * gj[1])
                     + vzij * (q_i * gi[2] + q_j * gj[2]))
        du = -prho[bi] * work + 0.5 * jnp.maximum(heat, 0.0)
        return acc[0], acc[1], acc[2], du

    return _rows(one_block, idx, block)


def ve_forces(targets, x, y, z, vx, vy, vz, h, m, temp, alpha, dt, *,
              lengths, periodic, gamma, cv, sinc_index, block=64,
              product_dtype=None, at_min=AT_MIN, at_max=AT_MAX,
              alphamin=ALPHA_MIN, alphamax=ALPHA_MAX,
              decay_constant=DECAY_CONSTANT):
    """``{"rho", "xm", "kx", "gradh", "divv", "curlv", "alpha", "ax", "ay",
    "az", "du"}`` at ``targets`` (numpy float32) plus the ring sizes
    ``{"ring_a", ..., "ring_d"}``, from the whole particle set. ``alpha``
    and ``dt`` are the live state's switches and last step (``min_dt``);
    ``lengths`` the box's three edges, ``periodic`` three bools; ``gamma``,
    ``cv`` and ``sinc_index`` the case's constants."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        x, y, z, vx, vy, vz, h, m, temp, alpha0 = map(
            f32, (x, y, z, vx, vy, vz, h, m, temp, alpha))
        n = x.shape[0]
        targets = np.asarray(targets)
        lengths = f32(lengths)
        periodic = tuple(bool(p) for p in periodic)
        k_norm = reference.sinc_kernel_norm(sinc_index)
        kw = dict(lengths=lengths, k_norm=k_norm,
                  sinc_index=float(sinc_index), periodic=periodic,
                  block=block, product_dtype=product_dtype)
        on = lambda idx, values: _spread(n, idx, values)

        rings = [targets]
        for _ in "abcd":
            rings.append(_ring(rings[-1], x, y, z, h, lengths, periodic,
                               block))
        ring_a, ring_b, ring_c, ring_d = rings[1:]
        a, b, c_, d = map(jnp.asarray, rings[1:])

        xm = on(ring_d, _xmass(d, x, y, z, h, m, **kw))
        kx, gradh = (on(ring_c, v) for v in
                     _kx_gradh(c_, x, y, z, h, m, xm, **kw))
        rho = kx * m / xm
        tmp = (gamma - 1.0) * cv * temp
        c = jnp.sqrt(tmp) + 0.0 * rho  # NaN outside ring C, like rho
        prho = rho * tmp / (kx * m * m * gradh)
        *cs, divv, curlv = (on(ring_b, v) for v in _iad_divv_curlv(
            b, x, y, z, vx, vy, vz, h, xm, kx, **kw))
        cs = tuple(cs)
        new_alpha = on(ring_a, _av_switches(
            a, x, y, z, vx, vy, vz, h, c, xm, kx, divv, alpha0, cs, f32(dt),
            alphamin=alphamin, alphamax=alphamax,
            decay_constant=decay_constant, **kw))
        ax, ay, az, du = _momentum_energy(
            jnp.asarray(targets), x, y, z, vx, vy, vz, h, m, prho, c, kx, xm,
            new_alpha, cs, at_min=at_min, at_max=at_max, **kw)
        t = jnp.asarray(targets)
        out = {"rho": rho[t], "xm": xm[t], "kx": kx[t], "gradh": gradh[t],
               "divv": divv[t], "curlv": curlv[t], "alpha": new_alpha[t],
               "ax": ax, "ay": ay, "az": az, "du": du}
        out = {k: np.asarray(v) for k, v in out.items()}
    out.update({"ring_" + k: len(r) for k, r in zip("abcd", rings[1:])})
    return out
