"""Smoothing kernels and per-pair closed forms.

Physics-equivalent of the reference's ``sph/kernels.hpp`` and
``sph_kernel_tables.hpp``: the sinc^n kernel family (SPHYNX,
DOI 10.1051/0004-6361/201630208), its derivative, the 3D normalization
constant, Monaghan-style artificial viscosity, the Courant signal-velocity
time step, and the neighbor-count-driven smoothing-length update.

Where the reference tabulates the kernel at 20000 points and does linear
lookups (table_lookup.hpp), the TPU build fits W as a degree-13 polynomial
in v^2 (``sinc_kernel_u``): a table gather would serialize on the VPU, and
the polynomial (a) needs no sqrt — the pair loops have d2, not dist —
(b) is 14 fused multiply-adds with no transcendental, and (c) matches the
exact kernel to ~3e-7 absolute (the f32 rounding floor, comparable to the
reference table's own interpolation+storage error). The exact ``sin``
forms below remain the accuracy reference and provide the derivative.
"""

import functools

import numpy as np
import jax.numpy as jnp

SUPPORT = 2.0  # kernel support radius in units of h

# Kernel families (the reference's SphKernelType enum,
# sph_kernel_tables.hpp:122-160, plus one non-sinc family):
#   "sinc"        — sinc(pi v / 2)^n (SPHYNX default, n = sinc_index)
#   "sinc-n1-n2"  — 0.9 sinc^4 + 0.1 sinc^9 (SincN1SincN2, fixed mix)
#   "wendland-c6" — Wendland C6 (Dehnen & Aly 2012), support 2h
KERNEL_CHOICES = ("sinc", "sinc-n1-n2", "wendland-c6")


def _kernel_samples(v: np.ndarray, n: float, kind: str) -> np.ndarray:
    """W(v) on v in [0, 2] in float64 (fit/normalization reference)."""
    def sincn(e):
        pv = 0.5 * np.pi * v
        s = np.ones_like(v)
        nz = v > 0
        s[nz] = np.sin(pv[nz]) / pv[nz]
        return s ** float(e)

    if kind == "sinc":
        return sincn(n)
    if kind == "sinc-n1-n2":
        return 0.9 * sincn(4.0) + 0.1 * sincn(9.0)
    if kind == "wendland-c6":
        q = np.clip(v / 2.0, 0.0, 1.0)
        return (1.0 - q) ** 8 * (1.0 + 8.0 * q + 25.0 * q**2 + 32.0 * q**3)
    raise ValueError(f"unknown kernel kind {kind!r} (choices: {KERNEL_CHOICES})")


@functools.lru_cache(maxsize=None)
def kernel_poly_coeffs(n: float, kind: str = "sinc", degree: int = 0) -> tuple:
    """Power coefficients of W as a polynomial in s = v^2/2 - 1.

    Sinc-family kernels are even entire functions of v, hence analytic in
    u = v^2; a Chebyshev fit on u in [0, 4] evaluated in the centered
    variable s in [-1, 1] keeps every Horner intermediate O(1), so the
    f32 evaluation stays at the ~3e-7 rounding floor (a plain fit in u
    overflows to ~5e-5 through coefficient cancellation). Works for any
    real exponent n — the reference's integer-n table restriction
    (sph_kernel_tables.hpp:122-160) does not apply. Wendland C6 has odd
    powers of v (C^6 at the origin in u), so it gets a higher degree;
    its fit error is ~2e-6 (pinned by tests/test_kernels).
    """
    if degree == 0:
        degree = 13 if kind.startswith("sinc") else 19
    t = np.cos(np.linspace(0.0, np.pi, 4000))  # [-1, 1] chebyshev nodes
    u = 2.0 * (t + 1.0)  # [0, 4]
    w = _kernel_samples(np.sqrt(u), float(n), kind)
    cheb = np.polynomial.chebyshev.Chebyshev.fit(t, w, degree, domain=[-1, 1])
    coeffs = cheb.convert(kind=np.polynomial.Polynomial).coef
    return tuple(float(c) for c in coeffs)


def sinc_poly_coeffs(n: float, degree: int = 13) -> tuple:
    """Back-compat alias: the default sinc-family fit."""
    return kernel_poly_coeffs(n, "sinc", degree)


def sinc_poly_eval(u, coeffs):
    """Horner evaluation of a ``sinc_poly_coeffs`` fit from the SQUARED
    normalized distance u = (dist/h)^2: clamped to the support, floored at
    0 (the fit crosses ~-3e-7 in the flat tail near the support edge).
    SINGLE implementation shared by the XLA ops and the Pallas tile
    kernels so both paths compute identical W."""
    s = jnp.clip(u * 0.5 - 1.0, -1.0, 1.0)
    acc = jnp.full_like(s, coeffs[-1])
    for c in coeffs[-2::-1]:
        acc = acc * s + c
    return jnp.maximum(acc, 0.0)


def sinc_kernel_u(u, n: float = 6.0, kind: str = "sinc"):
    """W from the SQUARED normalized distance (polynomial form, see
    kernel_poly_coeffs; the name keeps the historical sinc default)."""
    return sinc_poly_eval(u, kernel_poly_coeffs(float(n), kind))


@functools.lru_cache(maxsize=None)
def kernel_dterh_coeffs(n: float, kind: str = "sinc", degree: int = 0) -> tuple:
    """Coefficients of dterh(v) = -(3 W + v dW/dv) in s = v^2/2 - 1.

    The h-derivative combination of ve_def_gradh_kern.hpp:58-66, derived
    ANALYTICALLY from the W fit: with W = p(s), v dW/dv = 2(s+1) p'(s),
    so dterh = -(3 p + 2(s+1) p') — exactly consistent with the W the
    pair ops evaluate (f32 error ~2e-6, and dterh(0) = -3 by
    construction)."""
    c = kernel_poly_coeffs(n, kind, degree)
    d = []
    for k in range(len(c)):
        v = (3.0 + 2.0 * k) * c[k]
        if k + 1 < len(c):
            v += 2.0 * (k + 1) * c[k + 1]
        d.append(-v)
    return tuple(d)


def dterh_poly_eval(u, coeffs):
    """Horner in s = u/2 - 1 WITHOUT the zero floor (dterh is negative
    inside the support). SINGLE evaluator shared by the XLA ops and the
    Pallas tile kernels (mirror of sinc_poly_eval)."""
    s = jnp.clip(u * 0.5 - 1.0, -1.0, 1.0)
    acc = jnp.full_like(s, coeffs[-1])
    for c in coeffs[-2::-1]:
        acc = acc * s + c
    return acc


def sinc_dterh_u(u, n: float = 6.0, kind: str = "sinc"):
    """dterh = -(3 W + v dW/dv) from the SQUARED normalized distance."""
    return dterh_poly_eval(u, kernel_dterh_coeffs(float(n), kind))


def sinc_kernel(v, n: float = 6.0):
    """W_n(v) = sinc(pi/2 * v)^n on v in [0, 2]; 0 outside.

    v is dist/h. Clamping to the support makes out-of-range j-side
    evaluations (h_j < h_i) return exactly 0.
    """
    v = jnp.clip(v, 0.0, SUPPORT)
    pv = (0.5 * jnp.pi) * v
    sinc = jnp.where(v > 0.0, jnp.sin(pv) / jnp.where(v > 0.0, pv, 1.0), 1.0)
    return sinc**n


def sinc_kernel_derivative(v, n: float = 6.0):
    """dW_n/dv = n * sinc^(n-1)(pi/2 v) * d sinc/dv; 0 at v=0 and v>=2."""
    v = jnp.clip(v, 0.0, SUPPORT)
    pv = (0.5 * jnp.pi) * v
    safe_pv = jnp.where(v > 0.0, pv, 1.0)
    sinc = jnp.where(v > 0.0, jnp.sin(pv) / safe_pv, 1.0)
    # d/dv sinc(pi/2 v) = sinc * (pi/2) * (cot(pv) - 1/pv)
    dsinc = sinc * (0.5 * jnp.pi) * (
        jnp.cos(pv) / jnp.where(v > 0.0, jnp.sin(pv), 1.0) - 1.0 / safe_pv
    )
    return jnp.where(v > 0.0, n * sinc ** (n - 1.0) * dsinc, 0.0)


def kernel_norm_3d(n: float = 6.0, kind: str = "sinc",
                   support: float = SUPPORT, num: int = 20001) -> float:
    """3D normalization K with ∫ K W(|x|/h) h^-3 d^3x = 1.

    Same quantity as the reference's kernel_3D_k (sph_kernel_tables.hpp:77-84),
    computed here with numpy float64 Simpson integration at config time.
    """
    if num % 2 == 0:
        num += 1  # composite Simpson needs an even interval count
    x = np.linspace(0.0, support, num)
    f = 4.0 * np.pi * x**2 * _kernel_samples(x, n, kind)
    dx = x[1] - x[0]
    integral = dx / 3.0 * (f[0] + f[-1] + 4.0 * f[1:-1:2].sum() + 2.0 * f[2:-1:2].sum())
    return float(1.0 / integral)


def artificial_viscosity(alpha_i, alpha_j, c_i, c_j, w_ij, beta: float = 2.0):
    """Monaghan signal-velocity artificial viscosity (kernels.hpp:60-84).

    w_ij is the pair velocity projected on the separation axis; only
    approaching pairs (w_ij < 0) dissipate.
    """
    v_signal = 0.25 * (alpha_i + alpha_j) * (c_i + c_j) - beta * w_ij
    return jnp.where(w_ij < 0.0, -v_signal * w_ij, 0.0)


def ts_k_courant(maxvsignal, h, c, k_cour):
    """Courant time step from the max signal velocity (kernels.hpp:9-16)."""
    v = jnp.where(maxvsignal > 0.0, maxvsignal, c)
    return k_cour * h / v


_H_C0 = 1023.0
_H_EXP = 0.1


def update_h(ng0: int, nc, h):
    """Nudge h so the neighbor count drifts toward ng0 (kernels.hpp:18-32).

    nc includes the particle itself, like the reference's usage.
    """
    return h * 0.5 * (1.0 + _H_C0 * ng0 / jnp.maximum(nc, 1)) ** _H_EXP


def h_fixed_point(h_before: float, h_after: float) -> float:
    """Where one particle's ``update_h`` is heading, read back from one
    application of it (host arithmetic on two fetched scalars).

    ``update_h`` is a function of ``nc / ng0`` alone, so the step's
    growth ``g = h_after / h_before`` gives ``ng0 / nc = ((2g)^(1/exp)
    - 1) / c0``, and the neighbour count scales with h^3: the update
    stops moving at ``h_before * cbrt(ng0 / nc)``. ``g = 1`` returns
    ``h_before``. An estimate, not a bound: ``nc`` is a step function of
    ``h`` on a lattice."""
    g = h_after / h_before
    ratio = ((2.0 * g) ** (1.0 / _H_EXP) - 1.0) / _H_C0
    return h_before * max(ratio, 0.0) ** (1.0 / 3.0)
