"""Evolved 6-species primordial chemistry (H / H+ / He / He+ / He++ / e).

Replaces the CIE table's diagnostic-only fractions with a jitted
non-equilibrium network — the role of the reference's GRACKLE solver
(physics/cooling/cooler.cpp:313 solve_chemistry: species ODEs + cooling
integrated per particle each step; species list
cooling/chemistry_data.hpp:47-116). The TPU transposition keeps the
structure jit-friendly: fixed subcycle count (lax.scan), sequential
semi-implicit species updates (the Anninos et al. 1997 scheme GRACKLE
itself uses), and all unit conversions folded into two host-side
prefactors so every traced value stays in float32-safe magnitudes.

Reactions (collisional ionization + radiative/dielectronic
recombination; rate fits are the standard Cen 1992 / Katz, Weinberg &
Hernquist 1996 forms, also used by GRACKLE's primordial_chemistry=1):

    HI   + e -> HII   + 2e      k1      HII   + e -> HI   (+ photon) k2
    HeI  + e -> HeII  + 2e      k3      HeII  + e -> HeI  (incl. di) k4
    HeII + e -> HeIII + 2e      k5      HeIII + e -> HeII            k6

Cooling channels tied to the species (KWH96 Table 1): collisional
excitation (HI, HeII), collisional ionization (HI, HeI, HeII),
recombination (HII, HeII incl. dielectronic, HeIII), bremsstrahlung.

Number bookkeeping: species are MASS fractions (ChemistryData); the
solver works in per-mass number fractions y_X = X / A_X (O(1)) so the
only density scale is rho itself:

    n_X = rho_cgs * y_X / m_H
    dy/dt[code]   = k(T) * y_e * rho_code * R0,  R0 = rho_to_cgs/m_H * t_code
    du/dt[code]   = -rho_code * C0 * sum y_e * y_X * lam24(T),
                    C0 = rho_to_cgs/m_H^2 * t_code/u_to_cgs * 1e-24

with lam24 = Lambda * 1e24 (O(1)) and R0/C0 computed host-side in f64.
"""

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.physics.cooling import (
    KB, MH, ChemistryData, CoolingConfig, implicit_u_subcycle, u_to_temp,
)


# ---------------------------------------------------------------------------
# rate coefficients [cm^3/s] (Cen 1992; KWH96 eqs. 24-30)
# ---------------------------------------------------------------------------


def _t5(T):
    return 1.0 + jnp.sqrt(T * 1e-5)


def k1_ci_hi(T):
    """HI collisional ionization."""
    return 5.85e-11 * jnp.sqrt(T) / _t5(T) * jnp.exp(-157809.1 / T)


def k2_rec_hii(T):
    """HII radiative recombination (case A)."""
    return (8.4e-11 / jnp.sqrt(T) * (T * 1e-3) ** -0.2
            / (1.0 + (T * 1e-6) ** 0.7))


def k3_ci_hei(T):
    """HeI collisional ionization."""
    return 2.38e-11 * jnp.sqrt(T) / _t5(T) * jnp.exp(-285335.4 / T)


def k4_rec_heii(T):
    """HeII recombination: radiative + dielectronic."""
    rad = 1.5e-10 * T ** -0.6353
    di = (1.9e-3 * T ** -1.5 * jnp.exp(-470000.0 / T)
          * (1.0 + 0.3 * jnp.exp(-94000.0 / T)))
    return rad + di


def k5_ci_heii(T):
    """HeII collisional ionization."""
    return 5.68e-12 * jnp.sqrt(T) / _t5(T) * jnp.exp(-631515.0 / T)


def k6_rec_heiii(T):
    """HeIII radiative recombination."""
    return (3.36e-10 / jnp.sqrt(T) * (T * 1e-3) ** -0.2
            / (1.0 + (T * 1e-6) ** 0.7))


# ---------------------------------------------------------------------------
# cooling channels: Lambda * 1e24 [erg cm^3/s], per n_e * n_X (KWH96 T.1)
# ---------------------------------------------------------------------------


def lam24_channels(T):
    """Dict of per-(n_e n_X) cooling fits scaled by 1e24; key = which
    species' number fraction multiplies the channel."""
    sq = jnp.sqrt(T)
    return {
        # collisional excitation
        "ce_hi": 7.50e5 * jnp.exp(-118348.0 / T) / _t5(T),          # x n_HI
        "ce_heii": (5.54e7 * T ** -0.397 * jnp.exp(-473638.0 / T)
                    / _t5(T)),                                       # x n_HeII
        # collisional ionization
        "ci_hi": 1.27e3 * sq * jnp.exp(-157809.1 / T) / _t5(T),      # x n_HI
        "ci_hei": 9.38e2 * sq * jnp.exp(-285335.4 / T) / _t5(T),     # x n_HeI
        "ci_heii": 4.95e2 * sq * jnp.exp(-631515.0 / T) / _t5(T),    # x n_HeII
        # recombination
        "rec_hii": (8.70e-3 * sq * (T * 1e-3) ** -0.2
                    / (1.0 + (T * 1e-6) ** 0.7)),                    # x n_HII
        "rec_heii": 1.55e-2 * T ** 0.3647,                           # x n_HeII
        "rec_heiii": (3.48e-2 * sq * (T * 1e-3) ** -0.2
                      / (1.0 + (T * 1e-6) ** 0.7)),                  # x n_HeIII
        "di_heii": (1.24e11 * T ** -1.5 * jnp.exp(-470000.0 / T)
                    * (1.0 + 0.3 * jnp.exp(-94000.0 / T))),          # x n_HeII
        # bremsstrahlung (g_ff = 1.3), x (n_HII + n_HeII + 4 n_HeIII)
        "brem": 1.42e-3 * 1.3 * sq,
    }


def species_cooling24(T, y):
    """sum over channels of y_e * y_X * lam24(T): the composition-resolved
    CIE/non-equilibrium cooling function (per rho_code * C0)."""
    lam = lam24_channels(T)
    ye = y["e"]
    return ye * (
        lam["ce_hi"] * y["hi"] + lam["ce_heii"] * y["heii"]
        + lam["ci_hi"] * y["hi"] + lam["ci_hei"] * y["hei"]
        + lam["ci_heii"] * y["heii"]
        + lam["rec_hii"] * y["hii"]
        + (lam["rec_heii"] + lam["di_heii"]) * y["heii"]
        + lam["rec_heiii"] * y["heiii"]
        + lam["brem"] * (y["hii"] + y["heii"] + 4.0 * y["heiii"])
    )


def metal_cooling24(T, metal, cfg, x_h: Optional[float] = None):
    """Metal-line cooling on top of the primordial network — the
    GRACKLE decomposition (primordial network + Cloudy metal table,
    cooler.cpp metal_cooling flag): the metal channel is the RESIDUAL
    of the solar-metallicity CIE table over the primordial network's
    own equilibrium cooling at the same T, scaled linearly in the
    particle's metal mass fraction. Returns the lam24-normalized rate
    per (rho/m_H)^2 (the same units species_cooling24 uses).

    ``x_h`` defaults to ``cfg.hydrogen_fraction`` so a non-default
    composition gets the matching n_H^2 conversion (it used to
    hard-code 0.76, silently mis-scaling the table rate for any other
    CoolingConfig — ADVICE round 5)."""
    from sphexa_tpu.physics.cooling import _log_lambda_cie

    if x_h is None:
        x_h = cfg.hydrogen_fraction
    # table rate is per n_H^2 = (x_h rho/m_H)^2; convert to per
    # (rho/m_H)^2 with x_h^2
    lam_cie24 = 10.0 ** (_log_lambda_cie(T, cfg) + 24.0) * x_h**2
    eq = equilibrium_fractions(T, x_h, 1.0 - x_h)
    lam_prim24 = species_cooling24(T, eq)
    Z_SUN = 0.0122
    return jnp.maximum(lam_cie24 - lam_prim24, 0.0) * (metal / Z_SUN)


def equilibrium_fractions(T, x_h, x_he):
    """Analytic CIE ionization balance at temperature T: the fixed point
    the subcycled network must relax to (rate ratios only — density
    cancels). Returns the y-dict of per-mass number fractions."""
    r_h = k1_ci_hi(T) / k2_rec_hii(T)          # y_HII / y_HI
    r_he1 = k3_ci_hei(T) / k4_rec_heii(T)      # y_HeII / y_HeI
    r_he2 = k5_ci_heii(T) / k6_rec_heiii(T)    # y_HeIII / y_HeII
    y_h = x_h
    y_hi = y_h / (1.0 + r_h)
    y_hii = y_h - y_hi
    y_he = x_he / 4.0
    d = 1.0 + r_he1 + r_he1 * r_he2
    y_hei = y_he / d
    y_heii = y_hei * r_he1
    y_heiii = y_heii * r_he2
    return dict(hi=y_hi, hii=y_hii, hei=y_hei, heii=y_heii,
                heiii=y_heiii, e=y_hii + y_heii + 2.0 * y_heiii)


# ---------------------------------------------------------------------------
# the solver
# ---------------------------------------------------------------------------


def _prefactors(cfg: CoolingConfig):
    """(R0, C0) host-side f64 -> f32 unit folds (module docstring)."""
    r0 = cfg.rho_to_cgs / MH * cfg.t_code_s
    c0 = cfg.rho_to_cgs / MH**2 * cfg.t_code_s / cfg.u_to_cgs * 1e-24
    return np.float32(r0), np.float32(c0)


def _y_of(chem: ChemistryData):
    return dict(
        hi=chem.hi, hii=chem.hii,
        hei=chem.hei / 4.0, heii=chem.heii / 4.0, heiii=chem.heiii / 4.0,
        e=chem.e,
    )


def _mu_of_y(y, metal):
    inv_mu = (y["hi"] + y["hii"] + y["hei"] + y["heii"] + y["heiii"]
              + y["e"] + metal / 2.0)
    return 1.0 / jnp.maximum(inv_mu, 1e-10)


def _species_update(y, T, a, x_h, y_he_tot):
    """One network subcycle at temperature T with the dimensionless
    rate factor a = dt * n_H-equivalent * y_e.

    Each ionization pair is solved IMPLICITLY THROUGH ITS CLOSURE
    (substitute y_HII = X - y_HI into the backward-Euler update before
    solving), so stiff a*k factors relax monotonically to the exact
    balance instead of oscillating around it — the stability refinement
    of the Anninos et al. 1997 sequential scheme for subcycles much
    longer than the fastest reaction time. Fixed points are the exact
    CIE balances (k1 y_HI = k2 y_HII etc.; see
    tests/test_cooling.py::TestPrimordialNetwork)."""
    k1, k2 = k1_ci_hi(T), k2_rec_hii(T)
    y_hi = (y["hi"] + a * k2 * x_h) / (1.0 + a * (k1 + k2))
    y_hi = jnp.clip(y_hi, 0.0, x_h)
    y_hii = x_h - y_hi

    k3, k4 = k3_ci_hei(T), k4_rec_heii(T)
    k5, k6 = k5_ci_heii(T), k6_rec_heiii(T)
    y_hei = ((y["hei"] + a * k4 * y["heii"]) / (1.0 + a * k3))
    y_hei = jnp.clip(y_hei, 0.0, y_he_tot)
    # HeII: k6-recombination creation made implicit through the HeIII
    # closure (y_HeIII = Y - y_HeI - y_HeII) — same fixed point,
    # oscillation-free at large a*k6
    y_heii = ((y["heii"] + a * (k3 * y_hei + k6 * (y_he_tot - y_hei)))
              / (1.0 + a * (k4 + k5 + k6)))
    y_heii = jnp.clip(y_heii, 0.0, y_he_tot - y_hei)
    y_heiii = y_he_tot - y_hei - y_heii
    return dict(hi=y_hi, hii=y_hii, hei=y_hei, heii=y_heii,
                heiii=y_heiii, e=y_hii + y_heii + 2.0 * y_heiii)


def relax_to_equilibrium(T, rho_code, chem: ChemistryData,
                         cfg: CoolingConfig, dt_sub, steps: int = 2048):
    """Species-only relaxation at FIXED temperature: the CIE
    equilibrium limit (test pin) and an equilibrium-IC generator.
    ``dt_sub`` is the per-subcycle code-time step; pick it so the
    fastest rate factor a*k stays O(<=1)."""
    r0, _ = _prefactors(cfg)
    x_h = chem.hi + chem.hii
    y_he_tot = (chem.hei + chem.heii + chem.heiii) / 4.0
    dens = rho_code * r0

    def body(y, _):
        a = dt_sub * dens * y["e"]
        return _species_update(y, T, a, x_h, y_he_tot), None

    y_fin, _ = jax.lax.scan(body, _y_of(chem), None, length=steps)
    return ChemistryData(
        hi=y_fin["hi"], hii=y_fin["hii"],
        hei=y_fin["hei"] * 4.0, heii=y_fin["heii"] * 4.0,
        heiii=y_fin["heiii"] * 4.0, e=y_fin["e"], metal=chem.metal,
    )


def evolve_primordial(dt, rho_code, u_code, chem: ChemistryData,
                      cfg: CoolingConfig):
    """Subcycled coupled (species, energy) update over one step.

    Per subcycle (cooler.cpp solve_chemistry structure, jit-shaped):
    T from (u, mu) -> rates -> sequential semi-implicit species updates
    with exact closure (HII = X - HI; HeIII = Y/4 - HeI - HeII;
    e from charge balance) -> species-resolved + metal-residual cooling
    (metal_cooling24: the CIE-table residual over the network's own
    equilibrium, scaled by the particle's metal fraction — the GRACKLE
    network+metal-table decomposition) -> positivity-preserving
    implicit u update (cooling.implicit_u_subcycle, whose rates are
    accumulated beside u: du_avg is their mean, never (u_fin - u) / dt).
    Returns (du_avg, new ChemistryData); the metal FRACTION itself passes
    through unevolved.
    """
    r0, c0 = _prefactors(cfg)
    sub = cfg.substeps
    dt_sub = dt / sub
    x_h = chem.hi + chem.hii
    y_he_tot = (chem.hei + chem.heii + chem.heiii) / 4.0
    metal = chem.metal

    def body(carry, _):
        u, acc, y = carry
        mu = _mu_of_y(y, metal)
        T = jnp.maximum(u_to_temp(u, mu, cfg), 10.0)
        dens = rho_code * r0  # k * dens * y_e = dy/dt per code time
        a = dt_sub * dens * y["e"]
        y_new = _species_update(y, T, a, x_h, y_he_tot)

        # species-resolved cooling, implicit positivity-preserving in u
        cool = rho_code * c0 * (
            species_cooling24(T, y_new) + metal_cooling24(T, metal, cfg)
        )
        u_new, rate = implicit_u_subcycle(u, dt_sub, cool, cfg.heating_code)
        return (u_new, acc + rate, y_new), None

    carry0 = (u_code, jnp.zeros_like(u_code), _y_of(chem))
    (_, acc, y_fin), _ = jax.lax.scan(body, carry0, None, length=sub)
    new_chem = ChemistryData(
        hi=y_fin["hi"], hii=y_fin["hii"],
        hei=y_fin["hei"] * 4.0, heii=y_fin["heii"] * 4.0,
        heiii=y_fin["heiii"] * 4.0,
        e=y_fin["e"], metal=metal,
    )
    return acc / sub, new_chem


def primordial_cooling_timestep(rho_code, u_code, chem: ChemistryData,
                                cfg: CoolingConfig):
    """ct_crit * min |u / du_dt| with the species-resolved rate
    (eos_cooling.hpp:12-25 contract, network flavor)."""
    r0, c0 = _prefactors(cfg)
    y = _y_of(chem)
    mu = _mu_of_y(y, chem.metal)
    T = jnp.maximum(u_to_temp(u_code, mu, cfg), 10.0)
    dudt = (rho_code * c0 * (species_cooling24(T, y)
                             + metal_cooling24(T, chem.metal, cfg))
            - cfg.heating_code)
    tc = jnp.abs(u_code / jnp.where(jnp.abs(dudt) > 0, dudt, 1e-30))
    return cfg.ct_crit * jnp.min(tc)
