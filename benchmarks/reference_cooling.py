"""Plain reference of the cooling source the ``std-cooling`` step integrates,
for the comparison that holds ``sphexa_tpu/physics/primordial.py`` and
``physics/cooling.py`` to the configuration's cooling limits.

Float64 numpy, written from the published fits and importing nothing from
``sphexa_tpu.physics``: the same operations on the same data must give the
same answers. Everything the model is made of is an ARGUMENT here (``model``:
the configuration file's own ``cooling`` block: units, composition, the
13-point table, ``ct_crit``, the subcycle count), so what is compared is the
deployment the configuration states and not the program's defaults.

What is computed, and where it is published:

- ``rates``: the six rate coefficients [cm^3/s] of Cen (1992) as tabulated by
  Katz, Weinberg & Hernquist (1996, KWH96) eqs. 24-30: collisional ionisation
  of HI, HeI, HeII and recombination of HII, HeII (radiative + dielectronic),
  HeIII;
- ``channels``: the ten cooling channels of KWH96 table 1, times 1e24:
  collisional excitation (HI, HeII), collisional ionisation (HI, HeI, HeII),
  recombination (HII, HeII, HeIII), dielectronic recombination (HeII),
  bremsstrahlung with g_ff = 1.3;
- ``equilibrium``: the collisional ionisation balance at T (ratios of rates);
- ``metal_residual``: solar-metallicity CIE table (n_H^2 Lambda) less the
  network's own equilibrium cooling at the same T, never negative, linear in
  the particle's metal mass fraction over Z_sun = 0.0122;
- ``step``: ``substeps`` fixed backward-Euler subcycles of the species with
  each ionisation pair closed on its element total before it is solved
  (Anninos et al. 1997's sequential scheme, as the program's docstring
  describes its refinement), then the energy update u' = u / (1 + dt L / u)
  + dt H; the step-averaged source is the SUM OF THE SUBCYCLES' DECREMENTS
  over dt;
- ``cooling_time`` and ``dt_cool``: |u / du_dt| and ``ct_crit`` times its min.

Departures from the published description, each because the deployment
(configuration ``assumed``) defines it so: GRACKLE's adaptive subcycling is
eight fixed subcycles; the metal channel is the table's residual and not a
Cloudy table; T is floored at 10 K; no UV background, no H2 / HD / dust.
And one that is this file's alone: the source is never formed as
``(u_final - u) / dt``. At dt 1e-10 that difference is 5e-13 of u 1.5, four
ulp of a float64: the reference itself would be good to 25 %.

The two controls the limits must refuse:

- ``step(..., round_to=bfloat16)``: T, every rate and every channel rounded
  to bf16 before use, a lower precision than the configuration's float32;
- ``differenced_f32``: what ``(u_final - u) / dt`` reads when ``u`` is carried
  through the subcycles in IEEE float32: the parent program's form.
"""

import numpy as np

# cgs constants (CODATA 1986 values, the ones upstream's cooler was built on)
KB = 1.380658e-16    # erg / K
MH = 1.6726231e-24   # g
G_CGS = 6.6726e-8    # cm^3 g^-1 s^-2
MSUN = 1.98892e33    # g
KPC = 3.0856776e21   # cm
Z_SUN = 0.0122

SPECIES = ("hi", "hii", "hei", "heii", "heiii", "e")
#: nucleons per particle of each species' mass fraction (``e`` is carried
#: as a per-mass NUMBER fraction already)
WEIGHT = {"hi": 1.0, "hii": 1.0, "hei": 4.0, "heii": 4.0, "heiii": 4.0,
          "e": 1.0}


def units(model):
    """Code -> cgs factors of the G = 1 unit system the model states:
    ``(t_code_s, rho_to_cgs, u_to_cgs)``."""
    m = model["m_code_in_ms"] * MSUN
    length = model["l_code_in_kpc"] * KPC
    t = np.sqrt(length**3 / (G_CGS * m))
    return t, m / length**3, (length / t) ** 2


def _as(round_to):
    """Identity, or rounding through ``round_to`` and back to float64."""
    if round_to is None:
        return lambda a: a
    return lambda a: np.asarray(a).astype(round_to).astype(np.float64)


def _shapes(T):
    """The three temperature shapes the fits share: sqrt(T), the
    1 + sqrt(T / 1e5) cut-off of the collisional fits, and the
    recombination fits' (T / 1e3)^-0.2 / (1 + (T / 1e6)^0.7)."""
    return (np.sqrt(T), 1.0 + np.sqrt(T / 1e5),
            (T / 1e3) ** -0.2 / (1.0 + (T / 1e6) ** 0.7))


def _heating(model):
    """The constant heating X Gamma / m_H in code units per code time."""
    t_code, _, u_to_cgs = units(model)
    return (model["hydrogen_fraction"] * model["heating_rate"] / MH
            * t_code / u_to_cgs)


def rates(T, rnd=lambda a: a):
    """(k1..k6) [cm^3/s] at temperature T [K] (KWH96 eqs. 24-30)."""
    root, t5, rec = _shapes(T)
    k1 = 5.85e-11 * root * np.exp(-157809.1 / T) / t5
    k2 = 8.4e-11 / root * rec
    k3 = 2.38e-11 * root * np.exp(-285335.4 / T) / t5
    k4 = (1.5e-10 * T**-0.6353
          + 1.9e-3 * T**-1.5 * np.exp(-470000.0 / T)
          * (1.0 + 0.3 * np.exp(-94000.0 / T)))
    k5 = 5.68e-12 * root * np.exp(-631515.0 / T) / t5
    k6 = 3.36e-10 / root * rec
    return tuple(rnd(k) for k in (k1, k2, k3, k4, k5, k6))


def channels(T, rnd=lambda a: a):
    """The ten channels [1e-24 erg cm^3/s] per n_e n_X (KWH96 table 1), as
    ``{name: (value, species it multiplies)}``."""
    root, t5, rec = _shapes(T)
    out = {
        "ce_hi": (7.50e5 * np.exp(-118348.0 / T) / t5, ("hi",)),
        "ce_heii": (5.54e7 * T**-0.397 * np.exp(-473638.0 / T) / t5,
                    ("heii",)),
        "ci_hi": (1.27e3 * root * np.exp(-157809.1 / T) / t5, ("hi",)),
        "ci_hei": (9.38e2 * root * np.exp(-285335.4 / T) / t5, ("hei",)),
        "ci_heii": (4.95e2 * root * np.exp(-631515.0 / T) / t5, ("heii",)),
        "rec_hii": (8.70e-3 * root * rec, ("hii",)),
        "rec_heii": (1.55e-2 * T**0.3647, ("heii",)),
        "rec_heiii": (3.48e-2 * root * rec, ("heiii",)),
        "di_heii": (1.24e11 * T**-1.5 * np.exp(-470000.0 / T)
                    * (1.0 + 0.3 * np.exp(-94000.0 / T)), ("heii",)),
        # free-free on every ion, charge squared: HII, HeII, 4 HeIII
        "brem": (1.42e-3 * 1.3 * root, ("hii", "heii", "heiii4")),
    }
    return {k: (rnd(v), on) for k, (v, on) in out.items()}


def species_cooling(T, y, rnd=lambda a: a, skip=()):
    """sum over the channels of y_e y_X lam24(T), per (rho / m_H)^2 1e-24;
    ``skip`` names channels left out (a test's control)."""
    weight = {**y, "heiii4": 4.0 * y["heiii"]}
    total = 0.0
    for name, (lam, on) in channels(T, rnd).items():
        if name not in skip:
            total = total + lam * sum(weight[s] for s in on)
    return y["e"] * total


def equilibrium(T, x_h, x_he, rnd=lambda a: a):
    """Per-mass number fractions of the collisional ionisation balance at
    T for hydrogen mass fraction ``x_h`` and helium ``x_he``."""
    k1, k2, k3, k4, k5, k6 = rates(T, rnd)
    hii_over_hi = k1 / k2
    heii_over_hei = k3 / k4
    heiii_over_heii = k5 / k6
    hi = x_h / (1.0 + hii_over_hi)
    hei = (x_he / 4.0) / (1.0 + heii_over_hei
                          + heii_over_hei * heiii_over_heii)
    heii = hei * heii_over_hei
    heiii = heii * heiii_over_heii
    hii = x_h - hi
    return {"hi": hi, "hii": hii, "hei": hei, "heii": heii, "heiii": heiii,
            "e": hii + heii + 2.0 * heiii}


def table_log_lambda(T, model):
    """log10 of the CIE table's n_H^2 Lambda [erg cm^3/s] at T: piecewise
    linear in (log T, log Lambda), nothing below the table's first point,
    the last value beyond its last."""
    return np.interp(np.log10(np.maximum(T, 1.0)), model["logT_table"],
                     model["logL_table"], left=-60.0,
                     right=model["logL_table"][-1])


def metal_residual(T, metal, model, rnd=lambda a: a):
    """The metal channel per (rho / m_H)^2 1e-24."""
    x_h = model["hydrogen_fraction"]
    table = rnd(10.0 ** (table_log_lambda(T, model) + 24.0)) * x_h**2
    primordial = species_cooling(T, equilibrium(T, x_h, 1.0 - x_h, rnd), rnd)
    return np.maximum(table - primordial, 0.0) * (metal / Z_SUN)


def number_fractions(chem):
    """ChemistryData's mass fractions -> per-mass number fractions y."""
    return {s: np.asarray(chem[s], np.float64) / WEIGHT[s] for s in SPECIES}


def temperature(u, y, metal, model, rnd=lambda a: a):
    """T [K] of specific energy u [code] at composition y, floored at 10."""
    _, _, u_to_cgs = units(model)
    inv_mu = sum(y[s] for s in SPECIES) + metal / 2.0
    T = (model["gamma"] - 1.0) * MH * u * u_to_cgs / (KB * inv_mu)
    return rnd(np.maximum(T, 10.0))


def cooling_rate(rho, T, y, metal, model, rnd=lambda a: a, skip=()):
    """(cool, heat) [code energy per mass and time], each >= 0, at density
    rho [code], temperature T [K] and composition y."""
    t_code, rho_to_cgs, u_to_cgs = units(model)
    c0 = rho_to_cgs / MH**2 * t_code / u_to_cgs * 1e-24
    lam = species_cooling(T, y, rnd, skip) + metal_residual(T, metal, model,
                                                            rnd)
    return rho * c0 * lam, _heating(model)


def species_subcycle(y, T, a, x_h, y_he, rnd=lambda a: a):
    """Backward Euler over one subcycle at rate factor a = dt n y_e, every
    pair closed on its element total (H: HII = X - HI; He: HeIII = Y/4 -
    HeI - HeII; e from charge)."""
    k1, k2, k3, k4, k5, k6 = rates(T, rnd)
    hi = np.clip((y["hi"] + a * k2 * x_h) / (1.0 + a * (k1 + k2)), 0.0, x_h)
    hii = x_h - hi
    hei = np.clip((y["hei"] + a * k4 * y["heii"]) / (1.0 + a * k3), 0.0,
                  y_he)
    heii = ((y["heii"] + a * (k3 * hei + k6 * (y_he - hei)))
            / (1.0 + a * (k4 + k5 + k6)))
    heii = np.clip(heii, 0.0, y_he - hei)
    heiii = y_he - hei - heii
    return {"hi": hi, "hii": hii, "hei": hei, "heii": heii, "heiii": heiii,
            "e": hii + heii + 2.0 * heiii}


def step(dt, rho, u, chem, model, substeps=None, round_to=None, skip=(),
         evolve_species=True):
    """One step's cooling source at every row of (rho, u, chem):
    ``(du_avg, fractions, decrements)``: the step-averaged du/dt, the new
    mass fractions ``{species: array}``, and the (substeps, N) energy
    changes of the subcycles, whose sum over dt ``du_avg`` is.

    ``evolve_species=False`` is the table mode: fractions pass through, the
    rate is the table's alone at mu from the mass fractions."""
    rnd = _as(round_to)
    n_sub = model["substeps"] if substeps is None else substeps
    t_code, rho_to_cgs, _ = units(model)
    r0 = rho_to_cgs / MH * t_code
    rho = np.asarray(rho, np.float64)
    u = np.asarray(u, np.float64)
    metal = np.asarray(chem["metal"], np.float64)
    y = number_fractions(chem)
    x_h = y["hi"] + y["hii"]
    y_he = y["hei"] + y["heii"] + y["heiii"]
    dt_sub = np.float64(dt) / n_sub
    decrements = np.empty((n_sub,) + u.shape)
    for k in range(n_sub):
        if evolve_species:
            # one temperature a subcycle: the species move at it, and the
            # moved species cool at it
            T = temperature(u, y, metal, model, rnd)
            y = species_subcycle(y, T, dt_sub * rho * r0 * y["e"], x_h, y_he,
                                 rnd)
            cool, heat = cooling_rate(rho, T, y, metal, model, rnd, skip)
        else:
            cool, heat = table_rate(rho, u, chem, model, rnd)
        dec = -dt_sub * cool / (1.0 + dt_sub * cool / u) + dt_sub * heat
        decrements[k] = dec
        u = u + dec
    fractions = {s: y[s] * WEIGHT[s] for s in SPECIES}
    fractions["metal"] = metal
    return decrements.sum(axis=0) / np.float64(dt), fractions, decrements


def table_rate(rho, u, chem, model, rnd=lambda a: a):
    """(cool, heat) of the table mode: n_H^2 Lambda(T) / rho at the mean
    molecular weight of the MASS fractions (metals count half a particle a
    nucleon), no floor on T but the table's own."""
    t_code, rho_to_cgs, u_to_cgs = units(model)
    inv_mu = (chem["hi"] + 2.0 * chem["hii"] + chem["hei"] / 4.0
              + chem["heii"] / 2.0 + 0.75 * chem["heiii"]
              + chem["metal"] / 2.0)
    T = rnd((model["gamma"] - 1.0) * MH * u * u_to_cgs / (KB * inv_mu))
    prefac = ((model["hydrogen_fraction"] / MH) ** 2 * rho_to_cgs * t_code
              / u_to_cgs)
    net = _heating(model) - rnd(10.0 ** table_log_lambda(T, model)) * prefac * rho
    return np.maximum(-net, 0.0), np.maximum(net, 0.0)


def cooling_time(rho, u, chem, model, evolve_species=True):
    """|u / du_dt| of every row, at the state as it stands."""
    rho = np.asarray(rho, np.float64)
    u = np.asarray(u, np.float64)
    if evolve_species:
        y = number_fractions(chem)
        metal = np.asarray(chem["metal"], np.float64)
        cool, heat = cooling_rate(rho, temperature(u, y, metal, model), y,
                                  metal, model)
    else:
        cool, heat = table_rate(rho, u, {k: np.asarray(v, np.float64)
                                         for k, v in chem.items()}, model)
    dudt = cool - heat
    return np.abs(u / np.where(np.abs(dudt) > 0, dudt, 1e-30))


def dt_cool(rho, u, chem, model, evolve_species=True):
    """The limiter: ``ct_crit`` times the shortest cooling time."""
    return model["ct_crit"] * cooling_time(rho, u, chem, model,
                                           evolve_species).min()


def differenced_f32(u, decrements, dt):
    """The parent program's form of the source: ``u`` carried through the
    subcycles in IEEE float32, then ``(u_final - u) / dt``."""
    u0 = np.asarray(u, np.float32)
    carried = u0
    for dec in decrements:
        carried = (carried + dec.astype(np.float32)).astype(np.float32)
    return ((carried - u0) / np.float32(dt)).astype(np.float64)


def rel_errors(got, want):
    """(rel_rms, rel_max) of ``got`` against ``want``, both over the rms of
    ``want``: rms|err| / rms|want| and max|err| / rms|want|."""
    err = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    scale = np.sqrt(np.mean(np.square(want)))
    return (float(np.sqrt(np.mean(err * err)) / scale),
            float(np.max(np.abs(err)) / scale))


def fraction_errors(got, want, model):
    """Largest absolute error of the six fractions, each over its element's
    total (H species over X, He species over Y, ``e`` over the fully
    ionised X + Y / 2)."""
    x = model["hydrogen_fraction"]
    y = 1.0 - x - model["metallicity"]
    total = {"hi": x, "hii": x, "hei": y, "heii": y, "heiii": y,
             "e": x + y / 2.0}
    return float(max(
        np.max(np.abs(np.asarray(got[s], np.float64) - want[s])) / total[s]
        for s in SPECIES))
