"""Radiative cooling: reduced tabulated model (GRACKLE-equivalent role).

Counterpart of the reference's ``physics/cooling/`` (cooler.hpp wraps the
external GRACKLE C/Fortran library: per-particle chemistry, u<->T
conversion, cooling timestep limiter ct_crit, cooling-aware EOS,
std_hydro_grackle.hpp couples it after the force stage). The TPU build
replaces the library with a self-contained, jit-compatible model:

- a collisional-ionization-equilibrium (CIE) cooling curve Lambda(T),
  tabulated at solar composition (piecewise log-log interpolation; the
  table is a config field, so a user can substitute e.g. a Sutherland &
  Dopita or GRACKLE-generated table);
- optional constant photoelectric heating rate Gamma;
- a reduced ChemistryData carrying the ionization fractions the reference
  tracks (they set the mean molecular weight; the CIE assumption makes
  them diagnostic rather than evolved ODEs);
- sub-cycled semi-implicit integration of du/dt inside the jitted step
  (replacing GRACKLE's internal stiff solver), with the same ct_crit
  timestep limiter contract (eos_cooling.hpp:12-25).

Unit handling: the simulation runs in code units; CoolingConfig carries
the code->cgs conversions (mass, length, and the G=1 time unit), matching
the reference's cooling::m_code_in_ms / l_code_in_kpc attributes
(evrard_cooling_init.hpp:59-60).
"""

import dataclasses
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

# cgs constants
KB = 1.380658e-16          # erg/K
MH = 1.6726231e-24         # g
G_CGS = 6.6726e-8          # cm^3 g^-1 s^-2
MSUN = 1.98892e33          # g
KPC = 3.0856776e21         # cm

# Approximate solar-metallicity CIE cooling curve, log10 T [K] ->
# log10 Lambda [erg cm^3 / s]: H/He + metal line peak near 1e5 K,
# bremsstrahlung ~ sqrt(T) beyond 1e7.5 K. Control points follow the
# canonical shape of Sutherland & Dopita (1993) to ~0.1 dex.
_LOGT_TABLE = np.array(
    [3.8, 4.0, 4.2, 4.6, 5.0, 5.4, 5.8, 6.2, 6.6, 7.0, 7.5, 8.0, 8.5]
)
_LOGL_TABLE = np.array(
    [-28.0, -23.2, -21.8, -21.4, -21.1, -21.3, -21.7, -22.1, -22.5,
     -22.7, -22.65, -22.55, -22.4]
)


@dataclasses.dataclass(frozen=True)
class CoolingConfig:
    """Static cooling parameters + unit system (cooler.hpp attributes)."""

    ct_crit: float = 0.1            # cooling-time step fraction (cooler.hpp:90)
    gamma: float = 5.0 / 3.0
    mu: float = 0.6                 # mean molecular weight (ionized solar)
    hydrogen_fraction: float = 0.76
    heating_rate: float = 0.0       # Gamma, erg/s per H atom (photoelectric)
    # code -> cgs conversions (evrard_cooling_init: m_code_in_ms, l_code_in_kpc)
    m_code_g: float = 1e16 * MSUN
    l_code_cm: float = 46400.0 * KPC
    substeps: int = 8               # sub-cycles of the semi-implicit update
    logT_table: Tuple[float, ...] = tuple(_LOGT_TABLE)
    logL_table: Tuple[float, ...] = tuple(_LOGL_TABLE)
    # evolve the 6-species primordial network (physics/primordial.py) in
    # place of the CIE table: species ODEs + composition-resolved cooling
    # per step, the cooler.cpp solve_chemistry role. False keeps the
    # metal-inclusive CIE curve with diagnostic-only fractions.
    # Simulation(prop="std-cooling") builds its default with True.
    evolve_species: bool = False

    @property
    def t_code_s(self) -> float:
        """G=1 time unit: sqrt(l^3 / (G m))."""
        return float(np.sqrt(self.l_code_cm**3 / (G_CGS * self.m_code_g)))

    @property
    def rho_to_cgs(self) -> float:
        return float(self.m_code_g / self.l_code_cm**3)

    @property
    def u_to_cgs(self) -> float:
        """specific energy: (l/t)^2."""
        return float((self.l_code_cm / self.t_code_s) ** 2)

    # The raw cgs chain (rho_cgs ~ 1e-41 g/cm^3 at these units) under- and
    # overflows float32, so the conversions are folded into two host-side
    # prefactors and the device math stays in code-unit magnitudes:
    #   du/dt_cool [code] = -10^(logL + log_cool_prefac) * rho_code
    #   du/dt_heat [code] = heating_code
    @property
    def log_cool_prefac(self) -> float:
        """log10 of (X/m_H)^2 * rho_to_cgs * t_code / u_to_cgs."""
        x_over_mh = self.hydrogen_fraction / MH
        return float(
            2.0 * np.log10(x_over_mh)
            + np.log10(self.rho_to_cgs)
            + np.log10(self.t_code_s)
            - np.log10(self.u_to_cgs)
        )

    @property
    def heating_code(self) -> float:
        """specific heating rate X Gamma / m_H in code units per code time."""
        if self.heating_rate == 0.0:
            return 0.0
        return float(
            self.hydrogen_fraction * self.heating_rate / MH
            * self.t_code_s / self.u_to_cgs
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ChemistryData:
    """Reduced per-particle chemistry fractions (mass fractions).

    The reference's ChemistryData tracks 21 GRACKLE species
    (cooling/chemistry_data.hpp:47-116); under the CIE closure the model
    here needs only the composition that fixes the mean molecular weight.
    """

    hi: jax.Array      # neutral H mass fraction
    hii: jax.Array     # ionized H
    hei: jax.Array
    heii: jax.Array
    heiii: jax.Array
    # electron abundance as a per-MASS number fraction y_e = n_e m_H/rho
    # (the same convention primordial._y_of passes through unchanged:
    # fully-ionized primordial gives y_e = X + Y/2, NOT "per H")
    e: jax.Array
    metal: jax.Array

    @staticmethod
    def ionized(n: int, hydrogen_fraction: float = 0.76,
                metallicity: float = 0.0122) -> "ChemistryData":
        """Fully ionized primordial + solar-metal composition."""
        x = hydrogen_fraction
        y = 1.0 - x - metallicity
        f = lambda v: jnp.full(n, v, jnp.float32)
        return ChemistryData(
            hi=f(0.0), hii=f(x), hei=f(0.0), heii=f(0.0), heiii=f(y),
            e=f(x + y / 2.0), metal=f(metallicity),
        )

    def mean_molecular_weight(self) -> jax.Array:
        """mu from the composition: 1/mu = 2 X_HII + X_HI + ... (amu)."""
        inv_mu = (
            self.hi + 2.0 * self.hii
            + self.hei / 4.0 + self.heii / 2.0 + 3.0 * self.heiii / 4.0
            + self.metal / 2.0
        )
        return 1.0 / jnp.maximum(inv_mu, 1e-10)


def u_to_temp(u_code, mu, cfg: CoolingConfig):
    """T[K] = (gamma-1) mu m_H u_cgs / kB (cooler energy_to_temperature)."""
    u_cgs = u_code * cfg.u_to_cgs
    return (cfg.gamma - 1.0) * mu * MH * u_cgs / KB


def temp_to_u(temp, mu, cfg: CoolingConfig):
    """Inverse of u_to_temp, returns code units."""
    u_cgs = temp * KB / ((cfg.gamma - 1.0) * mu * MH)
    return u_cgs / cfg.u_to_cgs


def _log_lambda_cie(temp, cfg: CoolingConfig):
    """log10 Lambda(T) [erg cm^3/s] by interpolation of the CIE table."""
    logT = jnp.log10(jnp.maximum(temp, 1.0))
    return jnp.interp(
        logT,
        jnp.asarray(cfg.logT_table, jnp.float32),
        jnp.asarray(cfg.logL_table, jnp.float32),
        left=-60.0,  # no radiative cooling below the table
        right=float(cfg.logL_table[-1]),
    )


def _lambda_cie(temp, cfg: CoolingConfig):
    """Lambda(T) [erg cm^3/s] (diagnostic form of _log_lambda_cie)."""
    return 10.0 ** _log_lambda_cie(temp, cfg)


def cooling_rate(rho_code, u_code, chem: ChemistryData, cfg: CoolingConfig):
    """du/dt in code units: (n_H Gamma - n_H^2 Lambda(T)) / rho.

    Negative = net cooling. The n_H^2 scaling is the two-body CIE form the
    GRACKLE tabulated mode uses. The unit conversions are pre-folded into
    log-space prefactors (see CoolingConfig.log_cool_prefac) so all traced
    values stay in float32-safe magnitudes.
    """
    mu = chem.mean_molecular_weight()
    temp = u_to_temp(u_code, mu, cfg)
    log_lam = _log_lambda_cie(temp, cfg)
    cool = 10.0 ** (log_lam + cfg.log_cool_prefac) * rho_code
    return cfg.heating_code - cool


def cooling_timestep(rho_code, u_code, chem: ChemistryData, cfg: CoolingConfig):
    """min over particles of ct_crit * |u / (du/dt)| (eos_cooling.hpp:12-25)."""
    dudt = cooling_rate(rho_code, u_code, chem, cfg)
    tc = jnp.abs(u_code / jnp.where(jnp.abs(dudt) > 0, dudt, 1e-30))
    return cfg.ct_crit * jnp.min(tc)


def implicit_u_subcycle(u, dt_sub, cool, heat):
    """One subcycle of the energy update both integrators share:
    ``(u_new, rate)``.

    Cooling is applied as u' = u / (1 + dt_sub * L/u), which is
    unconditionally stable and positivity-preserving; heating is added
    explicitly. ``rate`` is the same subcycle's (u' - u) / dt_sub formed
    WITHOUT the difference: heat - L / (1 + dt_sub * L/u). At a step's dt
    u' - u is a few ulp of u (wind-shock's ramp starts at dt 1e-10 against
    cooling times of 3 to 300), so the differenced form reads zero or
    one ulp over dt; the rate is a quotient of two well-conditioned
    numbers at every dt."""
    damp = 1.0 + dt_sub * cool / jnp.maximum(u, 1e-30)
    return u / damp + dt_sub * heat, heat - cool / damp


def cool_particles(dt, rho_code, u_code, chem: ChemistryData, cfg: CoolingConfig):
    """Integrate the cooling source over dt; returns du/dt averaged over the
    step (the quantity the propagator adds to du,
    std_hydro_grackle.hpp:214-226): the mean of the subcycles' rates
    (``implicit_u_subcycle``), accumulated beside ``u``.
    """
    dt_sub = dt / cfg.substeps

    def body(carry, _):
        u, acc = carry
        dudt = cooling_rate(rho_code, u, chem, cfg)
        cool = jnp.where(dudt < 0, -dudt, 0.0)
        heat = jnp.where(dudt > 0, dudt, 0.0)
        u_new, rate = implicit_u_subcycle(u, dt_sub, cool, heat)
        return (u_new, acc + rate), None

    (_, acc), _ = jax.lax.scan(
        body, (u_code, jnp.zeros_like(u_code)), None, length=cfg.substeps)
    return acc / cfg.substeps


def cool_step(dt, rho_code, u_code, chem: ChemistryData, cfg: CoolingConfig):
    """One cooling source update: (du_avg, new ChemistryData).

    Dispatches on cfg.evolve_species — the evolved primordial network
    (physics/primordial.py, the cooler.cpp:313 solve_chemistry role) or
    the CIE table with pass-through fractions."""
    if cfg.evolve_species:
        from sphexa_tpu.physics.primordial import evolve_primordial

        return evolve_primordial(dt, rho_code, u_code, chem, cfg)
    return cool_particles(dt, rho_code, u_code, chem, cfg), chem


def cool_timestep(rho_code, u_code, chem: ChemistryData, cfg: CoolingConfig):
    """ct_crit cooling-time limiter, dispatching like cool_step."""
    if cfg.evolve_species:
        from sphexa_tpu.physics.primordial import primordial_cooling_timestep

        return primordial_cooling_timestep(rho_code, u_code, chem, cfg)
    return cooling_timestep(rho_code, u_code, chem, cfg)


def eos_cooling(rho_code, u_code, chem: ChemistryData, cfg: CoolingConfig):
    """EOS used by the cooling propagator's contract (eos_cooling.hpp:27-47).

    Under the CIE closure the composition enters only through the u <-> T
    conversion (mean molecular weight); pressure from specific internal
    energy is exactly the ideal-gas form p = (gamma-1) rho u, which is what
    the force stage (hydro_std.compute_eos_std) already evaluates — so the
    propagator needs no separate EOS hook. This function exists as the
    explicit statement of that identity (and the place a future
    variable-gamma chemistry model would plug in)."""
    from sphexa_tpu.sph.eos import ideal_gas_eos_u

    del chem  # composition-independent under the CIE closure
    return ideal_gas_eos_u(u_code, rho_code, cfg.gamma)


_CHEM_FIELDS = ("hi", "hii", "hei", "heii", "heiii", "e", "metal")


def chemistry_to_fields(chem: ChemistryData):
    """Flatten the chemistry pytree into snapshot datasets (prefixed
    ``chem_``), the checkpoint counterpart of the reference's per-particle
    GRACKLE fields (std_hydro_grackle.hpp:89-106)."""
    import numpy as np

    return {f"chem_{k}": np.asarray(getattr(chem, k)) for k in _CHEM_FIELDS}


def chemistry_from_fields(extra) -> ChemistryData:
    """Rebuild ChemistryData from snapshot datasets written by
    ``chemistry_to_fields``."""
    return ChemistryData(
        **{k: jnp.asarray(extra[f"chem_{k}"]) for k in _CHEM_FIELDS}
    )
