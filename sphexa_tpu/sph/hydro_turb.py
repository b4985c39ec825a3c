"""Turbulence stirring: Ornstein-Uhlenbeck forcing in Fourier modes.

TPU-native counterpart of the reference's ``sph/include/sph/hydro_turb/``
(turbulence_data.hpp, create_modes.hpp, driver.hpp, phases.hpp,
stirring.hpp): an OU process drives a fixed set of Fourier modes whose
Helmholtz (solenoidal/compressive) projection accelerates the gas
(Eswaran & Pope 1988 forcing, Mach-controlled).

Differences from the reference by design:
- the OU random stream is a jax PRNG key carried in the (checkpointable)
  TurbulenceState pytree instead of a host mt19937, so the whole update
  runs inside the jitted step;
- the per-particle stirring sum (stirring.hpp:42-78, one particle's loop
  over the modes) runs with the loops exchanged: a loop over the modes,
  ``MODES_PER_TURN`` a turn, each turn an elementwise sweep that adds its
  modes' terms to three (N,) f32 accumulators. Nothing of N x M elements
  exists at any N, and every product and sum is f32 on the VPU. It is NOT a
  matmul: a contraction over M modes with three output columns fills 3 of
  the MXU's 128 lanes, and an f32 matmul at a TPU's default precision
  rounds cosines, sines and weights to bf16 (measured on a v5e at 8.0M:
  relative error 2.3e-3 where this form reads 2.3e-7;
  benchmarks/reference_stirring.py is the plain reference and
  benchmarks/check_stirring.py the comparison on the chip).
"""

import dataclasses
from typing import Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TurbulenceConfig:
    """Static stirring parameters (turbulence_data.hpp:57-71,155-175)."""

    num_modes: int
    sol_weight: float
    sol_weight_norm: float
    decay_time: float
    variance: float
    ndim: int = 3


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TurbulenceState:
    """Checkpointable stirring state: fixed mode table + OU phases + RNG key
    (the reference serializes phases and the mt19937 stream the same way,
    turbulence_data.hpp:88-100)."""

    modes: jax.Array       # (M, 3) wave vectors
    amplitudes: jax.Array  # (M,) spectrum amplitudes
    phases: jax.Array      # (M, 3, 2) OU phases, [..., 0]=real, [..., 1]=imag
    key: jax.Array         # jax PRNG key


def create_stirring_modes(
    lbox: float,
    st_max_modes: int = 100000,
    energy_prefac: float = 5.0e-3,
    mach_velocity: float = 0.3,
    sol_weight: float = 0.5,
    spect_form: int = 1,
    ndim: int = 3,
    seed: int = 251299,
    eps: float = 1e-15,
    power_law_exp: float = 5.0 / 3.0,
    angles_exp: float = 2.0,
) -> Tuple[TurbulenceConfig, TurbulenceState]:
    """Build the stirring mode table + initial OU state.

    Mirrors TurbulenceData's constructor pipeline: stirring band
    k in [2pi/L, 3*2pi/L], band (spect_form=0), parabolic (=1) or
    power-law random-angle (=2, create_modes.hpp:179-238) spectrum,
    mirrored +-ky/+-kz modes (create_modes.hpp:30-160), OU variance from
    the target Mach energy input rate.
    """
    if spect_form not in (0, 1, 2):
        raise ValueError("spect_form must be 0 (band), 1 (parabolic) or "
                         "2 (power law)")
    twopi = 2.0 * np.pi
    velocity = mach_velocity
    energy = energy_prefac * velocity**3 / lbox
    stir_min = (1.0 - eps) * twopi / lbox
    stir_max = (3.0 + eps) * twopi / lbox
    decay_time = lbox / (2.0 * velocity)
    variance = np.sqrt(energy / decay_time)
    sol_weight_norm = (
        np.sqrt(3.0) * np.sqrt(3.0 / ndim)
        / np.sqrt(1.0 - 2.0 * sol_weight + ndim * sol_weight**2)
    )

    kc = 0.5 * (stir_min + stir_max) if spect_form == 1 else stir_min
    parab_prefact = -4.0 / (stir_max - stir_min) ** 2

    ik_max = int(np.ceil(stir_max / twopi * lbox)) + 1
    modes, amplitudes = [], []
    if spect_form == 2:
        # power-law spectrum, random-angle shell sampling
        # (create_modes.hpp:179-238): nang ~ 2^ndim ceil(ik^anglesExp)
        # directions per k-shell, amplitude (k/kc)^powerLawExp with the
        # angle-count correction
        rng = np.random.default_rng(seed)
        ik_min = max(1, int(stir_min * lbox / twopi + 0.5))
        ik_hi = int(stir_max * lbox / twopi + 0.5)
        for ik in range(ik_min, ik_hi + 1):
            nang = int(2**ndim * np.ceil(ik**angles_exp))
            for _ in range(nang):
                phi = twopi * rng.uniform()
                theta = (np.arccos(1.0 - 2.0 * rng.uniform())
                         if ndim > 2 else 0.5 * np.pi)
                rand = ik + rng.uniform() - 0.5
                kx = twopi * np.round(rand * np.sin(theta) * np.cos(phi)) / lbox
                ky = (twopi * np.round(rand * np.sin(theta) * np.sin(phi)) / lbox
                      if ndim > 1 else 0.0)
                kz = (twopi * np.round(rand * np.cos(theta)) / lbox
                      if ndim > 2 else 0.0)
                k = np.sqrt(kx**2 + ky**2 + kz**2)
                if not (stir_min <= k <= stir_max):
                    continue
                # PARITY NOTE: the reference computes pow(k/kc, +powerLawExp)
                # with default powerLawExp = 5/3 (create_modes.hpp:222,
                # turbulence_init.hpp:61) — a spectrum RISING with k over
                # the driving band; reproduced verbatim. A decaying
                # Kolmogorov band needs powerLawExp = -5/3 in the settings.
                amp = (k / kc) ** power_law_exp
                amp = np.sqrt(
                    amp * (ik ** (ndim - 1) * 4.0 * np.sqrt(3.0) / nang)
                ) * (kc / k) ** (0.5 * (ndim - 1))
                modes.append((kx, ky, kz))
                amplitudes.append(amp)
                if len(modes) > st_max_modes:
                    raise ValueError(
                        f"too many stirring modes ({len(modes)} > {st_max_modes})"
                    )
    else:
      for ikx in range(0, ik_max + 1):
        kx = twopi * ikx / lbox
        for iky in range(0, ik_max + 1 if ndim > 1 else 1):
            ky = twopi * iky / lbox
            for ikz in range(0, ik_max + 1 if ndim > 2 else 1):
                kz = twopi * ikz / lbox
                k = np.sqrt(kx**2 + ky**2 + kz**2)
                if not (stir_min <= k <= stir_max):
                    continue
                amp = 1.0
                if spect_form == 1:
                    amp = abs(parab_prefact * (k - kc) ** 2 + 1.0)
                amp = 2.0 * np.sqrt(amp) * (kc / k) ** (0.5 * (ndim - 1))
                # mirrored sign combinations of ky/kz cover the half-space
                # of independent modes (create_modes.hpp:126-158)
                signsets = [(kx, ky, kz)]
                if ndim > 1:
                    signsets.append((kx, -ky, kz))
                if ndim > 2:
                    signsets += [(kx, ky, -kz), (kx, -ky, -kz)]
                for kvec in signsets:
                    modes.append(kvec)
                    amplitudes.append(amp)
                if len(modes) > st_max_modes:
                    raise ValueError(
                        f"too many stirring modes ({len(modes)} > {st_max_modes})"
                    )

    m = len(modes)
    cfg = TurbulenceConfig(
        num_modes=m,
        sol_weight=sol_weight,
        sol_weight_norm=float(sol_weight_norm),
        decay_time=float(decay_time),
        variance=float(variance),
        ndim=ndim,
    )
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    phases = variance * jax.random.normal(sub, (m, 3, 2), dtype=jnp.float32)
    state = TurbulenceState(
        modes=jnp.asarray(np.asarray(modes), jnp.float32),
        amplitudes=jnp.asarray(np.asarray(amplitudes), jnp.float32),
        phases=phases,
        key=key,
    )
    return cfg, state


def update_noise(
    turb: TurbulenceState, dt, cfg: TurbulenceConfig
) -> TurbulenceState:
    """One OU step: x' = f x + sigma sqrt(1 - f^2) z, f = exp(-dt/ts)
    (driver.hpp:43-91, Bartosch 2001)."""
    # f - 1 = expm1(-dt/ts), and 1 - f^2 = (1 - f)(1 + f) from it. A step
    # is dt/ts ~ 1e-5..1e-3, so f sits within 1e-3 of 1: on a v5e
    # exp(-1.6e-4) is 1.5e-6 off (1 % of 1 - f, the damping a step
    # applies), and sqrt(1 - f*f) of it is 0.2 % off there and 5 % off at
    # dt/ts = 6e-6; this form reads 2e-8 and 1e-8 (PERF.md, PR 31)
    f_minus_1 = jnp.expm1(-dt / cfg.decay_time)
    damping_a = 1.0 + f_minus_1
    damping_b = jnp.sqrt(-f_minus_1 * (2.0 + f_minus_1))
    key, sub = jax.random.split(turb.key)
    z = jax.random.normal(sub, turb.phases.shape, dtype=turb.phases.dtype)
    phases = turb.phases * damping_a + cfg.variance * damping_b * z
    return dataclasses.replace(turb, phases=phases, key=key)


def compute_phases(turb: TurbulenceState, cfg: TurbulenceConfig):
    """Helmholtz projection of the OU phases: solenoidal weight sw blends
    the curl (divergence-free) and div (compressive) parts per mode
    (phases.hpp:45-71). Returns (phases_real, phases_imag), each (M, 3)."""
    k = turb.modes                       # (M, 3)
    ph_re = turb.phases[..., 0]          # (M, 3)
    ph_im = turb.phases[..., 1]
    kk = jnp.sum(k * k, axis=1, keepdims=True)
    ka = jnp.sum(k * ph_im, axis=1, keepdims=True)
    kb = jnp.sum(k * ph_re, axis=1, keepdims=True)
    diva = k * ka / kk
    divb = k * kb / kk
    curla = ph_re - divb
    curlb = ph_im - diva
    sw = cfg.sol_weight
    return sw * curla + (1.0 - sw) * divb, sw * curlb + (1.0 - sw) * diva


#: modes summed per turn of the stirring loop: each turn is one elementwise
#: sweep over the particles that adds its modes' terms to the accumulators,
#: so they cross HBM M / MODES_PER_TURN times. 8 and 16 time alike on a v5e
#: (25.0 / 25.3 ms at 8.0M; PERF.md, PR 31); XLA keeps one (N,) temporary a
#: mode of the turn
MODES_PER_TURN = 8

# pi/2 in three parts with trailing zero bits, so q * _PIO2[0] and
# q * _PIO2[1] are exact for |q| < 2^15 (Cody-Waite; cephes sinf.c has the
# same parts of pi/4)
_PIO2 = (1.5703125, 4.837512969970703125e-4, 7.54978995489188e-8)
# cephes sinf.c / cosf.c: minimax polynomials on [-pi/4, pi/4]
_SIN_POLY = (-1.6666654611e-1, 8.3321608736e-3, -1.9515295891e-4)
_COS_POLY = (4.166664568298827e-2, -1.388731625493765e-3,
             2.443315711809948e-5)


def _sincos(a):
    """(sin a, cos a) of f32 ``a``, both from one argument reduction, in
    adds, multiplies and selects: |error| < 2e-7 for |a| < 1e4 (the
    stirring's |k.x| is under 17). ``jnp.sin`` and ``jnp.cos`` are as
    accurate, but XLA's TPU pipeline will not fuse them into the sum that
    consumes them: the stirring sweep with them took 75.6 ms at 8.0M on a
    v5e where this takes 25.0 (PERF.md, PR 31)."""
    q = jnp.round(a * (2.0 / np.pi))
    r = ((a - q * _PIO2[0]) - q * _PIO2[1]) - q * _PIO2[2]
    r2 = r * r
    s = r + r * r2 * (_SIN_POLY[0] + r2 * (_SIN_POLY[1] + r2 * _SIN_POLY[2]))
    c = (1.0 - 0.5 * r2) + r2 * r2 * (
        _COS_POLY[0] + r2 * (_COS_POLY[1] + r2 * _COS_POLY[2]))
    # a = q pi/2 + r: the quadrant swaps and signs the pair
    quadrant = q.astype(jnp.int32)
    odd = (quadrant & 1) == 1
    sin_a = jnp.where(odd, c, s)
    cos_a = jnp.where(odd, s, c)
    sin_a = jnp.where((quadrant & 2) == 2, -sin_a, sin_a)
    cos_a = jnp.where(((quadrant + 1) & 2) == 2, -cos_a, cos_a)
    return sin_a, cos_a


def st_calc_accel(
    x, y, z, turb: TurbulenceState, cfg: TurbulenceConfig,
    phases_real, phases_imag,
):
    """Stirring accelerations: a_i = norm * sum_m amp_m Re[(P_m) e^{i k_m x_i}]
    (stirring.hpp stirParticle), as a loop over the modes on three (N,)
    f32 accumulators: no array of N x M elements at any N or M."""
    num_modes = turb.modes.shape[0]
    turns = -(-num_modes // MODES_PER_TURN)
    weight = (cfg.sol_weight_norm * turb.amplitudes)[:, None]
    # one row a mode: k (3), weighted real phases (3), imaginary (3). A
    # table that is no multiple of the turn is filled up with rows of
    # zeros, which add cos(0) * 0
    rows = jnp.pad(
        jnp.concatenate(
            [turb.modes, weight * phases_real, weight * phases_imag], axis=1),
        ((0, turns * MODES_PER_TURN - num_modes), (0, 0)),
    )

    def turn(t, acc):
        row = jax.lax.dynamic_slice_in_dim(
            rows, t * MODES_PER_TURN, MODES_PER_TURN)
        for j in range(MODES_PER_TURN):
            sk, ck = _sincos(row[j, 0] * x + row[j, 1] * y + row[j, 2] * z)
            acc = tuple(
                a + (row[j, 3 + c] * ck - row[j, 6 + c] * sk)
                for c, a in enumerate(acc)
            )
        return acc

    zero = jnp.zeros_like(x)
    return jax.lax.fori_loop(0, turns, turn, (zero, zero, zero))


def drive_turbulence(
    x, y, z, ax, ay, az, dt, turb: TurbulenceState, cfg: TurbulenceConfig
) -> Tuple[jax.Array, jax.Array, jax.Array, TurbulenceState]:
    """OU update + projection + stirring add, one step (driver.hpp:104-130).
    Returns updated accelerations and the advanced TurbulenceState."""
    turb = update_noise(turb, dt, cfg)
    pr, pi = compute_phases(turb, cfg)
    tx, ty, tz = st_calc_accel(x, y, z, turb, cfg, pr, pi)
    return ax + tx, ay + ty, az + tz, turb


def turbulence_state_to_fields(
    turb: TurbulenceState, cfg: TurbulenceConfig
) -> Dict[str, np.ndarray]:
    """Flatten the stirring state AND config scalars into named arrays for
    checkpointing — a restart must resume the same forcing (variance,
    decay time, solenoidal weight), not rebuilt defaults
    (turbulence_data.hpp:88-100 serializes the same set)."""
    return {
        "turb_modes": np.asarray(turb.modes),
        "turb_amplitudes": np.asarray(turb.amplitudes),
        "turb_phases": np.asarray(turb.phases),
        "turb_key": np.asarray(turb.key),
        "turb_cfg": np.asarray(
            [cfg.sol_weight, cfg.sol_weight_norm, cfg.decay_time,
             cfg.variance, float(cfg.ndim)],
            np.float64,
        ),
    }


def turbulence_state_from_fields(
    fields: Dict[str, np.ndarray]
) -> Tuple[TurbulenceState, TurbulenceConfig]:
    """Inverse of turbulence_state_to_fields (restart path)."""
    state = TurbulenceState(
        modes=jnp.asarray(fields["turb_modes"]),
        amplitudes=jnp.asarray(fields["turb_amplitudes"]),
        phases=jnp.asarray(fields["turb_phases"]),
        key=jnp.asarray(fields["turb_key"]),
    )
    sw, swn, ts, var, ndim = (float(v) for v in fields["turb_cfg"])
    cfg = TurbulenceConfig(
        num_modes=state.modes.shape[0],
        sol_weight=sw,
        sol_weight_norm=swn,
        decay_time=ts,
        variance=var,
        ndim=int(ndim),
    )
    return state, cfg
