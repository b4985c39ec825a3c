"""Plain reference of the turbulence stirring (upstream SPH-EXA
``sph/include/sph/hydro_turb/``), for the comparison that holds
``sphexa_tpu/sph/hydro_turb.py`` to the configuration's stirring limits.

Float64 numpy, one particle's sum written as upstream writes it, no matmul,
tile or kernel of the program: the same operations on the same data must
give the same answers.

- ``update_noise``: the Ornstein-Uhlenbeck step of Bartosch (2001),
  x' = f x + sigma sqrt(1 - f^2) z with f = exp(-dt / ts) (driver.hpp:43-91);
- ``compute_phases``: the Helmholtz projection of the OU phases, the
  solenoidal weight blending the divergence-free and the compressive part of
  every mode (phases.hpp:45-71);
- ``stir_accel``: the per-particle loop over the modes (stirring.hpp:42-78,
  ``stirParticle``): per-axis cosines and sines joined by angle addition,
  a_i = norm * sum_m amp_m (P_re,m Re e^{i k_m.x_i} - P_im,m Im e^{i k_m.x_i}).

One departure from upstream, and it is the program's: upstream draws the
OU noise from a host mt19937, the program from a jax PRNG key carried in its
``TurbulenceState``. The noise is therefore an INPUT here (``update_noise``
takes the normal draws ``z``); ``system_draws`` makes the draws the program's
next step will make from a key, so that both sides step on the same noise.

``stir_accel(..., operand_dtype=bfloat16)`` is the control the limits must
refuse: the same sum with cosines, sines and amplitude-weighted phases
rounded to bf16 before each product, which is what an f32 matmul at a TPU's
default precision computes (one bf16 pass, f32 accumulation).
"""

import numpy as np


def system_draws(key, shape, dtype):
    """The standard normal draws the program's next ``update_noise`` makes
    from ``key``, and the key it carries on (hydro_turb.update_noise: one
    ``split``, then ``normal`` on the sub-key). Returns (z float64, key')."""
    import jax

    key, sub = jax.random.split(key)
    z = jax.random.normal(sub, shape, dtype=dtype)
    return np.asarray(z, np.float64), key


def update_noise(phases, z, dt, decay_time, variance):
    """One OU step of the (M, 3, 2) phases on the draws ``z``."""
    phases = np.asarray(phases, np.float64)
    damping_a = np.exp(-np.float64(dt) / decay_time)
    damping_b = np.sqrt(1.0 - damping_a * damping_a)
    return phases * damping_a + variance * damping_b * np.asarray(z, np.float64)


def compute_phases(modes, phases, sol_weight):
    """(phases_real, phases_imag), each (M, 3): mode by mode, the projection
    of the OU phases on and across the wave vector."""
    modes = np.asarray(modes, np.float64)
    phases = np.asarray(phases, np.float64)
    real = np.zeros(modes.shape)
    imag = np.zeros(modes.shape)
    for m, k in enumerate(modes):
        kk = k[0] * k[0] + k[1] * k[1] + k[2] * k[2]
        ka = kb = 0.0
        for j in range(3):
            kb += k[j] * phases[m, j, 0]
            ka += k[j] * phases[m, j, 1]
        for j in range(3):
            diva = k[j] * ka / kk
            divb = k[j] * kb / kk
            curla = phases[m, j, 0] - divb
            curlb = phases[m, j, 1] - diva
            real[m, j] = sol_weight * curla + (1.0 - sol_weight) * divb
            imag[m, j] = sol_weight * curlb + (1.0 - sol_weight) * diva
    return real, imag


def stir_accel(x, y, z, modes, amplitudes, phases_real, phases_imag,
               sol_weight_norm, operand_dtype=None):
    """(ax, ay, az) of the stirring at the particles ``x, y, z``, float64.
    The loop runs over the modes and every line of its body is one
    particle's arithmetic (numpy carries it over all the particles given).
    ``operand_dtype``: round every product's operands to that type first
    (the lower-precision control)."""
    x, y, z = (np.asarray(a, np.float64) for a in (x, y, z))
    modes = np.asarray(modes, np.float64)
    amplitudes = np.asarray(amplitudes, np.float64)
    phases_real = np.asarray(phases_real, np.float64)
    phases_imag = np.asarray(phases_imag, np.float64)
    if operand_dtype is None:
        rounded = lambda a: a
    else:
        rounded = lambda a: np.asarray(a).astype(operand_dtype).astype(
            np.float64)
    acc = [np.zeros_like(x) for _ in range(3)]
    for m in range(modes.shape[0]):
        cosxi, sinxi = np.cos(modes[m, 0] * x), np.sin(modes[m, 0] * x)
        cosxj, sinxj = np.cos(modes[m, 1] * y), np.sin(modes[m, 1] * y)
        cosxk, sinxk = np.cos(modes[m, 2] * z), np.sin(modes[m, 2] * z)
        realtrig = ((cosxi * cosxj - sinxi * sinxj) * cosxk
                    - (sinxi * cosxj + cosxi * sinxj) * sinxk)
        imtrig = (cosxi * (cosxj * sinxk + sinxj * cosxk)
                  + sinxi * (cosxj * cosxk - sinxj * sinxk))
        realtrig, imtrig = rounded(realtrig), rounded(imtrig)
        for j in range(3):
            acc[j] += (rounded(amplitudes[m] * phases_real[m, j]) * realtrig
                       - rounded(amplitudes[m] * phases_imag[m, j]) * imtrig)
    return tuple(sol_weight_norm * a for a in acc)


def rel_errors(got, ref):
    """(rel_rms, rel_max) of acceleration vectors ``got`` against ``ref``
    (each three arrays over the targets), both over the RMS MAGNITUDE of the
    reference at the targets: the stirring field has nodes, where an error
    over the local magnitude says nothing."""
    got = np.stack([np.asarray(a, np.float64) for a in got])
    ref = np.stack([np.asarray(a, np.float64) for a in ref])
    err = np.sqrt(np.sum((got - ref) ** 2, axis=0))
    scale = np.sqrt(np.mean(np.sum(ref ** 2, axis=0)))
    return (float(np.sqrt(np.mean(err ** 2)) / scale),
            float(np.max(err) / scale))
