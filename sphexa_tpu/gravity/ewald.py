"""Ewald summation for self-gravity in periodic boxes.

TPU-native counterpart of the reference's
``ryoanji/src/ryoanji/nbody/traversal_ewald_cpu.hpp`` (computeGravityEwald):
the total periodic force is

  near field   : Barnes-Hut forces summed over +-num_replica_shells box
                 replicas (tree of the base box, shifted targets);
  real space   : per-particle correction from the ROOT multipole over
                 +-num_ewald_shells replicas, erfc-screened (erf-subtracted
                 inside the region the near field already covered);
  k space      : the smooth long-range remainder as a Fourier sum with
                 root-multipole-weighted coefficients.

The reference evaluates both corrections per particle in scalar loops; here
the shell/hvec tables are static (N, S)/(N, H) broadcasts, and the k-space
sum is a pair of cos/sin matmuls. Requires a cubic box (same restriction
as the reference, traversal_ewald_cpu.hpp:366).
"""

import dataclasses
import functools
from itertools import product
from typing import Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.scipy.special import erf, erfc

from sphexa_tpu.gravity.traversal import (
    GravityConfig,
    compute_gravity,
    compute_multipoles,
)
from sphexa_tpu.gravity.tree import GravityTree, GravityTreeMeta
from sphexa_tpu.sfc.box import Box


@dataclasses.dataclass(frozen=True)
class EwaldConfig:
    """Static Ewald parameters (ewaldInitParameters recommended values)."""

    num_replica_shells: int = 1
    lcut: float = 2.6
    hcut: float = 2.8
    alpha_scale: float = 2.0
    small_r_factor: float = 3.0e-3  # Gasoline value (traversal_ewald_cpu.hpp:147)

    @property
    def num_ewald_shells(self) -> int:
        return max(int(np.ceil(self.lcut)), self.num_replica_shells)


def _real_space_shells(cfg: EwaldConfig):
    """Static shell table: integer offsets (S, 3) + in-near-field flags."""
    s = cfg.num_ewald_shells
    r = cfg.num_replica_shells
    shells, in_near = [], []
    for ix, iy, iz in product(range(-s, s + 1), repeat=3):
        shells.append((ix, iy, iz))
        in_near.append(abs(ix) <= r and abs(iy) <= r and abs(iz) <= r)
    return np.asarray(shells, np.float32), np.asarray(in_near)


def _k_space_hvecs(cfg: EwaldConfig):
    """Static h-vector table (H, 3): 0 < |h| <= hcut."""
    reps = int(np.ceil(cfg.hcut))
    hvecs = [
        (hx, hy, hz)
        for hx, hy, hz in product(range(-reps, reps + 1), repeat=3)
        if 0 < hx * hx + hy * hy + hz * hz <= cfg.hcut**2
    ]
    return np.asarray(hvecs, np.float32)


def _eval_root_multipole(r, gamma, mass, q):
    """Potential + acceleration of the root expansion at offsets ``r``.

    Vectorized ewaldEvalMultipoleComplete (traversal_ewald_cpu.hpp:89-111):
    ``r`` (..., 3), ``gamma`` (..., 6), root monopole ``mass`` and
    trace-free quadrupole ``q`` (7,). Returns (u, a) with a (..., 3).
    """
    qxx = (q[0] + q[6]) / 3.0
    qyy = (q[3] + q[6]) / 3.0
    qzz = (q[5] + q[6]) / 3.0
    qxy, qxz, qyz = q[1] / 3.0, q[2] / 3.0, q[4] / 3.0

    rx, ry, rz = r[..., 0], r[..., 1], r[..., 2]
    qr = jnp.stack(
        [rx * qxx + ry * qxy + rz * qxz,
         rx * qxy + ry * qyy + rz * qyz,
         rx * qxz + ry * qyz + rz * qzz],
        axis=-1,
    )
    rqr = 0.5 * jnp.sum(r * qr, axis=-1)
    qtr = 0.5 * q[6]

    g0, g1, g2, g3 = gamma[..., 0], gamma[..., 1], gamma[..., 2], gamma[..., 3]
    u = -g0 * mass + g1 * qtr - g2 * rqr
    a = g2[..., None] * qr - r * (g1 * mass - g2 * qtr + g3 * rqr)[..., None]
    return u, a


def _real_space_correction(dr, mass, q, L, cfg: EwaldConfig):
    """Real-space Ewald sum over shells for particle offsets ``dr`` (N, 3).

    Gamma recurrences per traversal_ewald_cpu.hpp:199-297: erfc screening
    outside the near-field region, -erf subtraction inside it (the near
    field computed those shells exactly), Taylor series near R = 0.
    """
    shells, in_near = _real_space_shells(cfg)
    alpha = cfg.alpha_scale / L
    alpha2 = alpha * alpha
    ka = 2.0 * alpha / jnp.sqrt(jnp.pi)
    lcut2 = cfg.lcut**2 * L * L
    small_r2 = cfg.small_r_factor * L * L
    k1 = jnp.pi / (alpha2 * L**3)

    R = dr[:, None, :] + jnp.asarray(shells)[None, :, :] * L  # (N, S, 3)
    r2 = jnp.sum(R * R, axis=-1)
    in_near_j = jnp.asarray(in_near)[None, :]

    # shell selection: everything inside lcut, plus all near-field shells
    active = (r2 <= lcut2) | in_near_j

    # regular branch
    rmag = jnp.sqrt(jnp.maximum(r2, 1e-30))
    inv_r = 1.0 / rmag
    inv_r2 = inv_r * inv_r
    a_term = jnp.exp(-r2 * alpha2) * ka * inv_r2
    fn = jnp.where(in_near_j, -erf(alpha * rmag), erfc(alpha * rmag))
    g = [None] * 6
    g[0] = fn * inv_r
    g[1] = g[0] * inv_r2 + a_term
    alphan = 2 * alpha2
    g[2] = 3 * g[1] * inv_r2 + alphan * a_term
    alphan = alphan * 2 * alpha2
    g[3] = 5 * g[2] * inv_r2 + alphan * a_term
    alphan = alphan * 2 * alpha2
    g[4] = 7 * g[3] * inv_r2 + alphan * a_term
    alphan = alphan * 2 * alpha2
    g[5] = 9 * g[4] * inv_r2 + alphan * a_term
    gamma_reg = jnp.stack(g, axis=-1)  # (N, S, 6)

    # small-R series branch (cancellation-safe near the origin)
    r2a2 = r2 * alpha2
    cs = [None] * 6
    c0 = ka
    cs[0] = c0 * (r2a2 / 3.0 - 1.0)
    for i, (num, den) in enumerate(
        [(5.0, 3.0), (7.0, 5.0), (9.0, 7.0), (11.0, 9.0), (13.0, 11.0)], start=1
    ):
        c0 = c0 * 2 * alpha2
        cs[i] = c0 * (r2a2 / num - 1.0 / den)
    gamma_small = jnp.stack(cs, axis=-1)

    gamma = jnp.where((r2 < small_r2)[..., None], gamma_small, gamma_reg)
    gamma = jnp.where(active[..., None], gamma, 0.0)

    u, a = _eval_root_multipole(R, gamma, mass, q)
    # background term k1*M (compensates the mean density, :215)
    u_tot = jnp.sum(u, axis=1) + k1 * mass
    return u_tot, jnp.sum(a, axis=1)


def _k_space_correction(dr, mass, q, L, cfg: EwaldConfig):
    """Fourier-space Ewald sum (computeEwaldKSpace + hsum coefficients)."""
    hvecs = jnp.asarray(_k_space_hvecs(cfg))  # (H, 3)
    alpha = cfg.alpha_scale / L
    k4 = jnp.pi**2 / (alpha**2 * L**2)
    h2 = jnp.sum(hvecs * hvecs, axis=1)

    g0 = jnp.exp(-k4 * h2) / (jnp.pi * h2 * L)
    g1 = 2 * jnp.pi / L * g0
    g2 = -2 * jnp.pi / L * g1
    g3 = 2 * jnp.pi / L * g2
    g4 = -2 * jnp.pi / L * g3
    g5 = 2 * jnp.pi / L * g4
    zero = jnp.zeros_like(g0)
    # cos coefficients use even gammas, sin the odd ones (hsum build, :176)
    gamma_cos = jnp.stack([g0, zero, g2, zero, g4, zero], axis=-1)
    gamma_sin = jnp.stack([zero, g1, zero, g3, zero, g5], axis=-1)
    hfac_cos, _ = _eval_root_multipole(hvecs, gamma_cos, mass, q)
    hfac_sin, _ = _eval_root_multipole(hvecs, gamma_sin, mass, q)

    hr_scaled = 2 * jnp.pi / L * hvecs  # (H, 3)
    hdotx = dr @ hr_scaled.T  # (N, H)
    c, s = jnp.cos(hdotx), jnp.sin(hdotx)
    u = -(c @ hfac_cos + s @ hfac_sin)
    # acc = sum_h (hfac_cos * s - hfac_sin * c) * hr_scaled (:316)
    a = (s * hfac_cos[None, :] - c * hfac_sin[None, :]) @ hr_scaled
    return u, a


@functools.partial(jax.jit, static_argnames=("meta", "cfg", "ecfg", "shard"))
def compute_gravity_ewald(
    x, y, z, m, h, sorted_keys, box: Box,
    tree: GravityTree, meta: GravityTreeMeta, cfg: GravityConfig,
    ecfg: EwaldConfig, shard=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Periodic-box gravity: replica near field + Ewald corrections.

    Same return contract as compute_gravity. The near field runs one
    Barnes-Hut pass per replica shell offset ((2r+1)^3 passes, each a
    static jit region), matching computeGravityEwald's use of
    computeGravity(..., numReplicaShells).

    ``shard``: (axis, P, win) when running INSIDE shard_map on a local
    slab (same contract as compute_gravity): the upsweep is the psum
    leaf-payload allreduce, each replica-shell near field rides the
    halo exchange (windowed for an int ``win``; MAC-sized sparse for a
    per-distance cap tuple — the sizing unions the opened set over the
    replica shifts, so wrap-around leaves any shifted target reaches
    are covered), and the per-particle real/k-space corrections are
    row-local (the root expansion is replicated by the psum). egrav and
    diagnostics return per-shard.
    """
    L = box.lengths[0]
    n = x.shape[0]
    r = ecfg.num_replica_shells

    if cfg.multipole_order > 0:
        raise NotImplementedError(
            "spherical multipoles are open-boundary only; the Ewald path "
            "keeps the cartesian quadrupole (traversal_ewald_cpu.hpp parity)"
        )
    if shard is not None:
        from sphexa_tpu.gravity.traversal import compute_multipoles_sharded

        mp_cache = compute_multipoles_sharded(
            x, y, z, m, sorted_keys, tree, meta, shard[0]
        )
    else:
        mp_cache = compute_multipoles(x, y, z, m, sorted_keys, tree, meta)
    node_mass, node_com, node_q, _ = mp_cache

    # replica near field: ONE traced traversal scanned over the static
    # (2r+1)^3 shift table (shift/allow_self are traced, so XLA compiles a
    # single traversal body instead of 27 inlined copies)
    shells = np.array(
        [s for s in product(range(-r, r + 1), repeat=3)], np.float32
    )
    is_base = jnp.asarray(~np.any(shells != 0, axis=1))
    shifts = jnp.asarray(shells) * L
    cfg1 = dataclasses.replace(cfg, G=1.0)

    def body(carry, inp):
        ax, ay, az, phi, dmax = carry
        shift, base = inp
        dax, day, daz, dphi, d = compute_gravity(
            x, y, z, m, h, sorted_keys, box, tree, meta, cfg1,
            shift=shift, allow_self=~base, with_phi=True, mp_cache=mp_cache,
            shard=shard,
        )
        dmax = {k: jnp.maximum(dmax[k], d[k]) for k in dmax}
        return (ax + dax, ay + day, az + daz, phi + dphi, dmax), None

    zeros = jnp.zeros(n, x.dtype)
    diag0 = {
        "m2p_max": jnp.int32(0), "p2p_max": jnp.int32(0),
        "leaf_occ": jnp.int32(0),
        # the superblock / LET candidate high-waters must survive the
        # replica scan or the Simulation's cap overflow guards cannot fire
        "c_max": jnp.int32(0),
        "let_max": jnp.int32(0),
        "compact_width": jnp.int32(0),
    }
    if shard is not None and isinstance(shard[2], tuple):
        # sparse MAC-window mode: carry the per-shell exchange telemetry
        # through the scan (max fold — the worst shell sizes the caps);
        # keys absent from diag0 are dropped by the fold above, so these
        # exist exactly when compute_gravity emits them
        diag0["halo_rows"] = jnp.int32(0)
        diag0["halo_occ"] = jnp.float32(0)
        diag0["halo_runs"] = jnp.int32(0)
    (ax, ay, az, phi, diag), _ = jax.lax.scan(
        body, (zeros, zeros, zeros, zeros, diag0), (shifts, is_base)
    )

    root_m = node_mass[0]
    root_q = node_q[0]
    dr = jnp.stack([x, y, z], axis=1) - node_com[0][None, :]

    u_r, a_r = _real_space_correction(dr, root_m, root_q, L, ecfg)
    u_k, a_k = _k_space_correction(dr, root_m, root_q, L, ecfg)

    ax = (ax + a_r[:, 0] + a_k[:, 0]) * cfg.G
    ay = (ay + a_r[:, 1] + a_k[:, 1]) * cfg.G
    az = (az + a_r[:, 2] + a_k[:, 2]) * cfg.G
    phi = (phi + u_r + u_k) * cfg.G
    egrav = 0.5 * jnp.sum(m * phi)
    return ax, ay, az, egrav, diag
