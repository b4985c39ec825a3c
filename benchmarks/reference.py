"""Plain references the cells' ``correct`` compares the program with.

Straightforward ``jax.numpy`` in float32, all pairs, no neighbour search, no
tree, no kernels of the program: the same operations on the same data must
give the same answers. Both sums run over seeded *targets* against *all*
particles, in blocks, so they fit beside a 4M-particle state.

Departures from a textbook form, each because the program (and upstream
SPH-EXA) defines the quantity so:
- density: rho_i = K h_i^-3 sum_j m_j W(|r_ij| / h_i), self included,
  W(v) = sinc(pi v / 2)^n on v < 2, minimum image on periodic axes
  (sph/hydro_std.py compute_density; upstream computeDensity);
- gravity: inside h_i + h_j the distance is clamped to h_i + h_j
  (gravity/multipole.py p2p; ryoanji kernel.hpp P2P), no self term.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sinc_kernel_norm(n, support=2.0, num=20001):
    """K with  integral K W(|x|/h) h^-3 d^3x = 1, by Simpson in float64."""
    v = np.linspace(0.0, support, num)
    pv = 0.5 * np.pi * v
    w = np.ones_like(v)
    w[1:] = (np.sin(pv[1:]) / pv[1:]) ** float(n)
    f = 4.0 * np.pi * v * v * w
    dv = v[1] - v[0]
    integral = dv / 3.0 * (f[0] + f[-1] + 4.0 * f[1:-1:2].sum()
                           + 2.0 * f[2:-1:2].sum())
    return float(1.0 / integral)


def _blocks(targets, block):
    nt = targets.shape[0]
    nb = -(-nt // block)
    pad = jnp.broadcast_to(targets[-1:], (nb * block - nt,))
    return jnp.concatenate([targets, pad]).astype(jnp.int32).reshape(nb, block)


@functools.partial(jax.jit, static_argnames=("sinc_index", "periodic",
                                             "block"))
def brute_force_density(targets, x, y, z, h, m, lengths, sinc_index,
                        periodic, block=16):
    """rho at ``targets`` by the all-pairs kernel sum; ``lengths`` (3,) box
    edge lengths, ``periodic`` a static 3-tuple of bools."""
    k_norm = sinc_kernel_norm(sinc_index)

    def one_block(bi):
        d2 = jnp.zeros((block, x.shape[0]), jnp.float32)
        for a, (c, per) in enumerate(zip((x, y, z), periodic)):
            r = c[bi][:, None] - c[None, :]
            if per:
                r = r - lengths[a] * jnp.round(r / lengths[a])
            d2 = d2 + r * r
        hi = h[bi][:, None]
        v = jnp.sqrt(d2) / hi
        pv = (0.5 * jnp.pi) * v
        sinc = jnp.where(v > 0.0, jnp.sin(pv) / jnp.where(v > 0.0, pv, 1.0),
                         1.0)
        w = jnp.where(v < 2.0, sinc ** sinc_index, 0.0)
        return k_norm * jnp.sum(m[None, :] * w, axis=1) / (hi[:, 0] ** 3)

    out = jax.lax.map(one_block, _blocks(targets, block))
    return out.reshape(-1)[: targets.shape[0]]


@functools.partial(jax.jit, static_argnames=("block",))
def direct_sum_gravity(targets, x, y, z, m, h, g, block=64):
    """(ax, ay, az) at ``targets`` from all other particles."""
    n = x.shape[0]

    def one_block(bi):
        dx = x[None, :] - x[bi][:, None]
        dy = y[None, :] - y[bi][:, None]
        dz = z[None, :] - z[bi][:, None]
        r2 = dx * dx + dy * dy + dz * dz
        hij = h[bi][:, None] + h[None, :]
        r2_eff = jnp.maximum(jnp.maximum(r2, hij * hij), 1e-30)
        other = jnp.arange(n, dtype=jnp.int32)[None, :] != bi[:, None]
        w = jnp.where(other, m[None, :] * r2_eff ** -1.5, 0.0)
        return (jnp.sum(dx * w, 1), jnp.sum(dy * w, 1), jnp.sum(dz * w, 1))

    out = jax.lax.map(one_block, _blocks(targets, block))
    nt = targets.shape[0]
    return tuple(g * a.reshape(-1)[:nt] for a in out)


def seeded_targets(seed, n, count):
    """Sorted sample of ``count`` distinct particle indices drawn from
    ``--seed`` (the only thing the seed draws in these cells: the ICs are
    the upstream initialisers' own)."""
    return np.sort(np.random.default_rng(seed).choice(
        n, min(count, n), replace=False))


def vector_rel_error(got, ref):
    """Relative error of 3-vectors per target: (rms, p99)."""
    got = [np.asarray(a, np.float64) for a in got]
    ref = [np.asarray(a, np.float64) for a in ref]
    err = np.sqrt(sum((a - b) ** 2 for a, b in zip(got, ref)))
    mag = np.sqrt(sum(b ** 2 for b in ref))
    rel = err / np.maximum(mag, 1e-6)
    return float(np.sqrt(np.mean(rel ** 2))), float(np.percentile(rel, 99))


def scalar_rel_error(got, ref):
    """Largest and rms relative error of a positive scalar field."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    rel = np.abs(got - ref) / np.abs(ref)
    return float(rel.max()), float(np.sqrt(np.mean(rel ** 2)))
