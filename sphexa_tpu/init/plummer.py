"""Synthetic Plummer-sphere sample: the centrally concentrated mass
distribution that stresses Barnes-Hut MAC classification (deep,
strongly non-uniform trees). Not a reference init case — a gravity
benchmark/test IC (scripts/bench_gravity_scale.py).
"""

import numpy as np


def sample_plummer(n: int, a: float = 1.0, rmax: float = 8.0,
                   seed: int = 3):
    """(x, y, z, m) float32 arrays of an n-particle Plummer sphere with
    scale radius ``a``, radius-clipped at ``rmax`` (total mass 1)."""
    rng = np.random.default_rng(seed)
    u = rng.uniform(0.0, 1.0, n)
    r = a / np.sqrt(np.maximum(u ** (-2.0 / 3.0) - 1.0, 1e-12))
    r = np.minimum(r, rmax)
    cth = rng.uniform(-1.0, 1.0, n)
    sth = np.sqrt(1.0 - cth * cth)
    phi = rng.uniform(0.0, 2.0 * np.pi, n)
    x = (r * sth * np.cos(phi)).astype(np.float32)
    y = (r * sth * np.sin(phi)).astype(np.float32)
    z = (r * cth).astype(np.float32)
    m = np.full(n, 1.0 / n, np.float32)
    return x, y, z, m
