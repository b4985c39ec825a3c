"""O(N^2) direct-sum gravity, the accuracy reference for the tree solver.

Counterpart of ryoanji's directSum (ryoanji/src/ryoanji/nbody/direct.cuh):
all-pairs softened interactions, used only by tests and accuracy checks
(``direct_gravity_at`` samples a few targets where all pairs are too many).
"""

import functools

import jax
import jax.numpy as jnp

from sphexa_tpu.gravity import multipole as mp


@functools.partial(jax.jit, static_argnames=("G", "block"))
def direct_gravity_at(targets, x, y, z, m, h, G: float = 1.0,
                      block: int = 64):
    """Direct sum for the particles at index array ``targets`` only, over
    ALL sources: (ax, ay, az, phi), each (len(targets),). The accuracy
    reference at sizes where the full O(N^2) sum is out of reach
    (chip_smoke.py: 256 targets against 1.1M sources). ``block`` bounds
    the (block, N) pair tiles held at once.

    Uses the same h_i+h_j clamped softening as the tree P2P so the two
    solvers agree in the near field.
    """
    n = x.shape[0]
    nt = targets.shape[0]
    num_blocks = -(-nt // block)
    idx = jnp.concatenate(
        [targets, jnp.broadcast_to(targets[-1:], (num_blocks * block - nt,))]
    ).astype(jnp.int32).reshape(num_blocks, block)

    def one_block(bi):
        mask = jnp.arange(n, dtype=jnp.int32)[None, :] != bi[:, None]
        return mp.p2p(x[bi], y[bi], z[bi], h[bi], x, y, z, m, h, mask)

    out = jax.lax.map(one_block, idx)
    return tuple(a.reshape(-1)[:nt] * G for a in out)


def direct_gravity(x, y, z, m, h, G: float = 1.0):
    """Returns (ax, ay, az, egrav) by summing every pair exactly."""
    n = x.shape[0]
    ax, ay, az, phi = direct_gravity_at(
        jnp.arange(n, dtype=jnp.int32), x, y, z, m, h, G=G,
        block=min(n, 1024))
    return ax, ay, az, 0.5 * jnp.sum(m * phi)
