"""Plain reference of the right-hand side of one ``std-cooling`` step under
self-gravity (upstream's ``evrard-cooling`` case under ``HydroGrackleProp``),
at a seeded sample of targets.

    import reference_collapse_step; reference_collapse_step.collapse_step(targets, x, y, z, vx, vy, vz, h, m, temp, chem, const=..., model=..., dt_last=...)

(a library with no command line of its own: check_collapse_step.py and
tests/test_collapse_cooling_reference.py call it.)

Composed from the three plain references the benchmark has, and importing
nothing of the program (no kernel, list, tree or physics module of
``sphexa_tpu``): the same operations on the same data must give the same
answers.

    hydro     (rho, ax, ay, az, du)_i by reference_sph_std.py's all-pairs
              rings, open box (Evrard's);
    gravity   g_i = G sum_j m_j r_ij / max(|r_ij|, h_i + h_j)^3 over ALL other
              particles by reference.direct_sum_gravity: the softening the
              program states (the distance clamped to h_i + h_j);
              a_i = hydro + g_i;
    Courant   dt_i = Kcour h_i / max_j (c_i + c_j - 3 w_ij) over the pairs the
              momentum sum keeps, c_i where no pair has a positive signal
              (upstream's tsKCourant), by one more all-pairs pass;
    dt        the minimum of the candidates ``HydroGrackleProp`` takes, in the
              order a tie resolves: ``growth`` = maxDtIncrease x the last dt,
              ``courant`` = min_i dt_i, ``cool`` = ct_crit x min_i |u_i /
              du_cool_i| (reference_cooling.cooling_time, float64, at the
              reference's OWN rho and u = cv T), ``accel`` = etaAcc sqrt(eps /
              max_i |a_i|);
    cooling   du_cool_i, the advanced fractions and the cooling time by
              reference_cooling.py (float64, eight backward-Euler subcycles)
              at the reference's own rho, u = cv T and the reference's own dt;
              du_i = hydro du_i + du_cool_i.

Every minimum and maximum runs over the TARGETS: with every particle a target
(the tier-1 tests) the step's dt is the reference's own to the last candidate;
with a sample (the chip, 1.1M rows) ``courant``, ``cool`` and ``accel`` are
upper bounds of the step's, so there the comparison hands the step's dt in
(``dt=``: the source is then integrated over that, ``candidates`` still say
what the sample gives) and says so (check_collapse_step.compare).

Departures from upstream, each because the deployment (the configuration's
``assumed``) or the program defines it so:
- GRACKLE is this repo's six-species network with the table's metal residual
  (reference_cooling.py's docstring has its own list);
- the pair cutoffs, the 3x3 inverse and the kernel are reference_sph_std.py's
  (its docstring);
- upstream's ``HydroGrackleProp`` has no density-change candidate and neither
  has this; the acceleration candidate takes the TOTAL acceleration;
- the energy equation's source is the step AVERAGE of the subcycles' rates,
  never ``(u_final - u) / dt`` (reference_cooling.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
import reference_cooling
import reference_sph_std

#: the dt candidates in the order a tie resolves (the earlier name wins)
CANDIDATES = ("growth", "courant", "cool", "accel")


@functools.partial(jax.jit, static_argnames=("block",))
def _courant(idx, x, y, z, vx, vy, vz, h, c, k_cour, block):
    """Kcour h_i / max_j (c_i + c_j - 3 w_ij) at ``idx``: all pairs with
    |r_ij| < 2 min(h_i, h_j), self excluded."""
    n = x.shape[0]

    def one_block(bi):
        rx = x[bi][:, None] - x[None, :]
        ry = y[bi][:, None] - y[None, :]
        rz = z[bi][:, None] - z[None, :]
        dist = jnp.sqrt(rx * rx + ry * ry + rz * rz)
        hi = h[bi][:, None]
        other = jnp.arange(n, dtype=jnp.int32)[None, :] != bi[:, None]
        pair = other & (dist < 2.0 * hi) & (dist < 2.0 * h[None, :])
        rv = (rx * (vx[bi][:, None] - vx[None, :])
              + ry * (vy[bi][:, None] - vy[None, :])
              + rz * (vz[bi][:, None] - vz[None, :]))
        w_ij = rv / jnp.where(pair, dist, 1.0)
        signal = c[bi][:, None] + c[None, :] - 3.0 * w_ij
        top = jnp.max(jnp.where(pair, signal, -jnp.inf), axis=1)
        return k_cour * h[bi] / jnp.where(top > 0.0, top, c[bi])

    out = jax.lax.map(one_block, reference._blocks(idx, block))
    return out.reshape(-1)[: idx.shape[0]]


def collapse_step(targets, x, y, z, vx, vy, vz, h, m, temp, chem, *, const,
                  model, dt_last, dt=None, block=64, evolve_species=True,
                  product_dtype=None):
    """The step's right-hand side at ``targets`` (sorted int indices into the
    whole particle set), as a dict of numpy arrays and floats:

    ``rho``, ``ax_hydro`` / ``ay_hydro`` / ``az_hydro``, ``du_hydro`` (float32,
    reference_sph_std); ``gx`` / ``gy`` / ``gz`` (float32, direct sum);
    ``ax`` / ``ay`` / ``az`` = hydro + gravity; ``dt_courant`` per target;
    ``candidates`` {name: dt}, ``limiter`` (the name of the smallest) and
    ``dt`` (its value, or the ``dt`` handed in: a sample's minima bound the
    step's from above); ``du_cool``, ``fractions`` {species: array},
    ``t_cool`` (float64, reference_cooling, at the reference's own rho and
    that dt); ``du`` = ``du_hydro + du_cool``.

    ``chem`` is {field: (N,) array} row-aligned with the particles;
    ``const`` the case's constants as a dict (``gamma``, ``cv``,
    ``sinc_index``, ``g``, ``k_cour``, ``eta_acc``, ``eps``,
    ``max_dt_increase``); ``model`` the configuration's ``cooling`` block;
    ``dt_last`` the last step's dt. ``product_dtype`` is
    reference_sph_std's lower-precision control."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x, y, z, vx, vy, vz, h, m, temp = map(f32, (x, y, z, vx, vy, vz, h, m,
                                                temp))
    targets = np.asarray(targets)
    tj = jnp.asarray(targets, jnp.int32)
    out = reference_sph_std.std_forces(
        targets, x, y, z, vx, vy, vz, h, m, temp, gamma=const["gamma"],
        cv=const["cv"], sinc_index=const["sinc_index"], block=block,
        product_dtype=product_dtype)
    out = {"rho": out["rho"], "ax_hydro": out["ax"], "ay_hydro": out["ay"],
           "az_hydro": out["az"], "du_hydro": out["du"],
           "ring_a": out["ring_a"], "ring_b": out["ring_b"]}
    with jax.default_matmul_precision("highest"):
        grav = reference.direct_sum_gravity(tj, x, y, z, m, h, const["g"],
                                            block=block)
        c = jnp.sqrt((const["gamma"] - 1.0) * const["cv"] * temp)
        out["dt_courant"] = np.asarray(_courant(
            tj, x, y, z, vx, vy, vz, h, c, const["k_cour"], block))
    for k, g in zip("xyz", grav):
        out["g" + k] = np.asarray(g)
        out[f"a{k}"] = out[f"a{k}_hydro"] + out["g" + k]

    rho = out["rho"].astype(np.float64)
    u = const["cv"] * np.asarray(temp, np.float64)[targets]
    chem_t = {k: np.asarray(v, np.float64)[targets] for k, v in chem.items()}
    out["t_cool"] = reference_cooling.cooling_time(rho, u, chem_t, model,
                                                   evolve_species)
    a_max = np.sqrt(np.max(sum(out[f"a{k}"].astype(np.float64) ** 2
                               for k in "xyz")))
    out["candidates"] = {
        "growth": float(const["max_dt_increase"] * dt_last),
        "courant": float(out["dt_courant"].min()),
        "cool": float(model["ct_crit"] * out["t_cool"].min()),
        "accel": float(const["eta_acc"] * np.sqrt(const["eps"] / a_max)),
    }
    out["limiter"] = min(CANDIDATES, key=lambda k: out["candidates"][k])
    out["dt"] = out["candidates"][out["limiter"]] if dt is None else float(dt)
    out["du_cool"], out["fractions"], _ = reference_cooling.step(
        out["dt"], rho, u, chem_t, model, evolve_species=evolve_species)
    out["du"] = out["du_hydro"].astype(np.float64) + out["du_cool"]
    return out
