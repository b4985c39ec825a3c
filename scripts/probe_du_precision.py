"""Decisive rounding probe for the conservation gap (docs/NEXT.md):
recompute one shock-phase VE force evaluation through the XLA pipeline at
f32 AND f64 and compare Sum m*du. If dt * |S32 - S64| ~ 6e-6 * e0 (the
measured per-step drift), f32 pair-sum rounding drives the drift and
compensated engine accumulation closes it; if it is far smaller, the
drift is inherent scheme truncation at the Courant-limited shock.

save mode (TPU):  python scripts/probe_du_precision.py save
cmp mode (CPU):   JAX_PLATFORMS=cpu python scripts/probe_du_precision.py cmp
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

MODE = sys.argv[1] if len(sys.argv) > 1 else "save"
STATES = "/tmp/du_probe_states.npz"


def save():
    from sphexa_tpu.init import init_sedov
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_sedov(50)
    sim = Simulation(state, box, const, prop="ve", block=8192,
                     check_every=10)
    out = {}
    for s in range(151):
        if s in (100, 150):
            st = sim.state
            for f in ("x", "y", "z", "vx", "vy", "vz", "h", "m", "temp",
                      "alpha"):
                out[f"{f}_{s}"] = np.asarray(getattr(st, f))
            out[f"min_dt_{s}"] = float(st.min_dt)
        sim.step()
    np.savez(STATES, **out)
    print("saved", STATES, flush=True)


def cmp_mode():
    import jax
    # CPU f64 comparison: set the platform before jax starts a backend
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from sphexa_tpu.init import init_sedov
    from sphexa_tpu.neighbors.cell_list import find_neighbors
    from sphexa_tpu.sfc.keys import compute_sfc_keys
    from sphexa_tpu.simulation import make_propagator_config
    from sphexa_tpu.sph import hydro_std, hydro_ve

    _, box, const = init_sedov(50)
    d = np.load(STATES)

    for s in (100, 150):
        xs = {f: d[f"{f}_{s}"] for f in ("x", "y", "z", "vx", "vy", "vz",
                                         "h", "m", "temp", "alpha")}
        dt = float(d[f"min_dt_{s}"])
        keys = np.asarray(compute_sfc_keys(
            jnp.asarray(xs["x"]), jnp.asarray(xs["y"]),
            jnp.asarray(xs["z"]), box))
        order = np.argsort(keys, kind="stable")
        xs = {k: v[order] for k, v in xs.items()}
        skeys = jnp.asarray(keys[order])

        class St:  # minimal state shim for make_propagator_config
            n = xs["x"].shape[0]
            x = jnp.asarray(xs["x"]); y = jnp.asarray(xs["y"])
            z = jnp.asarray(xs["z"]); h = jnp.asarray(xs["h"])

        cfg = make_propagator_config(St, box, const, block=8192,
                                     backend="xla", ngmax=300)
        nbr = cfg.nbr

        def du_sum(dtype):
            f = lambda k: jnp.asarray(xs[k], dtype)
            x, y, z, h, m = f("x"), f("y"), f("z"), f("h"), f("m")
            vx, vy, vz = f("vx"), f("vy"), f("vz")
            temp, alpha = f("temp"), f("alpha")
            nidx, nmask, nc, occ = find_neighbors(
                x.astype(jnp.float32), y.astype(jnp.float32),
                z.astype(jnp.float32), h.astype(jnp.float32), skeys, box,
                nbr)
            assert int(occ) <= nbr.cap, int(occ)
            assert int(jnp.max(nc)) < nbr.ngmax, int(jnp.max(nc))
            blk = cfg.block
            xm = hydro_ve.compute_xmass(x, y, z, h, m, nidx, nmask, box,
                                        const, blk)
            kx, gradh = hydro_ve.compute_ve_def_gradh(
                x, y, z, h, m, xm, nidx, nmask, box, const, blk)
            prho, c, rho, p = hydro_ve.compute_eos_ve(temp, m, kx, xm,
                                                      gradh, const)
            cs = hydro_std.compute_iad(x, y, z, h, xm / kx, nidx, nmask,
                                       box, const, blk)
            dvout = hydro_ve.compute_iad_divv_curlv(
                x, y, z, vx, vy, vz, h, kx, xm, *cs, nidx, nmask, box,
                const, blk)
            divv = dvout[0]
            alpha2 = hydro_ve.compute_av_switches(
                x, y, z, vx, vy, vz, h, c, kx, xm, divv, alpha, *cs,
                nidx, nmask, box, jnp.asarray(dt, dtype), const, blk)
            ax, ay, az, du, _ = hydro_ve.compute_momentum_energy_ve(
                x, y, z, vx, vy, vz, h, m, prho, c, kx, xm, alpha2, *cs,
                nidx, nmask, nc, box, const, blk)
            m64 = np.asarray(m, np.float64)
            return (float(np.sum(m64 * np.asarray(du, np.float64))),
                    float(np.sum(m64 * (np.asarray(vx, np.float64)
                                        * np.asarray(ax, np.float64)
                                        + np.asarray(vy, np.float64)
                                        * np.asarray(ay, np.float64)
                                        + np.asarray(vz, np.float64)
                                        * np.asarray(az, np.float64)))))

        s32, w32 = du_sum(jnp.float32)
        s64, w64 = du_sum(jnp.float64)
        print(f"step {s}: dt={dt:.3e}")
        print(f"  Sum m du   f32={s32:+.6e} f64={s64:+.6e} "
              f"dt*diff={dt*(s32-s64):+.3e}")
        print(f"  Sum m v.a  f32={w32:+.6e} f64={w64:+.6e}")
        print(f"  closure f32 (heat+work)*dt = {dt*(s32+w32):+.3e}")
        print(f"  closure f64 (heat+work)*dt = {dt*(s64+w64):+.3e}",
              flush=True)


if MODE == "save":
    save()
else:
    cmp_mode()
