"""Ablation timing of the std pallas pipeline: ONE jitted program per
variant (sort+prologue+ops), so dispatch overhead cancels and per-op
cost = full - variant_without_op.

Usage: [PROF_SIDE=100] [PROF_ARGS='...'] python scripts/profile_ablate.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from sphexa_tpu.init import init_sedov
from sphexa_tpu.simulation import Simulation, make_propagator_config
from sphexa_tpu.sfc.box import make_global_box
from sphexa_tpu.sfc.keys import compute_sfc_keys
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.sph import hydro_std
from sphexa_tpu.sph import pallas_pairs as pp

SIDE = int(os.environ.get("PROF_SIDE", "100"))
ITERS = int(os.environ.get("PROF_ITERS", "5"))


def parse_args():
    kw = dict(cell_target=128, run_cap=1536, gap=384, group=64)
    for part in os.environ.get("PROF_ARGS", "").split(","):
        if "=" in part:
            k, v = part.split("=")
            kw[k.strip()] = int(v)
    return kw


def main():
    kw = parse_args()
    state, box, const = init_sedov(SIDE)
    sim = Simulation(state, box, const, prop="std", block=8192)
    for _ in range(2):
        sim.step()
    state, box = sim.state, sim.box
    box = make_global_box(state.x, state.y, state.z, box)
    state, _, _ = _sort_by_keys(state, box, "hilbert")
    n = state.n

    cfg = make_propagator_config(
        state, box, const, block=8192, backend="pallas", **kw)
    nbr = cfg.nbr
    print(f"n={n} level={nbr.level} cap={nbr.cap} win={nbr.window} "
          f"group={nbr.group} run_cap={nbr.run_cap} gap={nbr.gap}",
          flush=True)

    x, y, z, h, m = state.x, state.y, state.z, state.h, state.m
    args = (x, y, z, h, m, state.temp, state.vx, state.vy, state.vz)

    def build(with_sort=True, with_pro=True, with_den=True, with_iad=True,
              with_mom=True):
        @jax.jit
        def pipe(x, y, z, h, m, temp, vx, vy, vz):
            acc = jnp.zeros_like(x)
            keys = compute_sfc_keys(x, y, z, box)
            if with_sort:
                order = jnp.argsort(keys)
                keys = keys[order]
                mat = jnp.stack([x, y, z, h, m, temp, vx, vy, vz], 1)[order]
                x2, y2, z2, h2, m2, temp2, vx2, vy2, vz2 = (
                    mat[:, i] for i in range(9))
            else:
                keys = jnp.sort(keys)
                x2, y2, z2, h2, m2, temp2, vx2, vy2, vz2 = (
                    x, y, z, h, m, temp, vx, vy, vz)
            if with_pro:
                ranges = pp.group_cell_ranges(x2, y2, z2, h2, keys, box, nbr)
                acc = acc + ranges.lens.sum()
            else:
                return acc
            if with_den:
                rho, nc, occ = pp.pallas_density(
                    x2, y2, z2, h2, m2, keys, box, const, nbr, ranges=ranges)
                acc = acc + rho
            else:
                rho = m2 / (h2 * h2 * h2)
            p, c = hydro_std.compute_eos_std(temp2, rho, const)
            if with_iad:
                cs, _ = pp.pallas_iad(
                    x2, y2, z2, h2, m2 / rho, keys, box, const, nbr,
                    ranges=ranges)
                acc = acc + cs[0]
            else:
                zz = jnp.zeros_like(x)
                cs = (1.0 / (h2 * h2), zz, zz, 1.0 / (h2 * h2), zz,
                      1.0 / (h2 * h2))
            if with_mom:
                out = pp.pallas_momentum_energy_std(
                    x2, y2, z2, vx2, vy2, vz2, h2, m2, rho, p, c, *cs,
                    keys, box, const, nbr, ranges=ranges)
                acc = acc + out[0]
            return acc

        return pipe

    def timev(name, **kwv):
        pipe = build(**kwv)
        # warmup: compile + 2 discarded batches (first post-compile run is
        # a ~1.5x outlier)
        for _ in range(3):
            out = pipe(*args)
            jax.block_until_ready(out)
            _ = float(jnp.sum(out))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(ITERS):
                out = pipe(*args)
            jax.block_until_ready(out)
            _ = float(jnp.sum(out))
            best = min(best, (time.perf_counter() - t0) / ITERS)
        print(f"{name:14s} {best*1e3:8.2f} ms", flush=True)
        return best

    t_full = timev("full")
    t_nosort = timev("-sort", with_sort=False)
    t_nden = timev("-density", with_den=False)
    t_niad = timev("-iad", with_iad=False)
    t_nmom = timev("-momentum", with_mom=False)
    t_pro = timev("sort+prologue", with_den=False, with_iad=False,
                  with_mom=False)
    t_sort = timev("sort only", with_pro=False)

    print(f"\nderived: sort~{t_sort*1e3:.1f} pro~{(t_pro-t_sort)*1e3:.1f} "
          f"den~{(t_full-t_nden)*1e3:.1f} iad~{(t_full-t_niad)*1e3:.1f} "
          f"mom~{(t_full-t_nmom)*1e3:.1f} "
          f"sortperm~{(t_full-t_nosort)*1e3:.1f}")
    print(f"full pipeline: {t_full*1e3:.1f} ms -> "
          f"{n/t_full/1e6:.2f}M updates/s (hydro only)")


if __name__ == "__main__":
    main()
