"""Per-op timing of the std pallas pipeline on the current device.

Usage: [PROF_SIDE=100] [PROF_ARGS='cell_target=128,run_cap=1536,gap=384,group=64']
       python scripts/profile_ops.py
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
    s = os.environ.get("PROF_ARGS", "")
    for part in s.split(","):
        if "=" in part:
            k, v = part.split("=")
            kw[k.strip()] = int(v)
    return kw


def timeit(fn, args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = fn(*args)
    jax.block_until_ready(out)
    # force real completion with a device_get data dependency
    _ = float(jnp.sum(jax.tree.leaves(out)[0]))
    return (time.perf_counter() - t0) / ITERS


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
          f"group={nbr.group} run_cap={nbr.run_cap} gap={nbr.gap}")

    x, y, z, h, m = state.x, state.y, state.z, state.h, state.m
    keys = jnp.sort(compute_sfc_keys(x, y, z, box))

    f_ranges = jax.jit(lambda *a: pp.group_cell_ranges(*a, box, nbr))
    t_pro = timeit(f_ranges, (x, y, z, h, keys))
    ranges = f_ranges(x, y, z, h, keys)
    nrun = float(jnp.mean(ranges.ncells.astype(jnp.float32)))
    lanes = float(jnp.sum(jnp.ceil(
        (ranges.starts % 128 + ranges.lens) / 128.0) * 128)) / n
    print(f"prologue: {t_pro*1e3:8.2f} ms   runs/group~{nrun:.1f} "
          f"chunk-lanes/target~{lanes * nbr.group / 1:.0f}")

    f_sort = jax.jit(lambda x, y, z: jnp.argsort(
        compute_sfc_keys(x, y, z, box)))
    t_sort = timeit(f_sort, (x, y, z))
    print(f"keys+argsort: {t_sort*1e3:8.2f} ms")

    f_den = jax.jit(lambda *a: pp.pallas_density(
        *a, keys, box, const, nbr, ranges=ranges))
    t_den = timeit(f_den, (x, y, z, h, m))
    rho, nc, _ = f_den(x, y, z, h, m)
    print(f"density:  {t_den*1e3:8.2f} ms   <nc>={float(jnp.mean(nc)):.1f}")

    p, c = hydro_std.compute_eos_std(state.temp, rho, const)

    f_iad = jax.jit(lambda *a: pp.pallas_iad(
        *a, keys, box, const, nbr, ranges=ranges))
    t_iad = timeit(f_iad, (x, y, z, h, m / rho))
    cs, _ = f_iad(x, y, z, h, m / rho)
    print(f"iad:      {t_iad*1e3:8.2f} ms")

    f_mom = jax.jit(lambda *a: pp.pallas_momentum_energy_std(
        *a, keys, box, const, nbr, ranges=ranges))
    args_m = (x, y, z, state.vx, state.vy, state.vz, h, m, rho, p, c) + cs
    t_mom = timeit(f_mom, args_m)
    print(f"momentum: {t_mom*1e3:8.2f} ms")

    tot = t_pro + t_sort + t_den + t_iad + t_mom
    print(f"total:    {tot*1e3:8.2f} ms  -> {n/tot/1e6:.2f}M updates/s")


if __name__ == "__main__":
    main()
