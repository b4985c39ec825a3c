"""Measure the list-walk engine vs the streaming engine per op on real
TPU hardware (Sedov 100^3 by default; ``--init noh -n 128``,
``--init wind-shock -n 100`` and ``--init evrard -n 128`` build the other
list cells' geometries) plus the list-build cost. ``--steps K`` first
advances the IC by K steps of the program (std, or VE with ``--ve``;
Evrard under its self-gravity) and measures on that state. The streamed
ops run under the streamed sizing, the list ops under the program's list
sizing (window with the skin and the open-box margin cell, ``slot_cap``,
``slots_cap``): what a run with ``use_lists`` False and True compiles.

Timing follows the rules in docs/NEXT.md: chain a data dependency across
repeats and discard the first post-compile batch.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.init import make_initializer
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.simulation import hull_h_relax, make_propagator_config
from sphexa_tpu.sph import pallas_pairs as pp
from sphexa_tpu.sph.hydro_std import compute_eos_std
from sphexa_tpu.sfc.box import make_global_box
from sphexa_tpu.sph.pair_lists import build_pair_lists
from sphexa_tpu.util.device import require_tpu


def _barrier(out):
    """A DEPENDENT scalar fetch as the completion barrier (the 2026-08
    setup saw block_until_ready return early on small pallas_call
    outputs; docs/NEXT.md)."""
    leaf = jax.tree.leaves(out)[0]
    float(jnp.sum(leaf.astype(jnp.float32) if leaf.dtype != jnp.float32
                  else leaf))


def timed(fn, *args, reps=10, **kw):
    out = fn(*args, **kw)           # compile
    _barrier(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    _barrier(out)
    return (time.perf_counter() - t0) / reps, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--init", default="sedov",
                    choices=["sedov", "noh", "wind-shock", "evrard"])
    ap.add_argument("--steps", type=int, default=0,
                    help="advance the IC by this many steps first")
    ap.add_argument("-n", type=int, default=100)
    ap.add_argument("--skin-rel", type=float, default=0.2,
                    help="skin as a fraction of 2*h_max")
    ap.add_argument("--ve", action="store_true",
                    help="also measure the VE ops, lists vs streamed")
    args = ap.parse_args()

    # every line below is a time: no interpreted rehearsal off the chip
    dev = require_tpu("scripts/bench_lists.py")
    print(f"# platform={dev.platform} kind={dev.kind!r} count={dev.count}")
    state, box, const = make_initializer(args.init)(args.n)
    # the program's first sizing is for where the hull's h is heading;
    # after steps, for what the first verified step showed
    h_relax = hull_h_relax(state, box, const.ng0)
    if args.steps:
        from sphexa_tpu.simulation import Simulation
        from sphexa_tpu.telemetry import Telemetry
        from sphexa_tpu.telemetry.sinks import MemorySink

        sink = MemorySink()
        sim = Simulation(state, box, const, prop="ve" if args.ve else "std",
                         backend="pallas", check_every=4,
                         telemetry=Telemetry(sinks=[sink]))
        for _ in range(args.steps):
            sim.step()
        sim.flush()
        # the list lifecycle the steps went through
        for e in sink.events:
            if e["kind"] in ("rebuild_lists", "reconfigure", "rollback"):
                print("  " + " ".join(f"{k}={v}" for k, v in e.items()
                                      if k not in ("t", "engine")))
        state, h_relax = sim.state, sim._h_relax
        box = make_global_box(state.x, state.y, state.z, sim.box)
        print(f"after {args.steps} steps: t={float(state.ttot):.5f} "
              f"dt={float(state.min_dt):.3e} lists={sim._use_lists} "
              f"h_relax={h_relax:.3f}")
        del sim
    nbr_s = make_propagator_config(state, box, const, backend="pallas").nbr
    cfg = make_propagator_config(state, box, const, backend="pallas",
                                 use_lists=True, list_skin_rel=args.skin_rel,
                                 h_relax=h_relax)
    nbr, scap, rows = cfg.nbr, cfg.list_slot_cap, cfg.list_slots_cap
    print(f"N={state.n}  level={nbr.level} cap={nbr.cap} "
          f"window={nbr.window} (streamed {nbr_s.window}) "
          f"run_cap={nbr.run_cap}")
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m

    h_max = float(jnp.max(h))
    skin = args.skin_rel * 2.0 * h_max
    print(f"skin={skin:.5f} ({args.skin_rel} x 2h_max)  slot_cap={scap}  "
          f"slots_cap={rows}")
    assert scap > 0, "the sizing left lists off (a folded grid)"

    build = jax.jit(
        lambda *a: build_pair_lists(*a, box, nbr, skin, scap, rows))
    t_build, lists = timed(build, x, y, z, h, keys)
    assert int(lists.overflow) == 0
    print(f"slot_need={int(lists.slot_need)}/{scap}  "
          f"slots_live={int(lists.slots_live)}/{rows}  "
          f"runs={int(lists.runs_live)}")
    lanes = float(lists.lanes_total) / state.n
    print(f"list build: {t_build*1e3:7.1f} ms   lanes/target={lanes:.0f}")
    # what the walk's compaction is worth: of the lanes of the chunks a
    # pass visits, the share it stages (and does the pair math on)
    kept = int(lists.chunks_live)
    print(f"groups={lists.cnt.shape[0]}  kept chunks/group="
          f"{kept / lists.cnt.shape[0]:.1f}  lanes kept per chunk lane="
          f"{float(lists.lanes_total) / (128.0 * kept):.3f}")

    t_rng, ranges = timed(
        jax.jit(lambda *a: pp.group_cell_ranges(*a, box, nbr_s)),
        x, y, z, h, keys)
    print(f"prologue  : {t_rng*1e3:7.1f} ms")

    # ---- density
    f_s = jax.jit(lambda rng, *a: pp.pallas_density(
        *a, box, const, nbr_s, ranges=rng))
    f_l = jax.jit(lambda ls, *a: pp.pallas_density(*a, box, const, nbr,
                                                   lists=ls))
    t0, (rho0, nc0, _) = timed(f_s, ranges, x, y, z, h, m, keys)
    t1, (rho1, nc1, _) = timed(f_l, lists, x, y, z, h, m, None)
    ok = np.array_equal(np.asarray(nc0), np.asarray(nc1))
    dr = float(jnp.max(jnp.abs(rho0 - rho1) / rho0))
    print(f"density   : stream {t0*1e3:7.1f} ms  lists {t1*1e3:7.1f} ms  "
          f"x{t0/t1:.2f}  nc_eq={ok} drho={dr:.2e}")
    rho = rho0

    # ---- IAD
    p, c = compute_eos_std(ss.temp, rho, const)
    vol = m / rho
    f_s = jax.jit(lambda rng, *a: pp.pallas_iad(*a, box, const, nbr_s,
                                                ranges=rng))
    f_l = jax.jit(lambda ls, *a: pp.pallas_iad(*a, box, const, nbr,
                                               lists=ls))
    t0, (cs0, _) = timed(f_s, ranges, x, y, z, h, vol, keys)
    t1, (cs1, _) = timed(f_l, lists, x, y, z, h, vol, None)
    sc = float(jnp.max(jnp.abs(cs0[0])))
    dc = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(cs0, cs1)) / sc
    print(f"iad       : stream {t0*1e3:7.1f} ms  lists {t1*1e3:7.1f} ms  "
          f"x{t0/t1:.2f}  dC={dc:.2e}")

    # ---- momentum
    margs = (x, y, z, ss.vx, ss.vy, ss.vz, h, m, rho, p, c, *cs0)
    f_s = jax.jit(lambda rng, *a: pp.pallas_momentum_energy_std(
        *a, keys, box, const, nbr_s, ranges=rng))
    f_l = jax.jit(lambda ls, *a: pp.pallas_momentum_energy_std(
        *a, None, box, const, nbr, lists=ls))
    t0, o0 = timed(f_s, ranges, *margs)
    t1, o1 = timed(f_l, lists, *margs)
    sc = float(jnp.max(jnp.abs(o0[0])))
    da = float(jnp.max(jnp.abs(o0[0] - o1[0]))) / sc
    print(f"momentum  : stream {t0*1e3:7.1f} ms  lists {t1*1e3:7.1f} ms  "
          f"x{t0/t1:.2f}  dax={da:.2e}")

    if not args.ve:
        return

    # ---- VE ops: on lists the walk, streamed with the cull their
    # pallas_pairs.PAIR_OP_ENGINE row names
    from sphexa_tpu.sph.hydro_ve import compute_eos_ve

    f_s = jax.jit(lambda rng, *a: pp.pallas_xmass(
        *a, box, const, nbr_s, ranges=rng))
    f_l = jax.jit(lambda ls, *a: pp.pallas_xmass(*a, box, const, nbr,
                                                 lists=ls))
    t0, (xm0, _, _) = timed(f_s, ranges, x, y, z, h, m, keys)
    t1, (xm, _, _) = timed(f_l, lists, x, y, z, h, m, None)
    dd = float(jnp.max(jnp.abs(xm0 - xm) / xm0))
    print(f"xmass     : stream {t0*1e3:7.1f} ms  lists {t1*1e3:7.1f} ms  "
          f"x{t0/t1:.2f}  dxm={dd:.2e}")
    f_s = jax.jit(lambda rng, *a: pp.pallas_ve_def_gradh(
        *a, box, const, nbr_s, ranges=rng))
    f_l = jax.jit(lambda ls, *a: pp.pallas_ve_def_gradh(
        *a, box, const, nbr, lists=ls))
    t0, ((kx0, gh0), _) = timed(f_s, ranges, x, y, z, h, m, xm, keys)
    t1, ((kx, gradh), _) = timed(f_l, lists, x, y, z, h, m, xm, None)
    dd = float(jnp.max(jnp.abs(kx0 - kx) / kx0))
    dg = float(jnp.max(jnp.abs(gh0 - gradh)))
    print(f"gradh     : stream {t0*1e3:7.1f} ms  lists {t1*1e3:7.1f} ms  "
          f"x{t0/t1:.2f}  dkx={dd:.2e} dgradh={dg:.2e}")
    prho, cve, rhove, pve = compute_eos_ve(ss.temp, m, kx, xm, gradh, const)
    # the fused IAD + divv/curlv op (one pass where there were two): on
    # lists, and the streamed engine the Evrard cells run; under a
    # velocity field with a gradient, so the outputs compare on something
    two_pi = 2.0 * np.pi / box.lengths
    v = (jnp.sin(two_pi[0] * x), jnp.sin(two_pi[1] * y + two_pi[0] * x),
         jnp.cos(two_pi[2] * z))
    dv_args = (x, y, z, *v, h, kx, xm)
    for gv in (False, True):
        f_l = jax.jit(lambda ls, *a: pp.pallas_iad_divv_curlv(
            *a, None, box, const, nbr, lists=ls, with_gradv=gv))
        f_s = jax.jit(lambda rng, *a: pp.pallas_iad_divv_curlv(
            *a, keys, box, const, nbr_s, ranges=rng, with_gradv=gv))
        tl, ol = timed(f_l, lists, *dv_args)
        ts, os_ = timed(f_s, ranges, *dv_args)
        dd = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(ol[1], os_[1]))
        print(f"iad+divv{'+gradv' if gv else '      '}: stream {ts*1e3:7.1f}"
              f" ms  lists {tl*1e3:7.1f} ms  x{ts/tl:.2f}  d={dd:.2e} of "
              f"{float(jnp.max(jnp.abs(os_[1][0]))):.2f}")
    cs0 = os_[0]

    divv = os_[1][0]
    av_args = (x, y, z, *v, h, cve, kx, xm, divv, ss.alpha, *cs0)
    f_l = jax.jit(lambda ls, *a: pp.pallas_av_switches(
        *a, None, box, 1e-5, const, nbr, lists=ls))
    f_s = jax.jit(lambda rng, *a: pp.pallas_av_switches(
        *a, keys, box, 1e-5, const, nbr_s, ranges=rng))
    tl, al = timed(f_l, lists, *av_args)
    ts, as_ = timed(f_s, ranges, *av_args)
    dd = float(jnp.max(jnp.abs(al[0] - as_[0])))
    print(f"av_switch : stream {ts*1e3:7.1f} ms  lists {tl*1e3:7.1f} ms  "
          f"x{ts/tl:.2f}  d={dd:.2e}")

if __name__ == "__main__":
    main()
