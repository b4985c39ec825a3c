"""What a pass over the persistent lists is made of: the list kernels at
Sedov ``-n 160`` (the 4.1M cells' lists) as they are and with ONE part
knocked out at a time, on the chip.

    chiprun -- python scripts/bench_walk_fixed.py [-n 160] [--out chiprun_out/<file>.json]

No tracing can look inside a Mosaic kernel, so the parts are priced by
taking them out: each variant is a THROWAWAY copy of
``sphexa_tpu/sph/pallas_pairs.py`` under ``_chipwork/walk_fixed/`` with a
few lines of its two list kernels rewritten (``VARIANTS``: every edit must
match the source exactly once per kernel, or the script stops), imported
beside the real module and timed on the real lists. Outputs of a variant are
garbage; only its time is read.

  whole   the kernel as it is
  a       no copy started after the ``LIST_RING - 1`` a group starts with,
          and none waited for but those (a semaphore left signalled halts
          the core): the visit chain and the math alone, on stale rows
  b       the chunks of a run removed (its tile's unrolled visits), copies
          kept: the DMA pipeline, the run loop and the grid step
  c       the pair math removed (``stage_math`` / ``chunk_math``), chunk
          visits, gathers and merges kept: whole - c is the math's share
  d       every group's run count (and flush) forced to 0: the grid step's
          own price (the SMEM and VMEM blocks a step brings in and takes
          out, the accumulators' zeroing, ``finalize``)

Two ops: ``density`` (on lists it runs the SKIP form of
``group_pair_engine``: every kept chunk's 128 lanes, no compaction) and std
``momentum-energy`` (``group_pair_engine_lists``: the list WALK, compacted
lanes). Prints ms a pass and us a group-pass; a time, so from the chip
alone (off a TPU it refuses).
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ENGINE = os.path.join(ROOT, "sphexa_tpu", "sph", "pallas_pairs.py")
WORK = os.path.join(ROOT, "_chipwork", "walk_fixed")

#: variant -> [(old, new, kernels that must hold it)]; a kernel is the text
#: from its ``def`` to the next top-level ``def``
SKIP, WALK = "group_pair_engine", "group_pair_engine_lists"
VARIANTS = {
    "whole": [],
    "a": [
        ("_prefetch(w, slot)\n", "pass\n", (SKIP, WALK)),
        ("dma(w, slot).wait()\n",
         "pl.when(w < RING - 1)(lambda: dma(w, slot).wait())\n",
         (SKIP, WALK)),
    ],
    "b": [
        ("for t in range(R):\n", "for t in range(0):\n", (SKIP,)),
        ("_walk_tile(slot, slot_base, nch, row0,\n",
         "(lambda *a: None)(slot, slot_base, nch, row0,\n", (WALK,)),
    ],
    "c": [
        ("def chunk_math(t):\n",
         "def chunk_math(t):\n                return\n", (SKIP,)),
        ("def stage_math(valid):\n",
         "def stage_math(valid):\n            return\n", (WALK,)),
    ],
    "d": [
        ("nc_g = ncells[0, 0, 0]\n", "nc_g = ncells[0, 0, 0] * 0\n",
         (SKIP, WALK)),
        ("tail = tail_r[0, 0, 0]\n", "tail = tail_r[0, 0, 0] * 0\n",
         (WALK,)),
    ],
}


def _kernel_span(src: str, name: str):
    a = src.index(f"\ndef {name}(")
    b = src.index("\ndef ", a + 1)
    return a, b


def variant_module(name: str):
    """Write and import the engine's copy with ``VARIANTS[name]`` applied."""
    src = open(ENGINE).read()
    for old, new, kernels in VARIANTS[name]:
        for k in kernels:
            a, b = _kernel_span(src, k)
            body = src[a:b]
            if body.count(old) != 1:
                sys.exit(f"variant {name}: {old!r} occurs {body.count(old)} "
                         f"times in {k}, not once: the kernel moved, "
                         "re-anchor VARIANTS")
            src = src[:a] + body.replace(old, new) + src[b:]
    os.makedirs(WORK, exist_ok=True)
    path = os.path.join(WORK, f"pallas_pairs_{name}.py")
    with open(path, "w") as f:
        f.write(src)
    spec = importlib.util.spec_from_file_location(
        f"sphexa_tpu.sph._walk_fixed_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _require_chip():
    from sphexa_tpu.util.device import on_tpu
    if not on_tpu():
        sys.exit("bench_walk_fixed.py reads times: it runs on the chip "
                 "(chiprun -- python scripts/bench_walk_fixed.py)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", type=int, default=160)
    ap.add_argument("--skin-rel", type=float, default=0.2)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    _require_chip()

    from scripts.bench_lists import timed
    from sphexa_tpu.init import init_sedov
    from sphexa_tpu.propagator import _sort_by_keys
    from sphexa_tpu.simulation import make_propagator_config
    from sphexa_tpu.sph import pallas_pairs as pp
    from sphexa_tpu.sph.hydro_std import compute_eos_std
    from sphexa_tpu.sph.pair_lists import build_pair_lists, estimate_list_caps

    state, box, const = init_sedov(args.n)
    nbr = make_propagator_config(state, box, const, backend="pallas").nbr
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    skin = args.skin_rel * 2.0 * float(jnp.max(h))
    scap, rows = estimate_list_caps(x, y, z, h, keys, box, nbr, skin)
    lists = jax.jit(lambda *a: build_pair_lists(
        *a, box, nbr, skin, scap, rows))(x, y, z, h, keys)
    assert int(lists.overflow) == 0
    groups = lists.ranges.num_groups
    print(f"N={state.n} groups={groups} level={nbr.level} "
          f"run_cap={nbr.run_cap} slot_cap={scap} slots_cap={rows}",
          flush=True)

    rho, _, _ = pp.pallas_density(x, y, z, h, m, None, box, const, nbr,
                                  lists=lists)
    p, c = compute_eos_std(ss.temp, rho, const)
    cs, _ = pp.pallas_iad(x, y, z, h, m / rho, None, box, const, nbr,
                          lists=lists)
    margs = (x, y, z, ss.vx, ss.vy, ss.vz, h, m, rho, p, c, *cs)

    table = {}
    for name in VARIANTS:
        mod = variant_module(name)
        f_d = jax.jit(lambda ls, *a: mod.pallas_density(
            *a, None, box, const, nbr, lists=ls)[0])
        f_m = jax.jit(lambda ls, *a: mod.pallas_momentum_energy_std(
            *a, None, box, const, nbr, lists=ls)[:4])
        row = {}
        for op, fn, a in (("density", f_d, (x, y, z, h, m)),
                          ("momentum-energy", f_m, margs)):
            t, _ = timed(fn, lists, *a, reps=args.reps)
            row[op] = {"ms": round(t * 1e3, 3),
                       "us_group": round(t * 1e6 / groups, 4)}
        table[name] = row
        print(f"{name:6s} " + "  ".join(
            f"{op} {v['ms']:8.2f} ms {v['us_group']:7.3f} us/group"
            for op, v in row.items()), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"n": int(state.n), "groups": int(groups),
                       "device": jax.devices()[0].device_kind,
                       "table": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
