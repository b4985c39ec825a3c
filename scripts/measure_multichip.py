"""Multi-chip comm-volume measurements (VERDICT r3 #6): Wmax vs S as
particles-per-shard grows, and bytes moved per exchange stage vs the
round-2 full-array replication baseline.

Size-based (no device timing): the windowed all_to_all moves
(P-1) * Wmax rows per shard per stage; replication moved S * (P-1);
the sparse per-cell exchange ships sum(hmax) — the same formulas the
runtime ``exchange`` telemetry events stamp (docs/OBSERVABILITY.md,
schema v2), so a run's events are checkable against this script.

Usage: JAX_PLATFORMS=cpu python scripts/measure_multichip.py
       [--quick] [--json]

``--json`` prints one bench-schema line ({"metric","value","unit",
"extra","manifest"}) — the shape ``sphexa-telemetry diff`` consumes
directly or buried in a ``MULTICHIP_r*.json`` wrapper's tail, giving
multi-chip comm regressions threshold exit codes in CI (the check.sh
full gate diffs a --quick run against MULTICHIP_BASELINE.json).
``--quick`` restricts to two small deterministic rows (no settling
step) so the gate stays cheap.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp

from sphexa_tpu.init import init_sedov
from sphexa_tpu.parallel.exchange import estimate_halo_window
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.sfc.box import make_global_box
from sphexa_tpu.sfc.keys import compute_sfc_keys
from sphexa_tpu.simulation import Simulation, make_propagator_config


def measure(side, P, settle=True):
    state, box, const = init_sedov(side)
    if settle and side < 120:
        # settle one step so the measured distribution is in-run, not the
        # raw lattice; at 4M+ a CPU step costs minutes and the lattice is
        # an adequate stand-in for the volume scaling
        sim = Simulation(state, box, const, prop="std", block=8192)
        sim.step()
        state, box = sim.state, sim.box
    box = make_global_box(state.x, state.y, state.z, box)
    state, keys, _ = _sort_by_keys(state, box, "hilbert")
    cfg = make_propagator_config(state, box, const, block=8192,
                                 backend="pallas")
    n = state.n
    S = -(-n // P)
    wmax = estimate_halo_window(state.x, state.y, state.z, state.h, keys,
                                box, cfg.nbr, P=P)
    # TRUE sparse halo need: distinct remote rows each dest requires
    # (what a per-cell halo exchange — the reference's exchangeHalos —
    # would move), vs the contiguous span the windowed design ships
    from sphexa_tpu.sph.pallas_pairs import group_cell_ranges

    ranges = group_cell_ranges(state.x, state.y, state.z, state.h, keys,
                               box, cfg.nbr)
    starts = np.asarray(ranges.starts)
    lens = np.asarray(ranges.lens)
    g = cfg.nbr.group
    ng = starts.shape[0]
    sparse = []
    for dest in range(P):
        g0, g1 = dest * S // g, min(((dest + 1) * S + g - 1) // g, ng)
        need = np.zeros(n, bool)
        for st, ln in zip(starts[g0:g1].ravel(), lens[g0:g1].ravel()):
            if ln > 0:
                need[st:st + ln] = True
        need[dest * S:(dest + 1) * S] = False  # own slab rows are local
        sparse.append(int(need.sum()))
    sparse_mean = float(np.mean(sparse))
    # bytes per shard per exchange stage: window rows x (P-1) peers x
    # fields x 4B. The std step exchanges 3 stage-sets (coords+h+m for
    # density: 4f; +vol for IAD: 4f; 17f for momentum); VE exchanges 6.
    # SHIPPED rows of the sparse per-cell exchange (the default path,
    # parallel/exchange.serve_sparse): sum of the sized per-distance
    # buffers — compare against the true sparse need above
    from sphexa_tpu.parallel.sizing import device_sparse_halo

    hcells, _ = device_sparse_halo(state.x, state.y, state.z, state.h, keys,
                                   box, cfg.nbr, P=P)
    win = (P - 1) * wmax
    rep = (P - 1) * S
    # gravity near field (the MAC-sized sparse serve, r13): per-dest
    # essential rows from the need matrix (what the Warren-Salmon LET
    # would ship) vs the retired full-slab exchange's (P-1)*S, plus the
    # per-distance cap fold the serve actually sizes its buffers from
    from sphexa_tpu.gravity.tree import linkage_from_leaves
    from sphexa_tpu.parallel.sizing import (
        gravity_need_matrix,
        leaf_array_from_device_keys,
    )

    leaf_tree = leaf_array_from_device_keys(keys, bucket_size=64)
    gtree, meta = linkage_from_leaves(leaf_tree, curve="hilbert")
    need = np.asarray(gravity_need_matrix(
        state.x, state.y, state.z, state.m, keys, box, gtree, meta,
        theta=0.5, P=P))
    grav_need = float((need.sum() - np.trace(need)) / P)
    j = np.arange(P)
    grav_shipped = int(sum(int(need[(j + r) % P, j].max())
                           for r in range(1, P)))
    return dict(n=n, S=S, wmax=wmax, ratio=wmax / S,
                win_rows=win, rep_rows=rep, saving=rep / max(win, 1),
                sparse=sparse_mean, sparse_frac=sparse_mean / S,
                shipped=sum(hcells), shipped_frac=sum(hcells) / S,
                grav_need=grav_need, grav_shipped=grav_shipped,
                grav_saving=rep / max(grav_need, 1.0))


#: the cheap deterministic rows of --quick mode: lattice state (no
#: settling step). side 16 = the dryrun scale sanity row; side 40 = the
#: first size whose sparse caps are genuinely partial on the lattice
#: (saving > 1 — the quantity the CI gate can actually see regress)
QUICK_CASES = ((16, 8), (40, 8))

FULL_CASES = ((16, 8), (24, 8), (32, 8), (48, 8), (64, 8),
              (80, 8), (160, 8), (160, 16),
              (48, 2), (48, 4), (48, 16))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="two small rows, no settling step (CI gate)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print one bench-schema JSON line for "
                         "sphexa-telemetry diff")
    args = ap.parse_args(argv)
    cases = QUICK_CASES if args.quick else FULL_CASES
    results = []
    if not args.as_json:
        print(f"{'side':>5} {'n':>9} {'P':>3} {'S':>8} {'Wmax':>7} "
              f"{'Wmax/S':>7} {'rows/stage':>11} {'vs repl':>8} "
              f"{'sparse':>8} {'sparse/S':>8} {'shipped':>8} {'ship/S':>7} "
              f"{'grav':>8} {'grav sv':>8}")
    for side, P in cases:
        try:
            r = measure(side, P, settle=not args.quick)
            results.append((side, P, r))
            if not args.as_json:
                print(f"{side:>5} {r['n']:>9} {P:>3} {r['S']:>8} "
                      f"{r['wmax']:>7} {r['ratio']:>7.3f} "
                      f"{r['win_rows']:>11} {r['saving']:>7.2f}x "
                      f"{r['sparse']:>8.0f} {r['sparse_frac']:>8.3f} "
                      f"{r['shipped']:>8} {r['shipped_frac']:>7.2f} "
                      f"{r['grav_need']:>8.0f} {r['grav_saving']:>7.2f}x",
                      flush=True)
        except Exception as e:
            print(f"{side:>5} P={P} FAILED: {type(e).__name__}: {e}"[:140],
                  file=sys.stderr, flush=True)
    if not args.as_json:
        return 0
    if not results:
        print("measure_multichip: every case failed", file=sys.stderr)
        return 1
    # headline: sparse-exchange saving vs full replication at the largest
    # measured row (higher is better — same diff direction as throughput);
    # per-row extras are flat numerics so `sphexa-telemetry diff` compares
    # them with the bench-vs-bench machinery
    side, P, head = results[-1]
    extra = {}
    for s, p, r in results:
        tag = f"s{s}_p{p}"
        extra[f"{tag}_shipped_rows"] = int(r["shipped"])
        extra[f"{tag}_shipped_frac"] = round(r["shipped_frac"], 4)
        extra[f"{tag}_sparse_frac"] = round(r["sparse_frac"], 4)
        extra[f"{tag}_wmax_frac"] = round(r["ratio"], 4)
        extra[f"{tag}_saving"] = round(r["rep_rows"] / max(r["shipped"], 1),
                                       4)
        extra[f"{tag}_grav_need_rows"] = round(r["grav_need"], 1)
        extra[f"{tag}_grav_shipped_rows"] = int(r["grav_shipped"])
        extra[f"{tag}_grav_saving"] = round(r["grav_saving"], 4)
    from sphexa_tpu.telemetry.manifest import build_manifest

    print(json.dumps({
        "metric": f"sparse-halo saving vs replication "
                  f"(sedov {side}^3, P={P})",
        "value": round(head["rep_rows"] / max(head["shipped"], 1), 4),
        "unit": "x",
        "extra": extra,
        "manifest": build_manifest(
            config={"quick": bool(args.quick),
                    "cases": [list(c) for c in cases]},
            particles=head["n"],
        ),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
