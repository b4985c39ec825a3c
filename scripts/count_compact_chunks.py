"""Count, on the CPU, what share of the chunks the tree solve's compaction
kernel walks hold a live lane (``compute_gravity``'s ``prepass_chunk_live`` /
``compact_chunk_live``), at any size and without running the solve.

    python3 scripts/count_compact_chunks.py [--side 128] [--shards 1]

The classification is tests/gravity_counts.py's (float32 numpy on the
solve's own MAC geometry) with an ``any`` per 128 slots: the superblocks'
pre-pass over the full tree (``--shards 4``: over each slab's LET list) and
the blocks' main pass over their superblock's candidate list, under the
solver shape and the caps the program picks at that size on the Mosaic
backend (``gravity_tuning``, ``estimate_gravity_caps``). ISSUE 39 was sized
on these counts: a dead chunk costs the kernel a scalar test, a live one
three MXU products a class. Counts, never times: ~25 s at --side 128
(1,098,340 particles), a few minutes at --side 200 --shards 4.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--side", type=int, default=128,
                    help="Evrard lattice side (128 = 1,098,340 particles)")
    ap.add_argument("--shards", type=int, default=1,
                    help="slabs of a mesh run (4: the LET pre-pass)")
    ap.add_argument("--theta", type=float, default=0.5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from gravity_counts import counted_chunk_live

    from sphexa_tpu.gravity.traversal import (
        GravityConfig, estimate_gravity_caps, gravity_tuning)
    from sphexa_tpu.gravity.tree import build_gravity_tree
    from sphexa_tpu.init import init_evrard
    from sphexa_tpu.sfc.box import make_global_box
    from sphexa_tpu.sfc.keys import compute_sfc_keys

    state, box, _ = init_evrard(args.side)
    n = state.n // args.shards * args.shards  # whole slabs, as run.py trims
    x, y, z, m = (a[:n] for a in (state.x, state.y, state.z, state.m))
    gbox = make_global_box(x, y, z, box)
    keys = compute_sfc_keys(x, y, z, gbox)
    order = jnp.argsort(keys)
    x, y, z, m, keys = (a[order] for a in (x, y, z, m, keys))
    shape = gravity_tuning(n, True)
    if shape["compaction"] != "bitmask":
        sys.exit(f"{n} particles: the program picks the sort compaction "
                 "here, which has no kernel and no chunks to count")
    cfg = GravityConfig(theta=args.theta, bucket_size=64, **shape)
    tree, meta = build_gravity_tree(np.asarray(keys), cfg.bucket_size)
    cfg = estimate_gravity_caps(
        x, y, z, m, keys, gbox, tree, meta, cfg,
        let_shards=args.shards if args.shards > 1 else 0)
    pre, main_ = counted_chunk_live(x, y, z, m, keys, gbox, tree, meta, cfg,
                                    shards=args.shards)
    row = cfg.let_cap if args.shards > 1 else meta.num_nodes
    print(json.dumps({
        "particles": int(n), "shards": args.shards,
        "tree_nodes": meta.num_nodes, "prepass_row_slots": int(row),
        "prepass_row_chunks": -(-int(row) // 128),
        "super_cap": cfg.super_cap,
        "prepass_chunk_live": round(float(pre), 4),
        "compact_chunk_live": round(float(main_), 4)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
