"""Hierarchical (superblock) MAC at the tree scale it exists for
(VERDICT r3 #4): a synthetic Plummer sphere at N >= 1e6 builds a
>=1e5-node tree; the dense blocks-x-nodes classification is compared
against the two-level super_factor path (GravityConfig.super_factor),
with mac_work_ratio and end-to-end solve throughput reported.

Usage: [N_PARTS=4000000] [THETA=0.5] python scripts/bench_gravity_scale.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.gravity.traversal import (
    GravityConfig,
    compute_gravity,
    estimate_gravity_caps,
)
from sphexa_tpu.gravity.tree import build_gravity_tree
from sphexa_tpu.sfc.box import BoundaryType, Box
from sphexa_tpu.sfc.keys import compute_sfc_keys

N = int(os.environ.get("N_PARTS", "4000000"))
THETA = float(os.environ.get("THETA", "0.5"))
BUCKET = int(os.environ.get("BUCKET", "64"))
SUPER = int(os.environ.get("SUPER", "8"))


from sphexa_tpu.init.plummer import sample_plummer as plummer


def time_solve(tag, args, cfg, iters=3):
    out = compute_gravity(*args, cfg)
    jax.block_until_ready(out)
    # warmup batch (the first post-compile run is an outlier)
    out = compute_gravity(*args, cfg)
    jax.block_until_ready(out)
    _ = float(out[3])
    best = 1e9
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = compute_gravity(*args, cfg)
        jax.block_until_ready(out)
        _ = float(out[3])
        best = min(best, (time.perf_counter() - t0) / iters)
    d = {k: float(v) for k, v in out[4].items()}
    print(f"{tag}: {best*1e3:9.1f} ms  {N/best/1e6:6.2f}M parts/s  "
          f"egrav={float(out[3]):+.6e}  mac_work_ratio={d['mac_work_ratio']:.4f} "
          f"m2p={int(d['m2p_max'])} p2p={int(d['p2p_max'])} "
          f"c_max={int(d['c_max'])}", flush=True)
    return best, out


def main():
    x, y, z, m = plummer(N)
    r = float(np.max(np.abs(np.stack([x, y, z])))) * 1.001
    box = Box.create(-r, r, boundary=BoundaryType.open)
    keys = np.asarray(compute_sfc_keys(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(z), box))
    order = np.argsort(keys)
    xs, ys, zs, ms = (jnp.asarray(a[order]) for a in (x, y, z, m))
    skeys = jnp.asarray(keys[order])
    t0 = time.perf_counter()
    gtree, meta = build_gravity_tree(keys[order], bucket_size=BUCKET)
    print(f"N={N} tree: {meta.num_nodes} nodes / {meta.num_leaves} leaves "
          f"({time.perf_counter()-t0:.1f}s host build)", flush=True)
    hs = jnp.full_like(xs, 1e-3)

    args = (xs, ys, zs, ms, hs, skeys, box, gtree, meta)
    results = {}
    compaction = os.environ.get("COMPACT", "sort")  # sort | bitmask
    # hierarchical pre-pass factor; 0 keeps the flat sweep
    sf_env = SUPER if compaction == "bitmask" else 0
    for tb in (64, 128, 256, 512):
        base = GravityConfig(theta=THETA, bucket_size=BUCKET, G=1.0,
                             target_block=tb,
                             blocks_per_chunk=max(4, 2048 // tb),
                             compaction=compaction, super_factor=sf_env,
                             use_pallas=jax.default_backend() == "tpu")
        cfg0 = estimate_gravity_caps(xs, ys, zs, ms, skeys, box, gtree,
                                     meta, base, margin=1.6)
        print(f"tb={tb}: caps m2p={cfg0.m2p_cap} p2p={cfg0.p2p_cap} "
              f"leaf={cfg0.leaf_cap}", flush=True)
        try:
            results[tb] = time_solve(f"dense tb={tb:4d}", args, cfg0)
        except Exception as e:
            print(f"tb={tb} FAILED: {type(e).__name__}: {e}"[:160],
                  flush=True)
    tbs = sorted(results)
    if len(tbs) >= 2:
        a0 = np.asarray(results[tbs[0]][1][0])
        a1 = np.asarray(results[tbs[-1]][1][0])
        scale = np.max(np.abs(a0))
        print(f"max|da|/max|a| (tb {tbs[0]} vs {tbs[-1]}) = "
              f"{np.max(np.abs(a0-a1))/scale:.3e}", flush=True)


if __name__ == "__main__":
    main()
