"""Phase-by-phase timing of the dense MAC gravity solve at 1M (VERDICT
r4 #3: measure, then fix). Re-times compute_gravity's internal stages as
incremental jitted programs: multipoles / accept sweep / +downsweep /
+sort-compaction / +M2P gather+eval / full solve — the deltas localize
the 975 ms (round-4 measurement, tb=256).

Usage: [N_PARTS=1000000] python scripts/profile_gravity_phases.py

Recording the results (chip-harvest protocol, docs/NEXT.md round 8):
set TRACE_DIR=/path to also capture a jax.profiler trace of the full
solve — the production gravity stages carry sphexa/gravity-upsweep/
-mac/-m2p/-p2p named scopes, so `sphexa-telemetry trace $TRACE_DIR`
renders the same phase split from device-op metadata (the durable,
diffable record; the incremental re-timings below remain the
fine-grained cross-check).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.gravity import multipole as mp
from sphexa_tpu.gravity.traversal import (
    GravityConfig, compute_gravity, compute_multipoles,
    estimate_gravity_caps,
)
from sphexa_tpu.gravity.tree import build_gravity_tree
from sphexa_tpu.init.plummer import sample_plummer as plummer
from sphexa_tpu.sfc.box import BoundaryType, Box
from sphexa_tpu.sfc.keys import compute_sfc_keys

N = int(os.environ.get("N_PARTS", "1000000"))
THETA = float(os.environ.get("THETA", "0.5"))
TB = int(os.environ.get("TB", "256"))


def timed(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    out = fn(*args)  # discard the first post-compile batch
    float(jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    float(jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / reps, out


def main():
    x, y, z, m = plummer(N)
    r = float(np.max(np.abs(np.stack([x, y, z])))) * 1.001
    box = Box.create(-r, r, boundary=BoundaryType.open)
    keys = np.asarray(compute_sfc_keys(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(z), box))
    order = np.argsort(keys)
    xs, ys, zs, ms = (jnp.asarray(a[order]) for a in (x, y, z, m))
    skeys = jnp.asarray(keys[order])
    gtree, meta = build_gravity_tree(keys[order], bucket_size=64)
    hs = jnp.full_like(xs, 1e-3)
    num_n = meta.num_nodes
    print(f"N={N} nodes={num_n} leaves={meta.num_leaves} tb={TB}")

    base = GravityConfig(theta=THETA, bucket_size=64, G=1.0,
                         target_block=TB,
                         blocks_per_chunk=max(4, 2048 // TB),
                         use_pallas=jax.default_backend() == "tpu")
    cfg = estimate_gravity_caps(xs, ys, zs, ms, skeys, box, gtree, meta,
                                base, margin=1.6)
    print(f"caps: m2p={cfg.m2p_cap} p2p={cfg.p2p_cap} leaf={cfg.leaf_cap}")

    t_mp, mpc = timed(
        jax.jit(lambda *a: compute_multipoles(*a, gtree, meta, order=0)),
        xs, ys, zs, ms, skeys)
    print(f"multipoles      : {t_mp*1e3:8.1f} ms")
    node_mass, node_com, node_q, edges = mpc
    valid = node_mass > 0.0

    lengths = box.lengths
    lo = jnp.stack([box.lo[0], box.lo[1], box.lo[2]])
    geo_center = lo[None, :] + gtree.center_frac * lengths[None, :]
    geo_size = gtree.halfsize_frac[:, None] * lengths[None, :]
    l_node = 2.0 * jnp.max(geo_size, axis=1)
    s_off = jnp.sqrt(jnp.sum((node_com - geo_center) ** 2, axis=1))
    # monotone MAC preamble (mirrors compute_gravity)
    smax = jnp.where(valid, s_off, 0.0)
    BIG = jnp.float32(1e15)
    com_lo = jnp.where(valid[:, None], node_com, BIG)
    com_hi = jnp.where(valid[:, None], node_com, -BIG)
    for s_, e_ in reversed(meta.level_ranges[1:]):
        par_ = gtree.parent[s_:e_]
        smax = smax.at[par_].max(smax[s_:e_])
        com_lo = com_lo.at[par_].min(com_lo[s_:e_])
        com_hi = com_hi.at[par_].max(com_hi[s_:e_])
    ccenter = jnp.where(valid[:, None], 0.5 * (com_lo + com_hi), BIG)
    chalf = jnp.where(valid[:, None],
                      jnp.maximum(0.5 * (com_hi - com_lo), 0.0), 0.0)
    mac2 = (l_node / cfg.theta + smax) ** 2
    self_parent = gtree.parent == jnp.arange(num_n,
                                             dtype=gtree.parent.dtype)

    blk = cfg.target_block
    num_blocks = -(-N // blk)
    chunk = cfg.blocks_per_chunk
    num_chunks = -(-num_blocks // chunk)
    idx = jnp.arange(num_chunks * chunk * blk, dtype=jnp.int32)
    idx = jnp.minimum(idx, N - 1).reshape(num_chunks, chunk, blk)

    node_packed = jnp.concatenate(
        [node_com, node_q, node_mass[:, None],
         jnp.zeros((num_n, 1), node_com.dtype)], axis=1)

    def _bbox(tx, ty, tz):
        bc = jnp.stack([(jnp.max(tx) + jnp.min(tx)) * 0.5,
                        (jnp.max(ty) + jnp.min(ty)) * 0.5,
                        (jnp.max(tz) + jnp.min(tz)) * 0.5])
        bs = jnp.stack([(jnp.max(tx) - jnp.min(tx)) * 0.5,
                        (jnp.max(ty) - jnp.min(ty)) * 0.5,
                        (jnp.max(tz) - jnp.min(tz)) * 0.5])
        return bc, bs

    def _accept(bc, bs, gc, gs, m2):
        d = jnp.maximum(jnp.abs(bc[None, :] - gc) - bs[None, :] - gs, 0.0)
        return jnp.sum(d * d, axis=1) >= m2

    def block_phase(bi, phase):
        tx, ty, tz = x_[bi], y_[bi], z_[bi]
        bc, bs = _bbox(tx, ty, tz)
        accept = valid & _accept(bc, bs, ccenter, chalf, mac2)
        if phase == 1:
            return jnp.sum(accept)
        # monotone MAC: one parent gather replaces the level downsweep
        anc = jnp.where(self_parent, False, accept[gtree.parent])
        m2p_mask = accept & ~anc
        p2p_mask = gtree.is_leaf & valid & ~accept
        if phase == 2:
            return jnp.sum(m2p_mask) + jnp.sum(p2p_mask)
        m2p_n = jnp.sum(m2p_mask)
        cls = jnp.where(m2p_mask, 0, jnp.where(p2p_mask, 1, 2))
        nbits = max(1, int(np.ceil(np.log2(max(num_n, 2)))))
        iota_k = jnp.arange(num_n, dtype=jnp.int32)
        ks = jnp.sort((cls.astype(jnp.int32) << nbits) | iota_k)
        order_all = ks & jnp.int32((1 << nbits) - 1)
        cls_sorted = ks >> nbits
        padn = max(cfg.m2p_cap, cfg.p2p_cap)
        order_all = jnp.concatenate(
            [order_all, jnp.full((padn,), num_n - 1, order_all.dtype)])
        cls_sorted = jnp.concatenate(
            [cls_sorted, jnp.full((padn,), 2, cls_sorted.dtype)])
        order_m = jnp.minimum(order_all[: cfg.m2p_cap], num_n - 1)
        m2p_ok = cls_sorted[: cfg.m2p_cap] == 0
        if phase == 3:
            return jnp.sum(order_m) + jnp.sum(m2p_ok) + m2p_n
        nd = node_packed[order_m]
        ax, ay, az, phi = mp.m2p(
            tx, ty, tz, nd[:, 0:3], nd[:, 3:10], nd[:, 10], m2p_ok)
        return jnp.sum(ax) + jnp.sum(ay) + jnp.sum(az)

    x_, y_, z_ = xs, ys, zs

    def make(phase):
        def run():
            def one_chunk(bidx):
                return jax.vmap(lambda b: block_phase(b, phase))(bidx)
            return jax.lax.map(one_chunk, idx)
        return jax.jit(run)

    labels = {1: "accept sweep    ", 2: "+downsweep      ",
              3: "+sort+compaction", 4: "+M2P gather+eval"}
    prev = 0.0
    for phase in (1, 2, 3, 4):
        t, _ = timed(make(phase))
        print(f"{labels[phase]}: {t*1e3:8.1f} ms   (delta "
              f"{(t-prev)*1e3:+8.1f} ms)")
        prev = t

    t_full, out = timed(
        jax.jit(lambda: compute_gravity(
            xs, ys, zs, ms, hs, skeys, box, gtree, meta, cfg,
            mp_cache=mpc)))
    d = {k: float(v) for k, v in out[4].items()}
    print(f"full solve      : {t_full*1e3:8.1f} ms   "
          f"({N/t_full/1e6:.2f}M parts/s, m2p_max={int(d['m2p_max'])} "
          f"p2p_max={int(d['p2p_max'])})")

    # compaction-mode comparison (ISSUE 1): the flat per-block sort vs
    # the bitmask-rank kernel, flat and hierarchical. compact_width is
    # the per-block candidate width of the list materialization — the
    # op-count/complexity proxy recorded when no chip is available
    # (blocks x width ~ hot-path compaction work; the sort pays an extra
    # log-factor on top of its width).
    import dataclasses

    sf = int(os.environ.get("SUPER", "8"))
    variants = [("sort     sf=0 ", cfg)]
    cfg_b0 = dataclasses.replace(cfg, compaction="bitmask", super_factor=0)
    variants.append(("bitmask  sf=0 ", cfg_b0))
    base_h = dataclasses.replace(base, compaction="bitmask", super_factor=sf)
    cfg_h = estimate_gravity_caps(xs, ys, zs, ms, skeys, box, gtree, meta,
                                  base_h, margin=1.6)
    variants.append((f"bitmask  sf={sf}", cfg_h))
    for tag, c in variants:
        t, o = timed(jax.jit(lambda c=c: compute_gravity(
            xs, ys, zs, ms, hs, skeys, box, gtree, meta, c, mp_cache=mpc)))
        dd = {k: float(v) for k, v in o[4].items()}
        print(f"solve [{tag}]: {t*1e3:8.1f} ms   compact_width="
              f"{int(dd['compact_width'])} c_max={int(dd['c_max'])} "
              f"m2p_max={int(dd['m2p_max'])}")

    # the durable record: capture the tuned solve under the profiler and
    # attribute by the in-graph gravity phases (sphexa-telemetry trace)
    trace_dir = os.environ.get("TRACE_DIR")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        for _ in range(2):
            jax.block_until_ready(compute_gravity(
                xs, ys, zs, ms, hs, skeys, box, gtree, meta, cfg,
                mp_cache=mpc))
        jax.profiler.stop_trace()
        print(f"trace -> {trace_dir}  (render: sphexa-telemetry trace "
              f"{trace_dir})")


if __name__ == "__main__":
    main()
