"""The tree solve's list occupancies counted in numpy, for the tests of
``compute_gravity``'s ``cand_fill`` / ``m2p_fill`` / ``p2p_fill``.

The MAC geometry (subtree-com boxes, squared acceptance radii) is the
solve's own (``traversal._monotone_mac_geometry`` on the upsweep's
multipoles); the classification of every block and superblock, the counts
and the fills are taken here, in float32 numpy, block by block.
"""

import numpy as np


def list_fills(x, y, z, ccenter, chalf, mac2, valid, parent, is_leaf,
               blk, sf, caps, shards=1):
    """(cand_fill, m2p_fill, p2p_fill) of SFC-sorted targets in blocks of
    ``blk`` rows and superblocks of ``sf`` blocks (0 = none), ``caps`` =
    (super_cap, m2p_cap, p2p_cap): live slots over real lists x cap. With
    ``shards`` > 1 every slab of ``n // shards`` rows forms its own blocks
    from its first row, and the fullest slab's fills are returned (the
    mesh's diagnostics are maxima over shards)."""
    f32 = np.float32
    pos = np.stack([x, y, z], axis=1).astype(f32)
    cc, ch, m2 = (np.asarray(a, f32) for a in (ccenter, chalf, mac2))
    pcc, pch, pm2 = cc[parent], ch[parent], m2[parent]
    anc_ok = (parent != np.arange(len(parent))) & valid[parent]
    leaf_ok = is_leaf & valid

    def accept(rows, gc, gs, r2):
        lo, hi = rows.min(axis=0), rows.max(axis=0)
        bc, bs = (hi + lo) * f32(0.5), (hi - lo) * f32(0.5)
        d = np.maximum(np.abs(bc[None, :] - gc) - bs[None, :] - gs, f32(0))
        dd = d * d
        return (dd[:, 0] + dd[:, 1]) + dd[:, 2] >= r2

    def counts(rows):
        acc = valid & accept(rows, cc, ch, m2)
        anc = anc_ok & accept(rows, pcc, pch, pm2)
        return ((~anc).sum(), (acc & ~anc).sum(), (leaf_ok & ~acc).sum())

    n = len(pos)
    S = n // shards
    fills = []
    for k in range(shards):
        slab = pos[k * S:(k + 1) * S]
        cand = [counts(slab[i:i + sf * blk])[0]
                for i in range(0, S, sf * blk)] if sf else []
        per_block = [counts(slab[i:i + blk])[1:] for i in range(0, S, blk)]
        lists = [np.asarray(cand)] + list(np.asarray(per_block).T)
        fills.append([
            float(np.minimum(c, cap).sum() / f32(len(c) * cap))
            if len(c) else 0.0 for c, cap in zip(lists, caps)])
    return tuple(np.max(np.asarray(fills), axis=0))


def counted_fills(x, y, z, m, keys, box, tree, meta, cfg, shards=1):
    """``list_fills`` of SFC-sorted one-device arrays under ``cfg``, with
    the geometry from the solve's own upsweep and MAC radii."""
    from sphexa_tpu.gravity import traversal as tv

    nm, com, _, _ = tv.compute_multipoles(x, y, z, m, keys, tree, meta)
    geo = tv._monotone_mac_geometry(box, tree, meta, com, nm > 0, cfg.theta)
    return list_fills(
        *(np.asarray(a) for a in (x, y, z)), *(np.asarray(a) for a in geo),
        np.asarray(nm) > 0, np.asarray(tree.parent),
        np.asarray(tree.is_leaf), cfg.target_block, cfg.super_factor,
        (min(cfg.super_cap, meta.num_nodes), cfg.m2p_cap, cfg.p2p_cap),
        shards=shards)
