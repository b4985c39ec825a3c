"""The tree solve's list occupancies counted in numpy, for the tests of
``compute_gravity``'s ``cand_fill`` / ``m2p_fill`` / ``p2p_fill`` and of its
``prepass_chunk_live`` / ``compact_chunk_live``.

The MAC geometry (subtree-com boxes, squared acceptance radii) is the
solve's own (``traversal._monotone_mac_geometry`` on the upsweep's
multipoles); the classification of every block and superblock, the counts
and the fills are taken here, in float32 numpy, block by block.
"""

import numpy as np


def _classifier(ccenter, chalf, mac2, valid, parent, is_leaf):
    """``masks(rows, nodes)`` -> (candidate, m2p, p2p) booleans of the
    ``nodes`` (an index array; default: all) against one target group's
    rows (a block's or a superblock's), as the solve's ``_packed_cand`` /
    ``_packed_cls`` class them."""
    f32 = np.float32
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

    def masks(rows, nodes=slice(None)):
        acc = valid[nodes] & accept(rows, cc[nodes], ch[nodes], m2[nodes])
        anc = anc_ok[nodes] & accept(rows, pcc[nodes], pch[nodes],
                                     pm2[nodes])
        return ~anc, acc & ~anc, leaf_ok[nodes] & ~acc

    return masks


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
    masks = _classifier(ccenter, chalf, mac2, valid, parent, is_leaf)

    def counts(rows):
        return tuple(m.sum() for m in masks(rows))

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


def _live_chunk_count(mask):
    """128-slot chunks of a row of booleans that hold a True."""
    pad = -len(mask) % 128
    return int(np.pad(mask, (0, pad)).reshape(-1, 128).any(axis=1).sum())


def chunk_live(x, y, z, ccenter, chalf, mac2, valid, parent, is_leaf,
               blk, sf, super_cap, shards=1, let_cap=0):
    """(prepass_chunk_live, compact_chunk_live) of the bitmask compaction
    with superblocks (``sf`` > 0): chunks of 128 slots that hold a live
    lane over the chunks the kernel's walk visits. The pre-pass walks
    every chunk of a superblock's row: the full tree in node order, or
    with ``shards`` > 1 the slab's essential list of ``let_cap`` slots
    (the candidates of the slab's own bounding box, ascending). The main
    pass walks a block's row, its superblock's candidate list, up to the
    list's count. Slab by slab, the largest share returned."""
    assert sf > 0
    pos = np.stack([x, y, z], axis=1).astype(np.float32)
    masks = _classifier(ccenter, chalf, mac2, valid, parent, is_leaf)
    S = len(pos) // shards
    shares = []
    for k in range(shards):
        slab = pos[k * S:(k + 1) * S]
        if shards > 1:
            row = np.flatnonzero(masks(slab)[0])[:let_cap]
            row_slots = let_cap
        else:
            row = np.arange(len(parent))
            row_slots = len(parent)
        pre, main = [0, 0], [0, 0]  # live, visited
        for i in range(0, S, sf * blk):
            cut = masks(slab[i:i + sf * blk], row)[0]
            pre[0] += _live_chunk_count(cut)
            pre[1] += -(-row_slots // 128)
            cand = row[cut][:super_cap]
            for j in range(i, min(i + sf * blk, S), blk):
                _, m2p, p2p = masks(slab[j:j + blk], cand)
                main[0] += _live_chunk_count(m2p | p2p)
                main[1] += -(-len(cand) // 128)
        shares.append([pre[0] / pre[1], main[0] / main[1]])
    return tuple(np.max(np.asarray(shares), axis=0))


def _solve_geometry(x, y, z, m, keys, box, tree, meta, cfg):
    """The arguments ``list_fills`` and ``chunk_live`` share, from the
    solve's own upsweep and MAC radii of SFC-sorted one-device arrays."""
    from sphexa_tpu.gravity import traversal as tv

    nm, com, _, _ = tv.compute_multipoles(x, y, z, m, keys, tree, meta)
    geo = tv._monotone_mac_geometry(box, tree, meta, com, nm > 0, cfg.theta)
    return (*(np.asarray(a) for a in (x, y, z)),
            *(np.asarray(a) for a in geo), np.asarray(nm) > 0,
            np.asarray(tree.parent), np.asarray(tree.is_leaf),
            cfg.target_block, cfg.super_factor)


def counted_fills(x, y, z, m, keys, box, tree, meta, cfg, shards=1):
    """``list_fills`` under ``cfg``."""
    return list_fills(
        *_solve_geometry(x, y, z, m, keys, box, tree, meta, cfg),
        (min(cfg.super_cap, meta.num_nodes), cfg.m2p_cap, cfg.p2p_cap),
        shards=shards)


def counted_chunk_live(x, y, z, m, keys, box, tree, meta, cfg, shards=1):
    """``chunk_live`` under ``cfg``."""
    return chunk_live(
        *_solve_geometry(x, y, z, m, keys, box, tree, meta, cfg),
        min(cfg.super_cap, meta.num_nodes), shards=shards,
        let_cap=min(cfg.let_cap, meta.num_nodes))
