"""The ``bitmask`` case of tests/mesh_gravity_case.py (which see), and the
sizing sweep that no case drives (here because this is the lightest of the
three files)."""

import numpy as np
import pytest

CASE = "bitmask"

from mesh_gravity_case import *  # noqa: E402,F401,F403  (the case's tests)


@pytest.mark.parametrize("rows,run_cap", [(64, 1024), (8 * 64, 1024),
                                          (64, 128)])
def test_sized_from_every_block_each_slab_forms(rows, run_cap):
    """``estimate_gravity_caps`` on a mesh takes its list high-water marks
    from ``_slab_list_highwater``: every slab-local block (or superblock),
    swept on the device. Against plain numpy over the same blocks (the
    cell's first chip run died of a 256-of-16k sample that missed the one
    block with twice the list of any sampled, PR 29).

    The fourth count sizes the near field's run axis (``p2p_run_cap``):
    an upper bound of the runs a block's opened leaves merge into at gap 0
    under ``run_cap`` rows. Held here to its definition (stretches of
    row-adjacent opened leaves + rows // the least closed piece, fullest
    block) and, block by block, over the runs ``_merge_runs`` itself makes
    of the same leaves (``run_cap`` 128, two leaves a run, is there for
    the clipping)."""
    import jax.numpy as jnp

    from sphexa_tpu.gravity.traversal import (
        _monotone_mac_geometry,
        _slab_list_highwater,
        compute_multipoles,
    )
    from sphexa_tpu.init import init_evrard
    from sphexa_tpu.sfc.keys import compute_sfc_keys
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_evrard(16)
    n4 = (state.n // 4) * 4
    sim = Simulation(state, box, const, prop="ve", theta=0.5, backend="xla")
    keys = compute_sfc_keys(state.x, state.y, state.z, sim.box,
                            curve=sim.curve)
    order = jnp.argsort(keys)[:n4]
    xs, ys, zs, ms = (a[order] for a in (state.x, state.y, state.z, state.m))
    tree, meta = sim._gtree, sim._cfg.grav_meta
    nm, com, _, edges = compute_multipoles(xs, ys, zs, ms, keys[order], tree,
                                           meta)
    got = np.asarray(_slab_list_highwater(
        xs, ys, zs, nm, com, sim.box, tree, meta, 0.5, rows, 4,
        edges=edges, run_cap=run_cap))
    # without the leaves' rows: the three counts alone, the same
    assert (np.asarray(_slab_list_highwater(
        xs, ys, zs, nm, com, sim.box, tree, meta, 0.5, rows, 4))
        == got[:3]).all()
    edges = np.asarray(edges)
    lrows = np.diff(edges)
    leaf_of_node = np.asarray(tree.leaf_of_node)
    piece = max(run_cap - int(lrows.max()) + 1, 1)

    valid = np.asarray(nm) > 0
    cc, ch, mac2 = (np.asarray(a) for a in _monotone_mac_geometry(
        sim.box, tree, meta, com, nm > 0, 0.5))
    parent, is_leaf = np.asarray(tree.parent), np.asarray(tree.is_leaf)
    root = parent == np.arange(meta.num_nodes)
    pos = np.stack([np.asarray(a) for a in (xs, ys, zs)], axis=1)
    S = n4 // 4
    per_block, lists = [], []
    for k in range(4):
        slab = pos[k * S:(k + 1) * S]
        for b0 in range(0, S, rows):
            blk = slab[b0:b0 + rows]
            bc = 0.5 * (blk.max(0) + blk.min(0))
            bs = 0.5 * (blk.max(0) - blk.min(0))
            d = np.maximum(np.abs(bc - cc) - bs - ch, 0.0)
            acc = valid & ((d * d).sum(1) >= mac2)
            anc = np.where(root, False, acc[parent])
            opened = np.sort(leaf_of_node[is_leaf & valid & ~acc])
            adjacent = edges[opened[1:]] == edges[opened[:-1] + 1]
            bound = ((1 + (~adjacent).sum() if len(opened) else 0)
                     + lrows[opened].sum() // piece)
            per_block.append([(acc & ~anc).sum(), len(opened), (~anc).sum(),
                              bound])
            lists.append(opened)
    per_block = np.asarray(per_block)
    assert (got == per_block.max(axis=0)).all(), (got, per_block.max(0))

    # the runs the near field makes of the same leaves, block by block
    from sphexa_tpu.gravity.traversal import _merge_p2p_runs

    cap = max(len(l) for l in lists)
    leaf = np.zeros((len(lists), cap), np.int32)
    live = np.zeros((len(lists), cap), bool)
    for b, l in enumerate(lists):
        leaf[b, :len(l)], live[b, :len(l)] = l, True
    ranges, (c0, c1) = _merge_p2p_runs(
        jnp.asarray(np.where(live, edges[leaf], 0)),
        jnp.asarray(np.where(live, lrows[leaf], 0)), run_cap,
        leaf=jnp.asarray(leaf))
    nruns = np.asarray(ranges.ncells)
    assert (nruns <= per_block[:, 3]).all() and nruns.max() > 1
    if run_cap < 1024:  # the cap cuts stretches: more runs than stretches
        assert (np.asarray(ranges.lens) <= run_cap).all()
        assert nruns.max() > (per_block[:, 3]
                              - [lrows[l].sum() // piece for l in lists]).max()
    # the runs cover the leaves' rows and no other, and carry the first
    # and last leaf of each run
    for b in (0, len(lists) // 2, len(lists) - 1):
        k = int(nruns[b])
        st, ln = (np.asarray(a)[b, :k] for a in (ranges.starts, ranges.lens))
        rows_of = lambda s_, l_: np.concatenate(
            [np.arange(a, a + n) for a, n in zip(s_, l_)] + [[]])
        l = lists[b]
        assert (rows_of(st, ln) == rows_of(edges[l], lrows[l])).all()
        assert (edges[np.asarray(c0)[b, :k]] == st).all()
        assert (edges[np.asarray(c1)[b, :k] + 1] == st + ln).all()
