"""Traversal-free Barnes-Hut gravity: batched MAC + fixed-cap interaction lists.

TPU-native re-design of ryoanji's warp-centric dual traversal
(ryoanji/src/ryoanji/nbody/traversal.cuh:60-79 TravConfig,
traversal_cpu.hpp:84 computeGravityGroup). Instead of a stack/ring-buffer
walk, every target group evaluates the vector MAC against *all* tree nodes
at once (the node array is small, ~N/bucket), then classifies each node by
the classic first-accepted-ancestor rule:

- M2P set: node passes the MAC and no ancestor passed it;
- P2P set: node is a leaf, and neither it nor any ancestor passed.

The ancestor predicate is a level-by-level downsweep (gather from parent),
and the sparse sets are compacted into fixed-cap index lists via a stable
argsort — overflow is reported as a diagnostic, standing in for the
reference's traversal stack-overflow detection (gravity_wrapper.hpp:120).

Target groups are fixed blocks of SFC-consecutive particles (the analog of
TravConfig's 64-particle targets), so all shapes are static. Work is
chunked with lax.map (sequential) over groups of blocks, with vmap inside,
to bound transient memory.

Softening/energy conventions follow the reference exactly: P2P clamps the
distance to h_i+h_j (kernel.hpp:515), egrav = 0.5*G*sum(m_i*phi_i)
(traversal_cpu.hpp:231).
"""

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sphexa_tpu.gravity import multipole as mp
from sphexa_tpu.gravity.tree import GravityTree, GravityTreeMeta
from sphexa_tpu.sfc.box import Box
from sphexa_tpu.util.phases import named_phase, phase_scope


@dataclasses.dataclass(frozen=True)
class GravityConfig:
    """Static gravity-solver configuration (hashable, jit-safe)."""

    theta: float = 0.5  # opening angle; accept if dist > 2*size/theta + com offset
    bucket_size: int = 64  # leaf capacity target for the gravity tree build
    target_block: int = 64  # particles per MAC target group (TravConfig analog)
    blocks_per_chunk: int = 32  # target groups processed per lax.map step
    m2p_cap: int = 512  # max accepted multipoles per target group
    p2p_cap: int = 48  # max near-field leaves per target group
    leaf_cap: int = 128  # max particles gathered per near-field leaf
    G: float = 1.0
    # multipole expansion order: 0 = cartesian quadrupole (the default
    # fast path, multipole.py); P >= 2 selects spherical multipoles with
    # P retained orders (gravity/spherical.py — the reference's EXAFMM
    # accuracy knob, kernel.hpp). Open-boundary solves only.
    multipole_order: int = 0
    # hierarchical MAC: blocks per SUPERBLOCK for the two-level
    # classification (0 = dense blocks x nodes sweep). The superblock
    # pre-pass keeps only its ancestor-closed open set + accepted cut
    # (<= super_cap nodes), and each block classifies against THAT list
    # instead of the whole tree — MAC work proportional to the accepted
    # region (VERDICT r2 #4a). MEASURED (Evrard 50^3, 3425 nodes, v5e):
    # the open set is ~60% of this small tree, so the pre-pass overhead
    # LOSES (457 vs 281 ms solve) — default 0; enable at large trees
    # (>= ~1e5 nodes) where C << num_n makes the refinement pay.
    super_factor: int = 0
    super_cap: int = 1024
    # LET analog (focused-octree role, octree_focus_mpi.hpp:50-698): on
    # SHARDED solves, classify each shard's blocks against the shard's
    # ESSENTIAL node set — the ancestor-closed open set + accepted cut
    # of the slab bbox — instead of the full replicated tree. Remote
    # regions appear only as their MAC-coarsened cut, so per-shard MAC
    # work and list sorts scale with the slab's essential tree
    # (O((N/P)^(2/3) + cut)), not num_nodes. 0 = off; sized by
    # estimate_gravity_caps(let_shards=P). The slab bbox is recomputed
    # every solve from the live positions, so the set is never stale.
    let_cap: int = 0
    # near-field engine: stream the P2P leaf ranges through the pallas
    # pair engine (sph/pallas_pairs.py) instead of XLA gathers — the
    # dominant cost of the XLA formulation at 1e5+ particles. Set by the
    # Simulation from the step backend (TPU only; CPU tests keep XLA).
    use_pallas: bool = False
    # interaction-list compaction mode. "sort": the per-block packed
    # 3-class sort (the 214 ms classification floor at 1M — every sort
    # VARIANT measured identical, docs/NEXT.md round 5). "bitmask": the
    # Mosaic bitmask+popcount-rank kernel (gravity/pallas_compact.py)
    # materializes both fixed-cap lists with no argsort anywhere on the
    # per-block path, and the first-accepted-ancestor test re-evaluates
    # the MAC on the PARENT's own arrays instead of gathering the block's
    # accept vector — exact-equivalent lists (pinned by
    # tests/test_gravity.py), and the shape the hierarchical superblock
    # path needs to pay. The dense sort stays selectable everywhere.
    compaction: str = "sort"
    # m2p cap sizing margin: M2P eval cost is linear in m2p_cap, and the
    # generic 1.5-1.6 sizing margin left ~35 ms of eval slack at 1M
    # (docs/NEXT.md round 5). Applied by estimate_gravity_caps to the m2p
    # cap only; overflow is guarded by the m2p_max diagnostic exactly
    # like let_max (Simulation regrows the margin and re-sizes on
    # overflow, so a too-tight cap costs a retry, never dropped nodes).
    m2p_cap_margin: float = 1.3


def gravity_tuning(n: int, use_pallas: bool, telemetry=None) -> dict:
    """Scale-dependent gravity-solver shape
    (Simulation._configure_gravity).

    Coarser classification blocks amortize the MAC sweep at large N
    (measured 1.86x at 1M Plummer: tb=256 975 ms vs tb=64 1810 ms,
    scripts/bench_gravity_scale.py); the hierarchical bitmask compaction
    pays only where num_nodes >> super_cap (>= ~1e5-node trees) AND the
    Mosaic kernel compiles (TPU backend — interpret mode is for tests).
    super_factor=8 is the sampled-width optimum at both 1M and 4M
    Plummer (sf sweep in docs/NEXT.md round 6: the candidate cut GROWS
    with the superblock bbox, so small supers win; the pre-pass is <20%
    of the block-stage slots at sf=8).
    """
    big = n >= 500_000
    if telemetry is not None and 450_000 <= n <= 550_000:
        # the silent cliff: these knobs are a step function of N, and a
        # run sitting within 10% of the threshold can flip the whole
        # solver shape (and recompile) on a small particle-count change.
        # Near the edge, say so — a first-class ``tuning`` event (and a
        # ConsoleSink-notable line) instead of a mysterious retrace
        telemetry.event(
            "tuning", source="heuristic", note="near-threshold",
            n=int(n), threshold=500_000, big=bool(big),
        )
    return {
        "target_block": 256 if big else 64,
        "blocks_per_chunk": 8 if big else 32,
        "super_factor": 8 if (big and use_pallas) else 0,
        "compaction": "bitmask" if (big and use_pallas) else "sort",
        "use_pallas": use_pallas,
    }


@functools.partial(jax.jit, static_argnames=("blk",))
def _block_bboxes(x, y, z, blk: int):
    """Per-target-block bounding boxes, (nb, 3) min / (nb, 3) max — the
    only per-particle quantity the cap estimator needs (tail block padded
    with the last row, which only shrinks nothing)."""
    n = x.shape[0]
    nb = -(-n // blk)
    pad = nb * blk - n

    def blocked(a):
        if pad:
            a = jnp.concatenate([a, jnp.broadcast_to(a[-1:], (pad,))])
        return a.reshape(nb, blk)

    xs, ys, zs = blocked(x), blocked(y), blocked(z)
    bmin = jnp.stack([xs.min(1), ys.min(1), zs.min(1)], axis=1)
    bmax = jnp.stack([xs.max(1), ys.max(1), zs.max(1)], axis=1)
    return bmin, bmax


def estimate_gravity_caps(
    x, y, z, m, sorted_keys, box: Box,
    tree: GravityTree, meta: GravityTreeMeta, cfg: GravityConfig,
    sample_blocks: int = 256, margin: float = 1.5, quantum: int = 32,
    let_shards: int = 0,
) -> GravityConfig:
    """Size the interaction-list caps from the current distribution.

    Host-side helper run at (re)configuration time, the gravity analog of
    estimate_cell_cap: simulate the MAC classification for a sample of
    target blocks in numpy and pad the observed maxima. The caps are upper
    bounds by sampling only — the overflow diagnostics returned by
    compute_gravity remain the correctness guard.
    """
    node_mass, node_com, node_q, edges = compute_multipoles(
        x, y, z, m, sorted_keys, tree, meta
    )
    # everything fetched is O(tree) or O(N/target_block) — never the
    # particle arrays themselves (the O(N/P) reconfiguration contract,
    # VERDICT r3 #3); per-block bboxes come from one jitted reduction
    from sphexa_tpu.parallel.sizing import fetch

    n = x.shape[0]
    blk = cfg.target_block
    nb = -(-n // blk)
    # ONE batched device->host transfer: on remote-attached TPUs each
    # fetch pays a full dispatch+sync round trip (the same reason
    # Simulation._fetch_scalars batches)
    (nm, com, edges, parent, is_leaf, lengths, lo, center_frac,
     halfsize_frac, (bmin, bmax)) = (
        np.asarray(a) if not isinstance(a, tuple) else a
        for a in fetch((
            node_mass, node_com, edges, tree.parent, tree.is_leaf,
            box.lengths, jnp.stack([box.lo[0], box.lo[1], box.lo[2]]),
            tree.center_frac, tree.halfsize_frac,
            _block_bboxes(x, y, z, blk),
        ))
    )
    bmin, bmax = np.asarray(bmin), np.asarray(bmax)
    valid = nm > 0.0
    counts = np.diff(edges)

    lo = np.asarray(lo, dtype=np.float64)
    geo_center = lo[None, :] + np.asarray(center_frac) * lengths[None, :]
    geo_size = np.asarray(halfsize_frac)[:, None] * lengths[None, :]
    l_node = 2.0 * geo_size.max(axis=1)
    s_off = np.linalg.norm(com - geo_center, axis=1)
    # monotone MAC radius + subtree com box — MUST match
    # compute_gravity's upsweeps or the sampled caps drift from the
    # real classification
    smax = np.where(valid, s_off, 0.0)
    BIG = 1e15  # squares stay finite in f32
    com_lo = np.where(valid[:, None], com, BIG)
    com_hi = np.where(valid[:, None], com, -BIG)
    for s, e in reversed(meta.level_ranges[1:]):
        np.maximum.at(smax, parent[s:e], smax[s:e])
        np.minimum.at(com_lo, parent[s:e], com_lo[s:e])
        np.maximum.at(com_hi, parent[s:e], com_hi[s:e])
    ccenter = np.where(valid[:, None], 0.5 * (com_lo + com_hi), BIG)
    chalf = np.where(valid[:, None],
                     np.maximum(0.5 * (com_hi - com_lo), 0.0), 0.0)
    mac2 = (l_node / cfg.theta + smax) ** 2
    self_parent = parent == np.arange(meta.num_nodes)

    rng = np.random.default_rng(0)
    blocks = (
        np.arange(nb)
        if nb <= sample_blocks
        else np.unique(np.concatenate([[0, nb - 1], rng.integers(0, nb, sample_blocks)]))
    )

    def classify(b0, b1):
        pmin = bmin[b0:b1].min(axis=0)
        pmax = bmax[b0:b1].max(axis=0)
        bc, bs = (pmax + pmin) / 2, (pmax - pmin) / 2
        d = np.maximum(
            np.abs(bc[None, :] - ccenter) - bs[None, :] - chalf, 0.0
        )
        accept = valid & ~((d * d).sum(axis=1) < mac2)
        # monotone MAC: accepted strict ancestor == accepted parent
        anc = np.where(self_parent, False, accept[parent])
        return accept, anc

    m2p_max, p2p_max = 1, 1
    for b in blocks:
        accept, anc = classify(b, b + 1)
        m2p_max = max(m2p_max, int((accept & ~anc).sum()))
        p2p_max = max(p2p_max, int((is_leaf & valid & ~accept).sum()))

    # superblock candidate-list high water (the hierarchical MAC's cap):
    # ~anc = open set + accepted cut of the super bbox
    c_cap_max = 1
    if cfg.super_factor > 0:
        sblk = cfg.super_factor * blk
        nsb = -(-n // sblk)
        supers = (
            np.arange(nsb)
            if nsb <= sample_blocks
            else np.unique(np.concatenate(
                [[0, nsb - 1], rng.integers(0, nsb, sample_blocks)]
            ))
        )
        for b in supers:
            _, anc = classify(b * cfg.super_factor,
                              min((b + 1) * cfg.super_factor, nb))
            c_cap_max = max(c_cap_max, int((~anc).sum()))

    # per-SHARD essential-set high water (the LET cap): ~anc of the
    # slab bbox — each shard's blocks span a contiguous block range
    let_max = 0
    if let_shards > 1:
        for k in range(let_shards):
            b0 = k * nb // let_shards
            b1 = max(b0 + 1, (k + 1) * nb // let_shards)
            _, anc = classify(b0, min(b1, nb))
            let_max = max(let_max, int((~anc).sum()))

    def pad(v, mg=margin):
        return int(np.ceil(v * mg / quantum) * quantum)

    leaf_cap = pad(int(counts.max()) if len(counts) else 1)
    # the m2p cap gets its own (tighter) margin — M2P eval cost is linear
    # in the cap, and the sampled maximum is exact whenever all blocks are
    # sampled. Scaled by margin/1.5 so the driver's overflow-retry margin
    # growth still reaches any true high water.
    m2p_margin = cfg.m2p_cap_margin * margin / 1.5
    return dataclasses.replace(
        cfg,
        m2p_cap=min(pad(m2p_max, m2p_margin), meta.num_nodes),
        p2p_cap=min(pad(p2p_max), meta.num_leaves),
        leaf_cap=leaf_cap,
        # only re-size when the hierarchical path is on: clobbering the
        # configured value for sf=0 would sabotage a later enable
        super_cap=(
            min(pad(c_cap_max), meta.num_nodes)
            if cfg.super_factor > 0 else cfg.super_cap
        ),
        let_cap=(
            min(pad(let_max), meta.num_nodes)
            if let_shards > 1 else cfg.let_cap
        ),
    )


@functools.partial(jax.jit, static_argnames=("meta", "order"))
@named_phase("gravity-upsweep")
def compute_multipoles(
    x, y, z, m, sorted_keys, tree: GravityTree, meta: GravityTreeMeta,
    order: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Masses, centers of mass and multipoles for every tree node.

    Device-side counterpart of computeLeafMultipoles + upsweepMultipoles
    (ryoanji/nbody/upsweep_cpu.hpp:26-92): leaf payload via segment sums
    over the particle->leaf assignment, then a level-by-level scatter-add
    upsweep with the M2M expansion-center shift.

    Returns (node_mass (N,), node_com (N,3), node_q, edges (L+1,)) with
    node_q (N, 7) real (cartesian quadrupole, order=0) or (N, ncoef(P))
    complex (spherical order-P coefficients).
    """
    lk = tree.leaf_keys
    num_l, num_n = meta.num_leaves, meta.num_nodes
    n = x.shape[0]
    edges = jnp.searchsorted(sorted_keys, lk, side="left").astype(jnp.int32)
    # particle -> leaf index WITHOUT the N-query u64 searchsorted (emulated
    # u64 compares x log2(L) gathers measured ~150 ms at 1M): leaf rows are
    # contiguous, so pleaf = (#leaf starts <= row) - 1 — one O(L) scatter
    # + O(N) cumsum over int32 rows
    pleaf = _pleaf_from_edges(edges, n)

    # pass 1: monopole + center of mass, leaves then upsweep. Processing
    # levels deepest-first means a node's own subtree sum is complete by the
    # time it is added to its parent. Leaf rows are contiguous in the
    # sorted arrays, so the leaf sums are cumsum differences at the leaf
    # edges (mp.edge_segment_sum) — not TPU-serializing scatter-adds.
    w = jnp.stack([m, m * x, m * y, m * z], axis=1)  # (n, 4)
    leaf_w = mp.edge_segment_sum(w, edges)  # (L, 4)
    node_mass, node_com = _upsweep_mass_com(leaf_w, tree, meta)

    if order > 0:
        from sphexa_tpu.gravity import spherical as sp

        leaf_com = node_com[tree.node_of_leaf]
        leaf_c = sp.p2m(x, y, z, m, leaf_com, edges, order, pleaf=pleaf)
        node_q = sp.upsweep(leaf_c, node_com, tree, meta,
                            tree.node_of_leaf, order)
        return node_mass, node_com, node_q, edges

    # pass 2: leaf quadrupoles around the leaf com, then M2M upsweep with
    # the expansion-center shift to the parent com
    leaf_com = node_com[tree.node_of_leaf]
    leaf_q = mp.p2m_leaf(x, y, z, m, pleaf, leaf_com, num_l,
                         edges=edges)  # (L, 7)
    node_q = _upsweep_quadrupoles(leaf_q, node_mass, node_com, tree, meta)
    return node_mass, node_com, node_q, edges


def _pleaf_from_edges(edges, n: int):
    """(n,) particle->leaf map from the (L+1 or L,) sorted leaf start
    rows: cumsum of a start-row indicator. Empty leaves (duplicate
    edges) advance the count twice and simply never appear."""
    mark = jnp.zeros(n + 1, jnp.int32).at[edges].add(1)
    return jnp.cumsum(mark)[:n] - 1


def _upsweep_mass_com(leaf_w, tree, meta):
    """Shared monopole/center-of-mass upsweep from (L, 4) leaf payloads
    (single-device and distributed callers MUST use the same loops so
    their multipoles cannot diverge)."""
    num_n = meta.num_nodes
    node_w = jnp.zeros((num_n, 4), leaf_w.dtype).at[tree.node_of_leaf].set(leaf_w)
    for s, e in reversed(meta.level_ranges[1:]):
        # parent rows are non-decreasing inside a level range (children
        # of one parent are contiguous in the level-ordered layout), so
        # the duplicate-index accumulation has a fixed segment order —
        # the JXA401 bitwise-replay contract depends on this hint
        node_w = node_w.at[tree.parent[s:e]].add(node_w[s:e],
                                                 indices_are_sorted=True)
    node_mass = node_w[:, 0]
    node_com = node_w[:, 1:4] / jnp.maximum(node_mass, 1e-30)[:, None]
    return node_mass, node_com


def _upsweep_quadrupoles(leaf_q, node_mass, node_com, tree, meta):
    """Shared M2M quadrupole upsweep from (L, 7) leaf payloads."""
    num_n = meta.num_nodes
    node_q = jnp.zeros((num_n, 7), leaf_q.dtype).at[tree.node_of_leaf].set(leaf_q)
    for s, e in reversed(meta.level_ranges[1:]):
        par = tree.parent[s:e]
        d = node_com[par] - node_com[s:e]
        # sorted parent rows, as in _upsweep_mass_com (JXA401)
        node_q = node_q.at[par].add(mp.m2m_shift(node_q[s:e], node_mass[s:e], d),
                                    indices_are_sorted=True)
    return node_q


@named_phase("gravity-upsweep")
def compute_multipoles_sharded(
    x, y, z, m, local_keys, tree: GravityTree, meta: GravityTreeMeta,
    axis: str, order: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Distributed multipole upsweep under shard_map — the
    global_multipole.hpp:44-73 allreduce analog.

    Each shard contributes the PARTIAL leaf sums of its slab rows (leaf
    row ranges clipped to the slab; leaves are key ranges, so membership
    needs only the local keys), one psum replicates the (L, k) leaf
    payloads, and the level-by-level M2M upsweep runs replicated on the
    (small) tree. Comm is O(tree), never O(N) — no particle gather.
    Returns the compute_multipoles contract (cartesian quadrupole at
    order=0, spherical order-P complex coefficients otherwise — the
    psum runs on the complex leaf payloads) with GLOBAL row edges.
    """
    lk = tree.leaf_keys
    num_l, num_n = meta.num_leaves, meta.num_nodes
    S = x.shape[0]
    k = jax.lax.axis_index(axis)
    pos_local = jnp.searchsorted(local_keys, lk, side="left").astype(jnp.int32)
    # jaxlint: disable=JXL006 -- data-chained upsweep: every later psum
    # consumes the previous psum's result (edges -> leaf_w -> leaf_q/c),
    # so program order is already total (JXA201 proves it on the jaxpr)
    edges = jax.lax.psum(pos_local, axis)  # global leaf boundary rows
    e_clip = jnp.clip(edges - k * S, 0, S)
    # local-row particle->leaf map: leaves starting before the slab clip
    # to 0 (counted for every local row), after it to S (never counted) —
    # same contiguous-rows identity as the single-device path
    pleaf = _pleaf_from_edges(e_clip, S)

    w = jnp.stack([m, m * x, m * y, m * z], axis=1)
    # jaxlint: disable=JXL006 -- data-chained on edges (via e_clip)
    leaf_w = jax.lax.psum(mp.edge_segment_sum(w, e_clip), axis)  # (L, 4)
    node_mass, node_com = _upsweep_mass_com(leaf_w, tree, meta)

    leaf_com = node_com[tree.node_of_leaf]
    if order > 0:
        from sphexa_tpu.gravity import spherical as sp

        # jaxlint: disable=JXL006 -- data-chained on leaf_w (via leaf_com)
        leaf_c = jax.lax.psum(
            sp.p2m(x, y, z, m, leaf_com, e_clip, order, pleaf=pleaf), axis
        )
        node_q = sp.upsweep(leaf_c, node_com, tree, meta,
                            tree.node_of_leaf, order)
        return node_mass, node_com, node_q, edges
    # jaxlint: disable=JXL006 -- data-chained on leaf_w (via leaf_com)
    leaf_q = jax.lax.psum(
        mp.p2m_leaf(x, y, z, m, pleaf, leaf_com, num_l, edges=e_clip), axis
    )
    node_q = _upsweep_quadrupoles(leaf_q, node_mass, node_com, tree, meta)
    return node_mass, node_com, node_q, edges


@named_phase("gravity-p2p")
def _pallas_p2p(x, y, z, m, h, shift, allow_self, cfg: GravityConfig,
                starts, lens, jdata=None, i_offset=0):
    """Near-field P2P through the streamed pair engine.

    ``starts``/``lens`` are the per-block near-leaf ranges from the MAC
    classification, (NB, p2p_cap) in GLOBAL sorted-array offsets. Leaf
    ranges are contiguous, so adjacent ones merge into long DMA runs —
    with gap=0 ONLY: a bridged gap would stream particles of leaves whose
    mass already arrives via M2P (no distance cutoff masks them away),
    double-counting. Returns (ax, ay, az, phi), each (NB*block,).

    Under shard_map, ``jdata = (x, y, z, m, h)`` supplies the j-side
    candidate arrays (slab + halo annex) the (pre-localized) ranges
    index into, and ``i_offset`` places the local targets in that index
    space — the same contract as the SPH engine ops.
    """
    from sphexa_tpu.neighbors.cell_list import NeighborConfig
    from sphexa_tpu.sph import pallas_pairs as pp

    nb = starts.shape[0]
    blk = cfg.target_block
    nbr = NeighborConfig(
        level=1, cap=cfg.leaf_cap, group=blk,
        run_cap=max(cfg.leaf_cap, 1024), gap=0,
    )
    zero3 = jnp.zeros(starts.shape + (3,), jnp.float32)
    rs, rl, sh3, nruns, _ = pp._merge_runs(
        starts, lens, lens > 0, zero3, nbr.run_cap, 0
    )
    ranges = pp.GroupRanges(
        starts=rs, lens=rl, shift_x=sh3[0], shift_y=sh3[1], shift_z=sh3[2],
        ncells=nruns, occupancy=jnp.int32(0),
        boxl=jnp.full((3,), 1e30, jnp.float32),
    )

    def pair_body(geom, i_fields, j_fields, accs):
        ax, ay, az, phi = accs
        hi = i_fields[3]
        mj, hj = j_fields[3], j_fields[4]
        # SPH-compatible softening: distance clamped to h_i + h_j
        # (ryoanji/nbody/kernel.hpp:515; force vanishes linearly at r->0)
        h_ij = hi + hj
        r2_eff = jnp.maximum(geom.d2, h_ij * h_ij)
        inv_r = jax.lax.rsqrt(jnp.maximum(r2_eff, 1e-30))
        w = jnp.where(geom.mask, mj * inv_r * inv_r * inv_r, 0.0)
        # geom.rx = x_i - x_j = -(source - target)
        return (ax - geom.rx * w, ay - geom.ry * w, az - geom.rz * w,
                phi - w * geom.d2)

    def finalize(i_fields, accs, nc):
        red = lambda a: jnp.sum(a, axis=1, keepdims=True)
        return tuple(red(a) for a in accs)

    engine = pp.group_pair_engine(
        pair_body, finalize, num_i=4, num_j=5, num_acc=4, cfg=nbr,
        fold=False, interpret=pp.pallas_interpret(),
        pair_cutoff=False, want_nc=False,
    )
    # i-side blocks padded to the classification's chunked block count
    # (tail groups re-evaluate the last particle; trimmed by the caller)
    npad = nb * blk
    n = x.shape[0]

    def blocked(a, off):
        a = a + off
        a = jnp.concatenate(
            [a, jnp.broadcast_to(a[-1:], (npad - n,))]
        ) if npad > n else a
        return a.reshape(nb, blk)

    i_fields = [blocked(x, shift[0]), blocked(y, shift[1]),
                blocked(z, shift[2]), blocked(h, 0.0)]
    jp = pp.pack_j_fields(jdata or (x, y, z, m, h), nbr.dma_cap)
    ax, ay, az, phi, _nc = engine(ranges, i_fields, jp, i_offset, allow_self)
    f = lambda a: a.reshape(-1)
    return f(ax), f(ay), f(az), f(phi)


@named_phase("gravity-mac")
def _monotone_mac_geometry(box, tree, meta, node_com, valid, theta):
    """MONOTONE vector-MAC acceptance geometry (macs.hpp computeVecMacR2
    role, made hierarchy-monotone): radius l/theta +
    max-over-subtree(|com - geo|), distance measured from the target bbox
    to the node's GEO BOX. Since child boxes nest and the radius is
    non-increasing down the tree, accept(parent) => accept(child) — so
    "first accepted ancestor" collapses to ONE parent lookup (no
    per-level downsweep, the 210 ms phase at 1M) and p2p = leaf & ~accept needs no
    ancestor chain at all. Validity: the true com distance >= box
    distance (com inside the box) and the monotone radius >= the node's
    own l/theta + s_off, so every acceptance satisfies the original
    vector-MAC error criterion — strictly conservative (measured ~+15%
    m2p work, traded for the whole downsweep).

    Returns (ccenter, chalf, mac2): the subtree-com bounding boxes and
    squared acceptance radii every block classifies against."""
    lengths = box.lengths  # (3,)
    lo = jnp.stack([box.lo[0], box.lo[1], box.lo[2]])
    geo_center = lo[None, :] + tree.center_frac * lengths[None, :]  # (N, 3)
    geo_size = tree.halfsize_frac[:, None] * lengths[None, :]  # (N, 3)
    l_node = 2.0 * jnp.max(geo_size, axis=1)
    s_off = jnp.sqrt(jnp.sum((node_com - geo_center) ** 2, axis=1))
    # empty nodes have no com (mass 0 -> com (0,0,0)); their bogus
    # s_off must not inflate any ancestor's monotone radius
    smax = jnp.where(valid, s_off, 0.0)
    # subtree com BOUNDING BOX: nests under the hierarchy like the geo
    # box (subtree com sets are subsets) but collapses toward a point at
    # depth, so the box-to-box distance below stays nearly as tight as
    # the reference's com-point distance where it matters (the deep
    # acceptance cut) — using the geo box instead measured ~2x more
    # accepted nodes at 1M/theta=0.5
    BIG = jnp.float32(1e15)  # "infinitely far"; squares stay finite in f32
    com_lo = jnp.where(valid[:, None], node_com, BIG)
    com_hi = jnp.where(valid[:, None], node_com, -BIG)
    for s, e in reversed(meta.level_ranges[1:]):
        par = tree.parent[s:e]
        smax = smax.at[par].max(smax[s:e])
        com_lo = com_lo.at[par].min(com_lo[s:e])
        com_hi = com_hi.at[par].max(com_hi[s:e])
    ccenter = jnp.where(valid[:, None], 0.5 * (com_lo + com_hi), BIG)
    chalf = jnp.where(valid[:, None],
                      jnp.maximum(0.5 * (com_hi - com_lo), 0.0), 0.0)
    mac2 = (l_node / theta + smax) ** 2  # (N,)
    return ccenter, chalf, mac2


@functools.partial(jax.jit,
                   static_argnames=("meta", "cfg", "with_phi", "shard"))
def compute_gravity(
    x, y, z, m, h, sorted_keys, box: Box,
    tree: GravityTree, meta: GravityTreeMeta, cfg: GravityConfig,
    shift=None, allow_self=None, with_phi: bool = False, mp_cache=None,
    shard=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Gravitational acceleration + potential for all (SFC-sorted) particles.

    Returns (ax, ay, az, egrav, diagnostics) — or (..., phi, diagnostics)
    when ``with_phi`` — where diagnostics report the high-water
    interaction-list occupancies; if any exceeds its cap the caller must
    enlarge the config and re-run (Simulation handles this the same way as
    neighbor-cell overflow).

    ``shift``: optional (3,) offset added to the *target* positions — the
    replica-shell evaluation of periodic gravity (targets against the
    tree of the base box, traversal_cpu.hpp computeGravity numReplicaShells).
    ``allow_self`` (traced bool scalar) must be True for nonzero shifts: a
    particle does interact with its own periodic image. Both are traced so
    the Ewald replica loop compiles this function once.
    ``mp_cache``: optional precomputed compute_multipoles result.
    ``shard``: (axis, P, win) when running INSIDE shard_map on a local
    slab — x/y/z/... are then the slab, mp_cache must come from
    compute_multipoles_sharded (global edges), and the near field
    fetches remote leaf rows through the halo exchange
    (parallel/exchange.py) instead of indexing a global array. ``win``
    an int is the windowed exchange's Wmax (full-slab fallback at
    win == S); a (P-1,)-tuple of ints is the MAC-sized sparse exchange's
    per-distance row caps (sizing.device_gravity_halo), which also adds
    ``halo_rows``/``halo_occ`` to the diagnostics. egrav and
    diagnostics are returned per-shard (the caller psums/pmaxes).
    """
    if shard is not None and not cfg.use_pallas:
        raise ValueError("sharded gravity needs the engine near field "
                         "(cfg.use_pallas=True; interpret mode off-TPU)")
    if shard is not None and mp_cache is None:
        raise ValueError("sharded gravity needs mp_cache from "
                         "compute_multipoles_sharded")
    n = x.shape[0]
    num_n = meta.num_nodes
    order = cfg.multipole_order
    node_mass, node_com, node_q, edges = (
        mp_cache
        if mp_cache is not None
        else compute_multipoles(x, y, z, m, sorted_keys, tree, meta,
                                order=order)
    )
    valid = node_mass > 0.0
    if shift is None:
        shift = jnp.zeros(3, x.dtype)
    if allow_self is None:
        allow_self = jnp.asarray(False)

    ccenter, chalf, mac2 = _monotone_mac_geometry(
        box, tree, meta, node_com, valid, cfg.theta
    )
    self_parent = tree.parent == jnp.arange(num_n, dtype=tree.parent.dtype)

    blk = cfg.target_block
    num_blocks = -(-n // blk)
    chunk = cfg.blocks_per_chunk
    num_chunks = -(-num_blocks // chunk)
    idx = jnp.arange(num_chunks * chunk * blk, dtype=jnp.int32)
    idx = jnp.minimum(idx, n - 1).reshape(num_chunks, chunk, blk)

    leaf_occ = jnp.max(edges[1:] - edges[:-1])

    # packed node payload for ONE row-gather per block: com 3 + mass 1 +
    # either the 7 quadrupole floats (padded to 12) or the spherical
    # coefficients split re|im — per-field gathers tripled the M2P
    # memory traffic
    if order > 0:
        node_packed = jnp.concatenate(
            [node_com, node_mass[:, None],
             jnp.real(node_q), jnp.imag(node_q)],
            axis=1,
        )
    else:
        node_packed = jnp.concatenate(
            [node_com, node_q, node_mass[:, None],
             jnp.zeros((num_n, 1), node_com.dtype)],
            axis=1,
        )

    def _bbox(tx, ty, tz):
        bc = jnp.stack(
            [(jnp.max(tx) + jnp.min(tx)) * 0.5,
             (jnp.max(ty) + jnp.min(ty)) * 0.5,
             (jnp.max(tz) + jnp.min(tz)) * 0.5]
        )
        bs = jnp.stack(
            [(jnp.max(tx) - jnp.min(tx)) * 0.5,
             (jnp.max(ty) - jnp.min(ty)) * 0.5,
             (jnp.max(tz) - jnp.min(tz)) * 0.5]
        )
        return bc, bs

    def _accept(bc, bs, gc, gs, m2):
        # box-to-box distance vs the monotone MAC radius (see above);
        # nested node boxes make this monotone where the reference's
        # com-distance evaluateMac (macs.hpp) is not
        d = jnp.maximum(
            jnp.abs(bc[None, :] - gc) - bs[None, :] - gs, 0.0
        )
        return jnp.sum(d * d, axis=1) >= m2

    def _compact_candidates(cand, cap):
        """(cidx, cok, ppos) fixed-cap candidate list from a bool node
        mask: stable compaction (level-major order preserved, so the
        kept prefix is ancestor-closed whenever ``cand`` is), num_n
        sentinel on dead slots keeps the list ascending for the
        parent-position searchsorted, ppos clamped into the list."""
        ordc = jnp.argsort(~cand, stable=True)[:cap]
        cok = cand[ordc]
        cidx = jnp.where(cok, ordc, num_n).astype(jnp.int32)
        ppos = jnp.searchsorted(
            cidx, tree.parent[jnp.minimum(cidx, num_n - 1)]
        ).astype(jnp.int32)
        return cidx, cok, jnp.minimum(ppos, cap - 1)

    sf = cfg.super_factor
    if cfg.compaction not in ("sort", "bitmask"):
        raise ValueError(f"unknown compaction mode {cfg.compaction!r}")
    use_bitmask = cfg.compaction == "bitmask"
    if use_bitmask and num_n > (1 << 24):
        raise ValueError(
            f"bitmask compaction packs node indices in 24 bits; "
            f"{num_n} nodes needs compaction='sort'"
        )
    # the LET essential set composes with BOTH compactions at sf == 0;
    # with the bitmask path it additionally feeds the superblock
    # pre-pass (supers classify against the slab's essential list, not
    # the full tree — the essential-set machinery reused one level up)
    use_let = shard is not None and cfg.let_cap > 0 and (
        sf == 0 or use_bitmask
    )
    ecap = min(cfg.let_cap, num_n) if use_let else 0
    scap = min(cfg.super_cap, num_n)
    if use_let:
        # per-shard essential node set (focused-octree / LET analog,
        # octree_focus_mpi.hpp:50-698): ONE slab-bbox classification
        # shared by every block of this shard. Monotone MAC => the open
        # set + accepted cut is ancestor-closed, and any node outside it
        # has an accepted ancestor INSIDE it for every block (block
        # bboxes are subsets of the slab bbox computed from the same
        # live positions, so the superblock containment argument applies
        # with zero staleness).
        with phase_scope("gravity-mac"):
            bc_s, bs_s = _bbox(x + shift[0], y + shift[1], z + shift[2])
            accept_s = valid & _accept(bc_s, bs_s, ccenter, chalf, mac2)
            anc_s = jnp.where(self_parent, False, accept_s[tree.parent])
            cand_s = ~anc_s
            lidx_, lok, lpar = _compact_candidates(cand_s, ecap)
            let_n = jnp.sum(cand_s)

    @named_phase("gravity-m2p")
    def _m2p_eval(tx, ty, tz, order_m, m2p_ok):
        """Far-field eval of one block's fixed-cap M2P list. Shared by
        the sort and bitmask compactions: identical masked sums over
        identical slot layouts keep the two paths bitwise equal."""
        nd = node_packed[jnp.minimum(order_m, num_n - 1)]  # one row gather
        if cfg.multipole_order > 0:
            from sphexa_tpu.gravity import spherical as sp

            nc_ = sp.ncoef(cfg.multipole_order)
            coeffs = jax.lax.complex(nd[:, 4 : 4 + nc_], nd[:, 4 + nc_ :])
            return sp.m2p(tx, ty, tz, nd[:, 0:3], coeffs, m2p_ok,
                          cfg.multipole_order)
        return mp.m2p(tx, ty, tz, nd[:, 0:3], nd[:, 3:10], nd[:, 10], m2p_ok)

    @named_phase("gravity-p2p")
    def _p2p_leaf_ranges(order_p, p2p_ok):
        """Sorted-array row ranges of one block's near-field leaves."""
        order_p = jnp.minimum(order_p, num_n - 1)
        lidx = tree.leaf_of_node[order_p]  # (P,)
        start = jnp.where(p2p_ok, edges[lidx], 0)
        length = jnp.where(p2p_ok, edges[lidx + 1] - edges[lidx], 0)
        return start, length

    @named_phase("gravity-p2p")
    def _p2p_xla(tx, ty, tz, th, bi, start, length, p2p_ok):
        """Portable gather-based near field (cfg.use_pallas=False)."""
        cand = start[:, None] + jnp.arange(cfg.leaf_cap, dtype=jnp.int32)
        cand_ok = (cand < (start + length)[:, None]) & p2p_ok[:, None]
        cand = jnp.clip(cand, 0, n - 1).reshape(-1)  # (P*C,)
        cand_ok = cand_ok.reshape(-1)
        # in a shifted replica pass a particle's own image is a real pair
        pair_ok = cand_ok[None, :] & ((cand[None, :] != bi[:, None]) | allow_self)
        return mp.p2p(
            tx, ty, tz, th,
            x[cand], y[cand], z[cand], m[cand], h[cand], pair_ok,
        )

    if use_bitmask:
        from sphexa_tpu.gravity import pallas_compact as pcmp
        from sphexa_tpu.sph.pallas_pairs import pallas_interpret

        interp = pallas_interpret()
        # first-accepted-ancestor by PARENT-GEOMETRY re-evaluation:
        # anc(block, node) == accept(block, parent(node)), so evaluating
        # the MAC on the parent's own (gathered-once) arrays replaces the
        # per-block (B, N) accept[parent] gather — identical f32 inputs,
        # identical booleans, no gather on the hot path. Works for ANY
        # candidate subset without requiring the parent in the list.
        par_i = jnp.minimum(tree.parent, num_n - 1)
        pcc = ccenter[par_i]
        pch = chalf[par_i]
        pmac2 = mac2[par_i]
        anc_ok = (~self_parent) & valid[par_i]
        leaf_ok = tree.is_leaf & valid
        iota_n = jnp.arange(num_n, dtype=jnp.int32)
        dense_geo = (ccenter, chalf, mac2, pcc, pch, pmac2, anc_ok,
                     leaf_ok, valid, jnp.ones((num_n,), bool), iota_n)

        def _gather_geo(cidx, ok):
            """Candidate-space MAC arrays of one node list (gathered ONCE
            per list and shared by every block classifying against it —
            the per-block candidate gathers are what sank the round-4
            superblock formulation)."""
            ci = jnp.minimum(cidx, num_n - 1)
            return (ccenter[ci], chalf[ci], mac2[ci], pcc[ci], pch[ci],
                    pmac2[ci], anc_ok[ci] & ok, leaf_ok[ci] & ok,
                    valid[ci] & ok, ok, ci)

        def _packed_cls(bc, bs, geo):
            """Per-candidate M2P/P2P/pruned class, packed with the node
            index for the compaction kernel."""
            cc, ch, m2, pc_, ph_, pm2, aok, lfk, vld, _ok, idxs = geo
            acc = vld & _accept(bc, bs, cc, ch, m2)
            anc = aok & _accept(bc, bs, pc_, ph_, pm2)
            cls = jnp.where(acc & ~anc, 0, jnp.where(lfk & ~acc, 1, 2))
            return (cls.astype(jnp.int32) << pcmp.IDX_BITS) | idxs

        def _packed_cand(bc, bs, geo):
            """Superblock pre-pass class: candidate = parent NOT accepted
            (open set + accepted cut; ancestor-closed under the monotone
            MAC — parents have smaller level-major indices, so any cap
            prefix of the ascending list stays closed)."""
            _cc, _ch, _m2, pc_, ph_, pm2, aok, _lfk, _vld, ok, idxs = geo
            anc = aok & _accept(bc, bs, pc_, ph_, pm2)
            cls = jnp.where(ok & ~anc, 0, 2)
            return (cls.astype(jnp.int32) << pcmp.IDX_BITS) | idxs

        @named_phase("gravity-mac")
        def _block_bm(bi, geo):
            bc, bs = _bbox(x[bi] + shift[0], y[bi] + shift[1],
                           z[bi] + shift[2])
            return _packed_cls(bc, bs, geo)

        def _eval_bm(bi, om, mn, op, pn):
            tx = x[bi] + shift[0]
            ty = y[bi] + shift[1]
            tz = z[bi] + shift[2]
            th = h[bi]
            m2p_ok = jnp.arange(cfg.m2p_cap, dtype=jnp.int32) < mn
            ax, ay, az, phi = _m2p_eval(tx, ty, tz, om, m2p_ok)
            p2p_ok = jnp.arange(cfg.p2p_cap, dtype=jnp.int32) < pn
            start, length = _p2p_leaf_ranges(op, p2p_ok)
            if cfg.use_pallas:
                return ax, ay, az, phi, mn, pn, start, length
            pax, pay, paz, pphi = _p2p_xla(tx, ty, tz, th, bi, start,
                                           length, p2p_ok)
            return ax + pax, ay + pay, az + paz, phi + pphi, mn, pn

        if use_let:
            let_geo = _gather_geo(jnp.minimum(lidx_, num_n - 1), lok)

        if sf > 0:
            # two-level hierarchical classification, bitmask-compacted:
            # supers classify against the LET list (sharded) or the full
            # tree, keep their candidate cut through the SAME kernel, and
            # blocks classify only against their super's list — all node
            # data gathered once per super, never per block.
            sblk = sf * blk
            num_super = -(-n // sblk)
            sidx = jnp.arange(num_super * sblk, dtype=jnp.int32)
            sidx = jnp.minimum(sidx, n - 1).reshape(num_super, sblk)
            pre_geo = let_geo if use_let else dense_geo

            @named_phase("gravity-mac")
            def one_super_pre(si):
                bc, bs = _bbox(x[si] + shift[0], y[si] + shift[1],
                               z[si] + shift[2])
                return _packed_cand(bc, bs, pre_geo)

            spc = max(1, min(num_super, chunk))
            nsc = -(-num_super // spc)
            sidx_p = jnp.concatenate(
                [sidx, jnp.broadcast_to(sidx[-1:],
                                        (nsc * spc - num_super, sblk))]
            ) if nsc * spc > num_super else sidx

            @named_phase("gravity-mac")
            def pre_chunk(sx):
                pk = jax.vmap(one_super_pre)(sx)
                sc, sn, _, _ = pcmp.compact_class_lists(
                    pk, scap, 128, interpret=interp)
                return sc, sn

            with phase_scope("gravity-mac"):
                scand, scand_n = jax.lax.map(
                    pre_chunk, sidx_p.reshape(nsc, spc, sblk))
            scand = scand.reshape(-1, scap)[:num_super]
            scand_n = scand_n.reshape(-1)[:num_super]
            c_max = jnp.max(scand_n)

            idxb = jnp.arange(num_super * sf * blk, dtype=jnp.int32)
            idxb = jnp.minimum(idxb, n - 1).reshape(num_super, sf, blk)

            def one_super_main(args):
                sc, sn, bidx = args
                with phase_scope("gravity-mac"):
                    ok = jnp.arange(scap, dtype=jnp.int32) < jnp.minimum(
                        sn, scap)
                    geo = _gather_geo(sc, ok)
                    pk = jax.vmap(lambda bi: _block_bm(bi, geo))(bidx)
                    om, mn, op, pn = pcmp.compact_class_lists(
                        pk, cfg.m2p_cap, cfg.p2p_cap, interpret=interp)
                return jax.vmap(_eval_bm)(bidx, om, mn, op, pn)

            # the block loops carry the MAC's scope: the loop op, its
            # per-iteration slicing and stacking and the copies XLA adds
            # round its carry belong to no stage of the body (on the v5e
            # 151 ms of an Evrard 1.1M step, PERF.md PR 23). The body's
            # stages keep their scopes further down the op's path
            # (.../sphexa/gravity-mac/while/body/.../sphexa/gravity-m2p/):
            # a reader that takes the innermost scope sees them, one
            # that takes the outermost sees the loop whole
            with phase_scope("gravity-mac"):
                out = jax.lax.map(one_super_main, (scand, scand_n, idxb))
        else:
            geo0 = let_geo if use_let else dense_geo

            def one_chunk_bm(bidx):
                with phase_scope("gravity-mac"):
                    pk = jax.vmap(lambda bi: _block_bm(bi, geo0))(bidx)
                    om, mn, op, pn = pcmp.compact_class_lists(
                        pk, cfg.m2p_cap, cfg.p2p_cap, interpret=interp)
                return jax.vmap(_eval_bm)(bidx, om, mn, op, pn)

            with phase_scope("gravity-mac"):
                out = jax.lax.map(one_chunk_bm, idx)

    if not use_bitmask and sf > 0:
        # superblock pre-pass (the two-level hierarchical classification):
        # classify a ~sf*blk-particle bbox against ALL nodes once, keep
        # its OPEN set + accepted cut — ancestor-closed, so per-block
        # refinement only re-evaluates this candidate list. Super-accept
        # implies block-accept (a block's bbox is inside the super bbox,
        # so its node distance can only grow), hence no block ever needs
        # a node outside the list.
        sblk = sf * blk
        num_super = -(-n // sblk)
        sidx = jnp.arange(num_super * sblk, dtype=jnp.int32)
        sidx = jnp.minimum(sidx, n - 1).reshape(num_super, sblk)

        @named_phase("gravity-mac")
        def one_super(si):
            bc, bs = _bbox(x[si] + shift[0], y[si] + shift[1],
                           z[si] + shift[2])
            accept = valid & _accept(bc, bs, ccenter, chalf, mac2)
            # monotone MAC: an accepted strict ancestor == accepted parent
            anc = jnp.where(self_parent, False, accept[tree.parent])
            cand = ~anc  # open nodes + the accepted cut (ancestor-closed)
            cidx, cok, ppos = _compact_candidates(cand, scap)
            return cidx, cok, ppos, jnp.sum(cand)

        nsc = -(-num_super // chunk)
        sidx_p = jnp.concatenate(
            [sidx, jnp.broadcast_to(sidx[-1:], (nsc * chunk - num_super, sblk))]
        ) if nsc * chunk > num_super else sidx
        with phase_scope("gravity-mac"):
            scand, scand_ok, spar, scand_n = jax.lax.map(
                jax.vmap(one_super), sidx_p.reshape(nsc, chunk, sblk)
            )
        scand = scand.reshape(-1, scap)
        scand_ok = scand_ok.reshape(-1, scap)
        spar = spar.reshape(-1, scap)
        c_max = jnp.max(scand_n)

    def one_block(bi, bnum):
        """bi: (blk,) particle indices of one target group; bnum: its
        block index (selects the superblock candidate list)."""
        tx, ty, tz, th = x[bi] + shift[0], y[bi] + shift[1], z[bi] + shift[2], h[bi]
        with phase_scope("gravity-mac"):
            bc, bs = _bbox(tx, ty, tz)

            if sf > 0 or use_let:
                if sf > 0:
                    sid = bnum // sf
                    cidx = jnp.minimum(scand[sid], num_n - 1)
                    cok = scand_ok[sid]
                    ppos = spar[sid]
                else:
                    # LET: the shard-wide essential list, shared by blocks
                    cidx = jnp.minimum(lidx_, num_n - 1)
                    cok = lok
                    ppos = lpar
                accept = cok & valid[cidx] & _accept(
                    bc, bs, ccenter[cidx], chalf[cidx], mac2[cidx]
                )
                # monotone MAC: the first accepted ancestor IS the parent.
                # The root's parent is ITSELF — mask self-parents or an
                # accepted root (far replica shifts) would mark itself as its
                # own accepted ancestor and zero the whole interaction
                not_self = cidx[ppos] != cidx
                anc = accept[ppos] & not_self
                m2p_mask = accept & ~anc
                p2p_mask = cok & tree.is_leaf[cidx] & valid[cidx] & ~accept
            else:
                cidx = None
                accept = valid & _accept(bc, bs, ccenter, chalf, mac2)
                # monotone MAC (see mac2 above): one parent gather replaces
                # the per-level first-accepted-ancestor downsweep, and
                # ~accept already implies no accepted ancestor for leaves
                anc = jnp.where(self_parent, False, accept[tree.parent])
                m2p_mask = accept & ~anc
                p2p_mask = tree.is_leaf & valid & ~accept
            m2p_n = jnp.sum(m2p_mask)
            p2p_n = jnp.sum(p2p_mask)

            # ONE 3-class sort compacts both interaction lists: class-0 nodes
            # (M2P) land first, class-1 (P2P leaves) directly after, so the
            # P2P list is a dynamic slice at the M2P count. The class and the
            # node index ride in one PACKED int32 key (class in the top bits,
            # index below) — a single single-operand sort where a stable
            # argsort + sort pair cost ~2x (the 208 ms phase at 1M);
            # unique keys make it
            # order-preserving within a class by construction
            cls = jnp.where(m2p_mask, 0, jnp.where(p2p_mask, 1, 2))
            cls_len = cls.shape[0]
            nbits = max(1, int(np.ceil(np.log2(max(cls_len, 2)))))
            iota_k = jnp.arange(cls_len, dtype=jnp.int32)
            # measured equals: lax.top_k(k = m2p_cap + p2p_cap) on the
            # negated keys costs the SAME as the full sort at 1M/58k nodes
            # (803.8 vs 798.7 ms end-to-end) — XLA's TPU top_k is not a
            # partial sort win at k/N ~ 13%; keep the simpler full sort
            ks = jnp.sort((cls.astype(jnp.int32) << nbits) | iota_k)
            order_all = ks & jnp.int32((1 << nbits) - 1)
            cls_sorted = ks >> nbits
            if cidx is not None:
                order_all = cidx[order_all]
            # sentinel-pad so the fixed-cap slices below stay in range when
            # the candidate list is shorter than a cap (tiny trees / small
            # super lists)
            padn = max(cfg.m2p_cap, cfg.p2p_cap)
            order_all = jnp.concatenate(
                [order_all, jnp.full((padn,), num_n - 1, order_all.dtype)]
            )
            cls_sorted = jnp.concatenate(
                [cls_sorted, jnp.full((padn,), 2, cls_sorted.dtype)]
            )
            order_m = jnp.minimum(order_all[: cfg.m2p_cap], num_n - 1)
            m2p_ok = cls_sorted[: cfg.m2p_cap] == 0
        ax, ay, az, phi = _m2p_eval(tx, ty, tz, order_m, m2p_ok)

        # dynamic_slice clamps the start when m2p_n is near the array
        # end; the slice then still covers the whole class-1 block and
        # stray class-0/2 entries are masked
        order_p = jax.lax.dynamic_slice(order_all, (m2p_n,), (cfg.p2p_cap,))
        p2p_ok = jax.lax.dynamic_slice(
            cls_sorted, (m2p_n,), (cfg.p2p_cap,)
        ) == 1
        start, length = _p2p_leaf_ranges(order_p, p2p_ok)

        if cfg.use_pallas:
            # defer the near field to the streamed engine (below)
            return ax, ay, az, phi, m2p_n, p2p_n, start, length

        pax, pay, paz, pphi = _p2p_xla(tx, ty, tz, th, bi, start, length,
                                       p2p_ok)
        return ax + pax, ay + pay, az + paz, phi + pphi, m2p_n, p2p_n

    if not use_bitmask:
        bnum = jnp.arange(num_chunks * chunk, dtype=jnp.int32)
        bnum = jnp.minimum(bnum, num_blocks - 1).reshape(num_chunks, chunk)

        def one_chunk(args):
            bidx, bn = args
            return jax.vmap(one_block)(bidx, bn)

        with phase_scope("gravity-mac"):
            out = jax.lax.map(one_chunk, (idx, bnum))
    escaped = jnp.asarray(False)
    grav_halo_metrics = None
    if cfg.use_pallas:
        ax, ay, az, phi, m2p_n, p2p_n, p2p_starts, p2p_lens = out
        starts2 = p2p_starts.reshape(-1, cfg.p2p_cap)
        lens2 = p2p_lens.reshape(-1, cfg.p2p_cap)
        jd = None
        if shard is not None:
            # near-field halos: leaf row ranges are GLOBAL rows; fetch
            # the remote ones through the halo exchange (the same
            # machinery the SPH stages ride; runs escaping their cap
            # flip the p2p sentinel so the driver re-sizes). The caller
            # clamps the window/caps <= slab rows (_gravity_sharded_stage).
            from sphexa_tpu.parallel import exchange as ex
            from sphexa_tpu.sph.pallas_pairs import GroupRanges

            axis, P_, win = shard
            kk = jax.lax.axis_index(axis)
            zf = jnp.zeros_like(starts2, dtype=jnp.float32)
            pr = GroupRanges(
                starts=starts2, lens=lens2, shift_x=zf, shift_y=zf,
                shift_z=zf,
                ncells=jnp.zeros(starts2.shape[0], jnp.int32),  # recomputed
                occupancy=jnp.int32(0),
                boxl=jnp.full((3,), 1e30, jnp.float32),
            )
            if isinstance(win, tuple):
                # MAC-sized sparse near field: ``edges`` (the sharded
                # upsweep's global leaf row boundaries) IS a cell table
                # in the exchange.py sense, so the cell-granular serve
                # ships only the rows of leaves this slab's essential
                # set opens — sized by sizing.device_gravity_halo, with
                # full slabs (caps == S) as the retry ceiling
                lranges, covered_all, escaped, covered = (
                    ex.localize_ranges_sparse(pr, edges, n, P_, win, kk,
                                              axis)
                )
                halo, _ = ex.serve_sparse(
                    (x, y, z, m, h), covered_all, edges, n, win, P_, kk,
                    axis, token=covered_all,
                )
                grav_halo_metrics = ex.exchange_metrics_sparse(
                    covered, edges, n, win, P_, kk
                )
            else:
                lranges, bounds, escaped = ex.localize_ranges(
                    pr, n, P_, win, kk, axis
                )
                halo = ex.serve_windows((x, y, z, m, h), bounds, n, win,
                                        P_, kk, axis)
            jd = tuple(
                jnp.concatenate([o, a])
                for o, a in zip((x, y, z, m, h), halo)
            )
            starts2, lens2 = lranges.starts, lranges.lens
        pax, pay, paz, pphi = _pallas_p2p(
            x, y, z, m, h, shift, allow_self, cfg,
            starts2, lens2, jdata=jd,
        )
        blkpad = ax.reshape(-1).shape[0]
        ax = ax.reshape(-1) + pax[:blkpad]
        ay = ay.reshape(-1) + pay[:blkpad]
        az = az.reshape(-1) + paz[:blkpad]
        phi = phi.reshape(-1) + pphi[:blkpad]
    else:
        ax, ay, az, phi, m2p_n, p2p_n = out
    ax = ax.reshape(-1)[:n] * cfg.G
    ay = ay.reshape(-1)[:n] * cfg.G
    az = az.reshape(-1)[:n] * cfg.G
    phi = phi.reshape(-1)[:n] * cfg.G
    # padded tail lanes duplicate the last particle; only [:n] is kept, and
    # egrav sums the trimmed arrays, so duplicates never double-count.
    # evaluations over REAL blocks only, matching the phantom-masked
    # numerator below: dense = blocks x nodes; hierarchical = supers x
    # nodes (pre-pass) + blocks x super_cap (refinement)
    if sf > 0:
        # supers classify against the LET list on the sharded bitmask
        # path (plus the one slab-bbox sweep that builds it), the full
        # tree otherwise
        pre_c = ecap if (use_bitmask and use_let) else num_n
        evals = num_super * pre_c + num_blocks * scap
        if use_bitmask and use_let:
            evals += num_n
    elif use_let:
        evals = num_n + num_blocks * ecap
    else:
        evals = num_blocks * num_n
    # per-block candidate width the compaction runs over — with the
    # sort path this is also the per-block sort width, so the hot-path
    # complexity proxy (blocks x width) is comparable across modes
    compact_width = scap if sf > 0 else (ecap if use_let else num_n)
    # phantom tail blocks (chunk padding re-evaluates the last particle as
    # a point bbox) classify DIFFERENTLY from any real block — a point
    # target accepts more nodes than the block containing it — and their
    # counts would inflate the cap-sizing high-water marks (their forces
    # are discarded by the [:n] trim either way)
    real_blk = (
        jnp.arange(m2p_n.size, dtype=jnp.int32) < num_blocks
    ).reshape(m2p_n.shape)
    m2p_n = jnp.where(real_blk, m2p_n, 0)
    p2p_n = jnp.where(real_blk, p2p_n, 0)
    p2p_hw = jnp.max(p2p_n)
    if shard is not None:
        # an escaped near-field run means truncated candidates: the
        # SHARED overflow contract encodes it as a p2p overflow (and
        # pmaxes) so the driver re-sizes the halo window
        from sphexa_tpu.parallel.exchange import chain_after, fold_escape_sentinel

        if cfg.use_pallas and jd is not None:
            # p2p_n comes from the PRE-exchange traversal sweep, so the
            # overflow pmax has no data order against serve_windows'
            # all_to_all without this pin (the rendezvous-race class
            # JXA201 gates)
            p2p_hw = chain_after(p2p_hw, jd[0])
        p2p_hw = fold_escape_sentinel(p2p_hw, escaped, cfg.p2p_cap, shard[0])
    diagnostics = {
        "m2p_max": jnp.max(m2p_n),
        "p2p_max": p2p_hw,
        "leaf_occ": leaf_occ,
        # superblock candidate-list high water (cap guard; 0 = dense path)
        "c_max": c_max if sf > 0 else jnp.int32(0),
        # per-shard essential-set high water (LET cap guard; 0 = off)
        "let_max": let_n if use_let else jnp.int32(0),
        # compaction complexity proxy: candidate slots each block's list
        # materialization scans (the interpret-mode op-count stand-in for
        # chip timings)
        "compact_width": jnp.int32(compact_width),
        # accepted-to-evaluated MAC work (VERDICT r2 #4 diagnostic): the
        # hierarchical path shrinks the denominator by ~num_n/super_cap
        "mac_work_ratio": (
            (jnp.sum(m2p_n) + jnp.sum(p2p_n)).astype(jnp.float32)
            / jnp.float32(evals)
        ),
    }
    if grav_halo_metrics is not None:
        # sparse MAC-window mode only (the windowed / grav_window=0
        # lowering stays byte-identical): device-measured TRUE remote
        # row need + per-distance cap occupancy, folded to the schema-v7
        # gravity-stage exchange telemetry by _gravity_sharded_stage
        diagnostics["halo_rows"] = grav_halo_metrics["halo_rows"]
        diagnostics["halo_occ"] = grav_halo_metrics["halo_occ"]
    if with_phi:
        return ax, ay, az, phi, diagnostics
    egrav = 0.5 * jnp.sum(m * phi)
    return ax, ay, az, egrav, diagnostics
