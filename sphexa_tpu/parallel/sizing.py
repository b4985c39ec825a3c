"""Device-side (re)configuration sizing: O(N/P)-transfer replacements for
the host gathers in make_propagator_config / Simulation._configure*.

The reference never materializes the global problem on one rank: octree
counts are allreduce-incremental (cstone/tree/update_mpi.hpp:26-106) and
assignment is rank-local (cstone/domain/assignment.hpp:84-122). The
transposition here: every sizing quantity is computed by jitted reductions
over the (possibly sharded) device arrays — GSPMD partitions them over the
mesh — and only SCALARS or O(#cells) histograms ever reach the host.

Three groups of helpers:

- ``sizing_stats``: max cell occupancy + per-dim group extents — the
  inputs of make_propagator_config's level/cap/window choice.
- ``device_halo_window``: the per-(dest, src) shard row-window maximum that
  sizes the windowed all_to_all exchange (parallel/exchange.py), computed
  with scatter-min/max instead of the host loop in estimate_halo_window.
- ``key_histogram``/``drill_histogram`` + ``leaf_array_from_device_keys``:
  the distributed-tree-build analog — a base-level key histogram plus
  targeted drill-downs of overfull cells replaces shipping the full key
  array to the host (update_mpi.hpp's node-count allreduce, transposed).
"""

import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.dtypes import KEY_BITS, KEY_DTYPE

# numpy, NOT jnp: lazily-imported module — a jnp constant built while a
# trace is active would itself be a tracer and leak into later traces
# (see parallel/exchange.py INF32)
INF32 = np.int32(2**30)

# device->host bytes moved by the sizing path since the last reset — the
# transfer-size counter that PROVES reconfiguration is O(N/P): every fetch
# in the device-sizing path goes through fetch(), and tests run the whole
# configure under jax.transfer_guard_device_to_host("disallow") so a stray
# implicit np.asarray(full_array) fails loudly instead of hiding.
TRANSFER_BYTES = 0


def reset_transfer_bytes() -> None:
    global TRANSFER_BYTES
    TRANSFER_BYTES = 0


def fetch(x):
    """Explicit, metered device->host transfer (allowed under the
    device-to-host transfer guard; implicit transfers are not)."""
    global TRANSFER_BYTES
    out = jax.device_get(x)
    TRANSFER_BYTES += sum(
        a.nbytes for a in jax.tree.leaves(out) if hasattr(a, "nbytes")
    )
    return out


# ---------------------------------------------------------------------------
# neighbor-config sizing
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("level", "group", "curve"))
def sizing_stats(x, y, z, box, level: int, group: int,
                 curve: str = "hilbert", keys=None, order=None):
    """(occ_max, ext (3,)): the per-level stats make_propagator_config
    needs beyond n and h_max (h_max must be fetched BEFORE this call —
    ``level`` is static and derives from it) — one jitted pass, four
    scalars to the host.

    ``keys``/``order``: optional precomputed device keys + argsort of
    the SAME (x, y, z, box, curve). Simulation._configure passes them
    when self-gravity also needs keys, so the multi-device reconfigure
    pays keygen+argsort over N ONCE (round-4 reviewer finding: this
    helper and _configure_gravity each ran their own)."""
    from sphexa_tpu.sfc.keys import compute_sfc_keys

    if keys is None:
        keys = compute_sfc_keys(x, y, z, box, curve=curve)
    if order is None:
        order = jnp.argsort(keys)
    skeys = keys[order]
    shift = KEY_DTYPE(3 * (KEY_BITS - level))
    ncell3 = (1 << level) ** 3
    cid = (skeys >> shift).astype(jnp.int32)
    occ = jnp.max(jnp.zeros(ncell3, jnp.int32).at[cid].add(1))

    n = x.shape[0]
    ng = -(-n // group)
    pad = ng * group - n

    def ext_of(a):
        a = a[order]
        if pad:
            a = jnp.concatenate([a, jnp.broadcast_to(a[-1:], (pad,))])
        g = a.reshape(ng, group)
        return jnp.max(g.max(axis=1) - g.min(axis=1))

    ext = jnp.stack([ext_of(x), ext_of(y), ext_of(z)])
    return occ, ext


# ---------------------------------------------------------------------------
# halo-window sizing
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("nbr", "P"))
def _halo_window_spans(x, y, z, h, keys, box, nbr, P: int):
    """Max over (dest, src != dest) pairs of the source-row span dest's
    candidate runs need — the device analog of estimate_halo_window's
    host loop, via scatter-min/max into a (P, P) bounds matrix."""
    from sphexa_tpu.sph.pallas_pairs import group_cell_ranges

    order = jnp.argsort(keys)
    xs, ys, zs, hs = x[order], y[order], z[order], h[order]
    skeys = keys[order]
    ranges = group_cell_ranges(xs, ys, zs, hs, skeys, box, nbr)
    starts, lens = ranges.starts, ranges.lens  # (NG, W3)
    ng, w3 = starts.shape
    n = x.shape[0]
    S = -(-n // P)

    # a group's rows can straddle two dest slabs: charge its runs to both
    g0 = (jnp.arange(ng, dtype=jnp.int32) * nbr.group) // S
    g1 = jnp.minimum(
        ((jnp.arange(ng, dtype=jnp.int32) + 1) * nbr.group - 1) // S, P - 1
    )

    active = lens > 0
    ends = starts + lens
    # a run crossing a slab boundary contributes a clipped piece to both
    # sources (the caller clamps run_cap <= S, so a run touches at most
    # two slabs and the two pieces below cover it exactly)
    src0 = jnp.clip(starts // S, 0, P - 1)
    src1 = jnp.clip(jnp.where(active, ends - 1, starts) // S, 0, P - 1)

    lo_m = jnp.full((P, P), INF32, jnp.int32)
    hi_m = jnp.zeros((P, P), jnp.int32)

    def add_piece(lo_m, hi_m, dest, src, lo, hi, valid):
        d = jnp.broadcast_to(dest[:, None], (ng, w3))
        lo = jnp.where(valid, lo, INF32)
        hi = jnp.where(valid, hi, 0)
        lo_m = lo_m.at[d, src].min(lo)
        hi_m = hi_m.at[d, src].max(hi)
        return lo_m, hi_m

    for dest in (g0, g1):
        # piece inside the run's first source slab
        p0_hi = jnp.minimum(ends, (src0 + 1) * S)
        lo_m, hi_m = add_piece(lo_m, hi_m, dest, src0, starts, p0_hi, active)
        # remainder in the next slab (zero-width unless crossing)
        cross = active & (src1 > src0)
        lo_m, hi_m = add_piece(
            lo_m, hi_m, dest, src1, src1 * S, ends, cross
        )

    off_diag = ~jnp.eye(P, dtype=bool)
    span = jnp.where(off_diag & (hi_m > 0), hi_m - jnp.minimum(lo_m, hi_m), 0)
    return jnp.max(span)


def device_halo_window(x, y, z, h, keys, box, nbr, P: int,
                       margin: float = 1.4, quantum: int = 1024) -> int:
    """estimate_halo_window with device-side discovery: one scalar comes
    to the host. Same margin/quantum padding contract."""
    import dataclasses

    n = x.shape[0]
    S = -(-n // P)
    # the sharded force stage clamps run_cap to the slab size (a run must
    # come from one source shard, propagator._std_forces_sharded), so
    # measure with the SAME clamp — it also guarantees a run spans at most
    # two slabs, which the two-piece scatter below relies on
    if nbr.run_cap > S:
        nbr = dataclasses.replace(nbr, run_cap=S)
    wmax = max(int(fetch(_halo_window_spans(x, y, z, h, keys, box, nbr, P))), 1)
    padded = int(-(-int(wmax * margin) // quantum) * quantum)
    return min(padded, S)


def _slab_mesh(P: int, mesh=None):
    """The run's ``mesh``, or one of the first P local devices for a
    caller that has none (a script or a test sizing P slabs)."""
    if mesh is not None:
        return mesh
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < P:
        raise ValueError(f"sizing {P} slabs takes {P} devices (the needs "
                         f"are taken under shard_map, as the step takes "
                         f"them); jax has {len(devices)}")
    return Mesh(np.asarray(devices[:P]), ("p",))


def _own_slab_read(need, S: int, what: str):
    """A need matrix's diagonal is the slab's own rows: S, whatever the
    state. A pass that read none of them read nothing (the fault of the
    GSPMD form on the four-chip host, PR 29): say so here, where it is
    cheap, and not as caps at their floor that no margin can out-grow."""
    if int(np.min(need)) <= 0:
        raise RuntimeError(
            f"{what}: the sizing pass read {int(np.min(need))} of a slab's "
            f"own {S} rows; its needs cannot be trusted on this backend")


def pad_run_slots(runs: int, margin: float, quantum: int = 8) -> int:
    """Slots of an exchange's run axis from the observed high-water of
    live runs a group (``exchange.cut_run_slots``): the halo's margin, a
    floor of four more because the count is a small integer (a group
    that gains three runs must not trip at any margin), rounded up to
    ``quantum``. The caller clamps it to the full width."""
    return int(-(-(int(max(int(runs), 1) * margin) + 4) // quantum) * quantum)


@functools.partial(jax.jit, static_argnames=("nbr", "P", "mesh"))
def sparse_need_matrix(x, y, z, h, keys, box, nbr, P: int, mesh=None):
    """``sparse_needs_and_runs``' need matrix alone."""
    return sparse_needs_and_runs(x, y, z, h, keys, box, nbr, P, mesh)[0]


@functools.partial(jax.jit, static_argnames=("nbr", "P", "mesh"))
def sparse_needs_and_runs(x, y, z, h, keys, box, nbr, P: int, mesh=None,
                          radius_pad=0.0):
    """(need, runs). ``need``: the (P_dest, P_src) row-need matrix of the
    sparse cell-granular halo
    exchange: entry [k, j] = rows shard k's covered cells clip to shard
    j's slab (diagonal = own slab, served locally). Computed from the
    SAME monotone group windows the in-step stage marks coverage with
    (group_cell_ranges over the same sorted arrays), so the in-step
    escape can only fire after genuine drift — and the in-step
    telemetry ``shard_rows`` (exchange.exchange_metrics_sparse) must
    equal this matrix's off-diagonal row sums on an undrifted state
    (pinned by tests/test_parallel.py). ``runs``: (P,) each slab's
    high-water of live runs a group (``max(ranges.ncells)`` of the same
    prologue), what the exchange's run-slot axis is sized from.
    ``radius_pad``: the prologue's coverage slack (the pair lists' skin:
    a list rebuild's halo stage runs on the inflated windows).

    The needs are taken the way the step takes them, under ``shard_map``
    over ``mesh`` (the run's; without one, the first P local devices):
    each device builds the table from the psum of the slabs' histograms
    and marks its own slab's coverage. The earlier form, slabs vmapped
    in one program that GSPMD partitioned over the run's sharded
    operands, returned all zeros on a four-chip v5e for the Evrard
    sphere at 4.19M (729 window slots x 16,364 groups a slab) while the
    step's own stage read 20-139 k rows per pair: every cap came out at
    its 256-row floor and no margin growth could reach the need
    (PERF.md, PR 29)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec

    from sphexa_tpu.parallel.exchange import (
        _sparse_layout,
        coverage_from_runs,
        global_cell_table,
    )
    from sphexa_tpu.sph.pallas_pairs import group_cell_ranges

    n = x.shape[0]
    if n % P:
        raise ValueError(f"sparse halo sizing needs n % P == 0 "
                         f"(shard_state's contract), got {n} % {P}")
    S = n // P
    order = jnp.argsort(keys)
    xs, ys, zs, hs = x[order], y[order], z[order], h[order]
    skeys = keys[order]
    mesh = _slab_mesh(P, mesh)
    (axis,) = mesh.axis_names

    # per-SHARD group windows: the in-step prologue forms groups within
    # each slab (rows restart at k*S), so sizing over global group
    # boundaries would measure different bboxes whenever S % group != 0
    # and could under-size a cap with zero drift. Coverage is the in-step
    # stage's own function of the prologue's own products (the runs and
    # the cells they carry), so the two cannot drift apart
    def slab_need(kk, xk, yk, zk, hk):
        table = global_cell_table(kk, nbr.level, axis)
        ranges, cells = group_cell_ranges(xk, yk, zk, hk, None, box, nbr,
                                          table=table, radius_pad=radius_pad,
                                          with_cells=True)
        covered = coverage_from_runs(ranges.starts, ranges.lens, table, cells)
        return (_sparse_layout(covered, table, S, P)[2][None, :],
                jnp.max(ranges.ncells)[None])

    rows = PartitionSpec(axis)
    return shard_map(slab_need, mesh=mesh, in_specs=(rows,) * 5,
                     out_specs=(rows, rows), check_vma=False)(
        skeys, xs, ys, zs, hs)  # (P_dest, P_src), (P,)


def _per_distance_needs(need, P: int):
    """(P,) from a (P_dest, P_src) need matrix: entries 0..P-2 the
    per-DISTANCE row needs (entry r-1 = max over shards j of the rows
    shard (j+r)%P needs from j: parallel/exchange.serve_sparse ships
    round r in a buffer of exactly this size), the last the least of the
    diagonal (``_own_slab_read``'s witness), in one array for one fetch."""
    j = jnp.arange(P, dtype=jnp.int32)
    return jnp.stack(
        [need[(j + r) % P, j].max() for r in range(1, P)]
        + [need[j, j].min()]
    )


@functools.partial(jax.jit, static_argnames=("nbr", "P", "mesh"))
def _sparse_halo_needs(x, y, z, h, keys, box, nbr, P: int, mesh=None,
                       radius_pad=0.0):
    """``_per_distance_needs`` of ``sparse_needs_and_runs``' matrix, then
    the fullest group's live runs over all slabs: one array, one fetch."""
    need, runs = sparse_needs_and_runs(x, y, z, h, keys, box, nbr, P, mesh,
                                       radius_pad)
    return jnp.concatenate([_per_distance_needs(need, P),
                            jnp.max(runs)[None]])


def device_sparse_halo(x, y, z, h, keys, box, nbr, P: int,
                       margin: float = 1.4, quantum: int = 256,
                       mesh=None, radius_pad=0.0,
                       ) -> Tuple[Tuple[int, ...], int]:
    """Size the sparse exchange from the current state: (caps, run_slots).
    ``caps``: the static per-distance row caps (the Hmax tuple of
    shard_halo_stage_sparse). ``run_slots``: the slots of its run axis
    (``pad_run_slots`` of the fullest group's live runs, at most the
    window's W3: ``PropagatorConfig.halo_runs``), under the same
    ``margin``: a trip of either grows both. P + 1 scalars to the host.
    ``mesh``: the run's mesh, ``radius_pad``: the pair lists' skin
    (``sparse_needs_and_runs``)."""
    import dataclasses

    n = x.shape[0]
    S = n // P
    if nbr.run_cap > S:
        nbr = dataclasses.replace(nbr, run_cap=S)
    *per_r, own, runs = np.asarray(fetch(_sparse_halo_needs(
        x, y, z, h, keys, box, nbr, P, mesh, radius_pad)))
    _own_slab_read(own, S, "sparse halo")
    pad = lambda v: min(
        int(-(-int(max(int(v), 1) * margin) // quantum) * quantum), S
    )
    return (tuple(pad(v) for v in per_r),
            min(pad_run_slots(runs, margin), nbr.window ** 3))


@functools.partial(jax.jit, static_argnames=("nbr", "P", "mesh", "hmax",
                                             "run_slots"))
def _list_chunk_needs(x, y, z, h, keys, box, nbr, P: int, mesh,
                      hmax: Tuple[int, ...], run_slots: int, skin):
    """(P, 3) per slab: most candidate chunks a group streams, their sum
    over the slab's groups in whole row tiles, and whether the halo
    escaped ``hmax`` / ``run_slots``: the chunks of the runs as the
    mesh's list build streams them, LOCALIZED into [own | annex] rows
    (a run cut at a slab boundary or moved into the annex changes its
    128-lane alignment, so the global runs' chunks are not these). Taken
    under ``shard_map`` by the rebuild's own halo stage (``ROADMAP.md``
    D14), the sorted arrays made as ``sparse_needs_and_runs`` makes them."""
    from jax import shard_map
    from jax.sharding import PartitionSpec

    from sphexa_tpu.parallel.exchange import shard_halo_stage_sparse
    from sphexa_tpu.sph.pair_lists import _run_chunks
    from sphexa_tpu.sph.pallas_pairs import LIST_ROW_TILE, _round_up

    order = jnp.argsort(keys)
    xs, ys, zs, hs = x[order], y[order], z[order], h[order]
    skeys = keys[order]
    (axis,) = mesh.axis_names

    def slab_chunks(kk, xk, yk, zk, hk):
        ranges, _, _, escaped, _ = shard_halo_stage_sparse(
            xk, yk, zk, hk, kk, box, nbr, P, hmax, axis,
            run_slots=run_slots, radius_pad=skin)
        per_group = jnp.sum(_run_chunks(ranges.starts, ranges.lens), axis=1)
        return jnp.stack([
            jnp.max(per_group),
            jnp.sum(_round_up(per_group, LIST_ROW_TILE)),
            escaped.astype(jnp.int32)])[None, :]

    rows = PartitionSpec(axis)
    return shard_map(slab_chunks, mesh=mesh, in_specs=(rows,) * 5,
                     out_specs=rows, check_vma=False)(skeys, xs, ys, zs, hs)


def device_list_caps(x, y, z, h, keys, box, nbr, skin: float, mesh,
                     halo_margin: float = 1.4, slot_margin: float = 1.3):
    """A mesh's persistent pair lists, sized: ``(halo_cells, halo_runs,
    list_slot_cap, list_slots_cap)``. The halo caps are
    ``device_sparse_halo``'s for the skin-inflated windows a rebuild's
    halo stage runs on (and every steady step ships over); the list caps
    ``estimate_list_caps``' per SLAB, the fullest slab's under
    ``slot_margin``, counted on the runs that stage localizes under those
    caps. ``h``: the smoothing lengths the lists are sized for."""
    import dataclasses

    from sphexa_tpu.neighbors.cell_list import pad_cap
    from sphexa_tpu.sph.pair_lists import LIST_TABLE_TILE

    P = mesh.size
    S = x.shape[0] // P
    hcells, hruns = device_sparse_halo(
        x, y, z, h, keys, box, nbr, P=P, margin=halo_margin, mesh=mesh,
        radius_pad=jnp.float32(skin))
    if nbr.run_cap > S:
        nbr = dataclasses.replace(nbr, run_cap=S)
    need = np.asarray(fetch(_list_chunk_needs(
        x, y, z, h, keys, box, nbr, P, mesh, tuple(min(c, S) for c in hcells),
        hruns, jnp.float32(skin))))
    if need[:, 2].any():
        raise RuntimeError(
            f"pair-list sizing: the halo sized a moment ago (caps {hcells}, "
            f"{hruns} run slots) escaped on slabs "
            f"{np.flatnonzero(need[:, 2]).tolist()}")
    return (hcells, hruns, pad_cap(int(need[:, 0].max()), slot_margin, 8),
            pad_cap(int(need[:, 1].max()), slot_margin, LIST_TABLE_TILE))


# ---------------------------------------------------------------------------
# gravity near-field (MAC) sizing
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("meta", "theta", "P"))
def gravity_need_matrix(xs, ys, zs, ms, skeys, box, tree, meta,
                        theta: float, P: int, shifts=None):
    """(P_dest, P_src) row-need matrix of the sparse gravity near-field
    exchange: entry [k, j] = rows of shard j's slab whose leaf cells FAIL
    the monotone MAC opening test against shard k's slab bbox — dest k's
    P2P essential set (the Warren-Salmon LET boundary). Everything the
    slab bbox accepts is already covered by M2P on the replicated coarse
    tree, so those rows never cross the wire.

    Conservative by the monotone vector MAC (traversal.py
    ``_monotone_mac_geometry``): the accept region only GROWS as the
    target bbox shrinks, so a leaf opened by any in-slab target block
    (or LET / bitmask-superblock classification) is opened by the whole
    slab bbox too — the in-step ``need > cap`` escape can only fire
    after genuine drift. ``shifts`` ((ns, 3), optional) unions the
    opened set over the Ewald replica offsets; ``compute_gravity`` adds
    a shift to the TARGET positions and the shell set is symmetric, so
    ``bc + shift`` covers every replica pass. Inputs are the SORTED
    gravity arrays (``skeys`` ascending) so slab k of ``reshape(P, S)``
    is shard k's key slab."""
    from sphexa_tpu.gravity.traversal import (
        _monotone_mac_geometry,
        compute_multipoles,
    )
    from sphexa_tpu.parallel.exchange import _sparse_layout

    n = xs.shape[0]
    if n % P:
        raise ValueError(f"gravity halo sizing needs n % P == 0 "
                         f"(shard_state's contract), got {n} % {P}")
    S = n // P
    node_mass, node_com, _, edges = compute_multipoles(
        xs, ys, zs, ms, skeys, tree, meta, order=0
    )
    valid = node_mass > 0
    gc, gs, mac2 = _monotone_mac_geometry(box, tree, meta, node_com,
                                          valid, theta)
    slab = lambda a: a.reshape(P, S)
    bmin = jnp.stack([slab(a).min(axis=1) for a in (xs, ys, zs)], axis=1)
    bmax = jnp.stack([slab(a).max(axis=1) for a in (xs, ys, zs)], axis=1)
    bc, bs = 0.5 * (bmax + bmin), 0.5 * (bmax - bmin)  # (P, 3)

    def opened_from(center):
        d = jnp.maximum(
            jnp.abs(center[:, None, :] - gc[None, :, :])
            - bs[:, None, :] - gs[None, :, :], 0.0)
        return jnp.sum(d * d, axis=2) < mac2[None, :]  # (P, num_nodes)

    opened = opened_from(bc)
    if shifts is not None:
        for i in range(shifts.shape[0]):
            opened = opened | opened_from(bc + shifts[i][None, :])
    cov = opened[:, tree.node_of_leaf]  # (P_dest, num_leaves)
    return jax.vmap(lambda c: _sparse_layout(c, edges, S, P)[2])(cov)


@functools.partial(jax.jit, static_argnames=("meta", "theta", "P"))
def _gravity_halo_needs(xs, ys, zs, ms, skeys, box, tree, meta,
                        theta: float, P: int, shifts=None):
    """``_per_distance_needs`` of ``gravity_need_matrix``."""
    need = gravity_need_matrix(xs, ys, zs, ms, skeys, box, tree, meta,
                               theta, P, shifts)
    return _per_distance_needs(need, P)


def device_gravity_halo(xs, ys, zs, ms, skeys, box, tree, meta,
                        theta: float, P: int, shifts=None,
                        margin: float = 1.4, quantum: int = 256,
                        ) -> Tuple[int, ...]:
    """Size the sparse gravity near-field exchange's static per-distance
    row caps (the hmax tuple compute_gravity's sparse shard path hands
    to exchange.serve_sparse). P scalars to the host. A cap padded to
    S ships the full slab for that distance — the retry ceiling, where
    need <= S guarantees the escape sentinel cannot fire."""
    n = xs.shape[0]
    S = n // P
    *per_r, own = np.asarray(fetch(_gravity_halo_needs(
        xs, ys, zs, ms, skeys, box, tree, meta, theta, P, shifts
    )))
    _own_slab_read(own, S, "gravity near field")
    pad = lambda v: min(
        int(-(-int(max(int(v), 1) * margin) // quantum) * quantum), S
    )
    return tuple(pad(v) for v in per_r)


# ---------------------------------------------------------------------------
# distributed gravity-tree build (histogram pyramid + drill-down)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("level",))
def key_histogram(keys, level: int):
    """Global cell-occupancy histogram at ``level`` over the (sharded) key
    array: the allreduce'd node-count vector of update_mpi.hpp:26-106.
    O(8^level) ints to the host, independent of N."""
    shift = KEY_DTYPE(3 * (KEY_BITS - level))
    cid = (keys >> shift).astype(jnp.int32)
    return jnp.zeros((1 << (3 * level),), jnp.int32).at[cid].add(1)


@functools.partial(jax.jit, static_argnames=("level", "sub", "k_cap"))
def drill_histogram(keys, cell_ids_sorted, level: int, sub: int, k_cap: int):
    """Counts of the 8^sub sub-cells of ``k_cap`` selected cells at
    ``level`` — the targeted refinement round for cells still above the
    bucket size (keys outside the selected cells fall in a discard bin).
    cell_ids_sorted: (k_cap,) int32 sorted cell indices, padded with 2^30.
    Returns (k_cap, 8^sub) int32."""
    nsub = 1 << (3 * sub)
    shift_hi = KEY_DTYPE(3 * (KEY_BITS - level))
    cid = (keys >> shift_hi).astype(jnp.int32)
    pos = jnp.searchsorted(cell_ids_sorted, cid).astype(jnp.int32)
    pos_c = jnp.clip(pos, 0, k_cap - 1)
    hit = cell_ids_sorted[pos_c] == cid
    shift_lo = KEY_DTYPE(3 * (KEY_BITS - level - sub))
    subid = ((keys >> shift_lo) & KEY_DTYPE(nsub - 1)).astype(jnp.int32)
    b = jnp.where(hit, pos_c * nsub + subid, k_cap * nsub)
    hist = jnp.zeros((k_cap * nsub + 1,), jnp.int32).at[b].add(1)
    return hist[: k_cap * nsub].reshape(k_cap, nsub)


def leaf_array_from_device_keys(
    keys_dev, bucket_size: int, base_level: int = 5, sub: int = 2,
    k_cap: int = 4096,
) -> np.ndarray:
    """Cornerstone leaf array (sorted start keys + KEY_MAX sentinel) built
    WITHOUT shipping the key array to the host.

    Top-down equivalent of compute_octree (csarray.hpp:456 invariant): a
    node splits while its count exceeds ``bucket_size`` (never creating a
    mergeable sibling set, so the result equals the converged rebalance,
    capped at the key resolution KEY_BITS). Counts come from one
    base-level histogram plus drill rounds over the overfull frontier.
    """
    base_level = min(base_level, KEY_BITS)
    hist = np.asarray(fetch(key_histogram(keys_dev, base_level)))
    # aggregate the pyramid upward (host, O(8^base) ints)
    pyramid = {base_level: hist.astype(np.int64)}
    for lvl in range(base_level - 1, -1, -1):
        pyramid[lvl] = pyramid[lvl + 1].reshape(-1, 8).sum(axis=1)

    leaves: list = []  # (cell_index, level)
    overfull = []      # frontier beyond the pyramid, all at base_level

    def split_through_pyramid(idx: int, lvl: int):
        stack = [(idx, lvl)]
        while stack:
            i, l = stack.pop()
            c = int(pyramid[l][i])
            if c <= bucket_size or l >= KEY_BITS:
                leaves.append((i, l))
            elif l < base_level:
                stack.extend((i * 8 + k, l + 1) for k in range(8))
            else:
                overfull.append(i)

    split_through_pyramid(0, 0)

    # drill rounds: refine every overfull cell ``sub`` levels at a time;
    # the fetched depth-``sub`` counts are aggregated back up so splitting
    # still happens one level at a time (a level+1 child under the bucket
    # must become ONE leaf, not 8 over-refined grandchildren)
    level = base_level
    pending = overfull
    while pending and level < KEY_BITS:
        step = min(sub, KEY_BITS - level)
        nsub = 1 << (3 * step)
        nxt = []
        for c0 in range(0, len(pending), k_cap):
            chunk = np.sort(np.asarray(pending[c0 : c0 + k_cap], np.int64))
            ids = np.full(k_cap, 2**30, np.int32)
            ids[: len(chunk)] = chunk.astype(np.int32)
            counts = np.asarray(
                fetch(drill_histogram(
                    keys_dev, jnp.asarray(ids), level, step, k_cap
                ))
            )
            for r, cell in enumerate(chunk):
                sums = [
                    counts[r].reshape(1 << (3 * d), -1).sum(axis=1)
                    for d in range(step + 1)
                ]
                stack = [(k, 1) for k in range(8)]  # cell is known overfull
                while stack:
                    i, d = stack.pop()
                    c = int(sums[d][i])
                    lvl = level + d
                    if c <= bucket_size or lvl >= KEY_BITS:
                        leaves.append((int(cell) * (1 << (3 * d)) + i, lvl))
                    elif d < step:
                        stack.extend((i * 8 + k, d + 1) for k in range(8))
                    else:
                        nxt.append(int(cell) * nsub + i)
        pending = nxt
        level += step

    key_of = lambda idx, lvl: np.uint64(idx) << np.uint64(3 * (KEY_BITS - lvl))
    starts = np.sort(np.asarray([key_of(i, l) for i, l in leaves], np.uint64))
    return np.concatenate([starts, [np.uint64(1) << np.uint64(3 * KEY_BITS)]])
