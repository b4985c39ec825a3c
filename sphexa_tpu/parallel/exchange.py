"""Halo exchange for the sharded Pallas fast path: sparse by cells (what
runs), or by one row window per peer (the fallback).

TPU-native transposition of the reference's halo subsystem
(cstone/halos/exchange_halos.hpp:43-119 pack-ranges -> p2p -> scatter,
discovery cstone/traversal/collisions.hpp:26-106). The reference sends
per-peer lists of octree-leaf row ranges. Here each shard runs the shared
group-window prologue on its OWN slab against the GLOBAL cell-starts
table (``global_cell_table``: an O(ncells) psum of per-shard histograms —
the update_mpi.hpp allreduce analog, no key gather), so its candidate
runs are global rows of the distributed array, and then one of two
stages turns them into rows of [own slab | annex]:

**Sparse, cell-granular** (``shard_halo_stage_sparse``; the default the
Simulation sizes, ``halo_mode="sparse"``, ``PropagatorConfig.halo_cells``):

1. discovery: mark the grid cells the shard's runs touch
   (``coverage_from_runs``). The prologue hands each run's first and last
   cell along with it (group_cell_ranges ``with_cells``), and the gravity
   near field the leaf each of its ranges was made from, so nothing is
   searched per run (``_cells_of_runs`` stays as what the tests compare
   the carried cells with);
2. negotiation: ONE all_gather of the (P, ncells) coverage bitmaps. The
   packed layout of every (dest, src) buffer is a pure function of a
   bitmap and the replicated table (``_sparse_layout``), so sender and
   receiver agree without exchanging an offset;
3. serve (``serve_sparse``, once per group of fields a pair op reads):
   P - 1 ``ppermute`` rounds, round r shipping each shard's packed rows to
   its distance-r SFC successor in a buffer of STATIC size hmax[r-1] —
   per-distance caps sized at reconfiguration
   (sizing.device_sparse_halo), so the volume tracks the halo surface;
4. ``localize_ranges_sparse`` rewrites the runs into j-buffer rows.

Steps 1 and 4 are index work per run SLOT (the split's sorts, the
coverage scatter-adds, the rewrite's lookups: a TPU scatter or gather
pays per index, live or dead), and the slots are mostly dead: the
prologue compacts a group's runs to the front of its W3 window slots
and ``_merge_runs`` leaves about seven of 125-729 live. So the run axis
is cut on entry (``cut_run_slots``) to ``run_slots``, the fullest
group's live runs as the same sizing pass observes them, padded
(``PropagatorConfig.halo_runs``; the near field: its leaves merged into
runs first, ``GravityConfig.p2p_run_cap``). A caller that sizes none
keeps the full width.

**Windowed** (``shard_halo_stage``; ``halo_mode="windowed"``, and the
full-slab fallback of the retry loop): per source shard ONE row window
[lo, hi) covering every run needed from it, an all_gather of the
(P, P, 2) bounds matrix (the exchange_keys.hpp negotiation analog), and
ONE all_to_all of fixed (P, Wmax, nf) buffers per serve. Comm volume
(P-1) * Wmax rows; at CI scale the windows span most of a slab, which is
why the sparse stage exists (docs/NEXT.md round 4).

Either way a run that escapes what was sized (particle drift since the
last sizing: a row cap, a window, or more live runs in a group than
``run_slots``) zeroes itself and trips the step's occupancy sentinel
(``fold_escape_sentinel``); the CALLER owns recovery — discard the step
and rebuild the sharded stepper with larger caps (tests/test_parallel.py
exercises both the sentinel and the resize), mirroring the neighbor-cap
overflow contract. What the stage costs on four chips is in PERF.md §5.
"""

from typing import NamedTuple, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.dtypes import KEY_BITS, KEY_DTYPE
from sphexa_tpu.sph.pallas_pairs import GroupRanges
from sphexa_tpu.util.phases import named_phase, stage_scope

# numpy, NOT jnp: this module is first imported INSIDE jitted stage
# functions, and a module-level jnp constant created under an active
# trace is a tracer — it leaks into later traces (UnexpectedTracerError
# in dryrun_multichip once the shard_map import shim let the pallas
# steps run). A numpy scalar weak-types identically in every jnp op.
INF32 = np.int32(2**30)


def _stage(stage: str):
    """Stage scope of this layer (util/phases.STAGES): the functions below
    serve the SPH halo and the gravity near field alike; the caller's
    first scope tells the two apart, the stage is the same word in both.
    ``wire`` goes round every collective and nothing else."""
    return stage_scope("halo-exchange", stage)


def cut_run_slots(ranges: GroupRanges, run_slots: int, cells=None):
    """The run-slot axis of ``ranges`` (and of their carried ``cells``) cut
    to its first ``run_slots`` slots: the prologue compacts a group's live
    runs to the FRONT of its W3 window slots and ``ncells`` counts them, so
    the cut is a slice, and every per-slot index op downstream (the split's
    sorts, the coverage scatters, the rewrite's lookups, the pair kernels'
    SMEM blocks) works on ``run_slots`` columns instead of W3. Returns
    (ranges, cells, over); ``over``: some group holds more live runs than
    the cut keeps — the caller folds it into ``escaped`` so that the step is
    discarded and re-sized, as for a row cap: no run is dropped silently.
    ``run_slots`` 0 (a caller that sized none) or >= the width: unchanged."""
    if not 0 < run_slots < ranges.starts.shape[1]:
        return ranges, cells, jnp.asarray(False)
    cut = lambda a: a[:, :run_slots]
    over = jnp.any(ranges.ncells > run_slots)
    ranges = ranges._replace(
        starts=cut(ranges.starts), lens=cut(ranges.lens),
        shift_x=cut(ranges.shift_x), shift_y=cut(ranges.shift_y),
        shift_z=cut(ranges.shift_z))
    if cells is not None:
        cells = tuple(cut(c) for c in cells)
    return ranges, cells, over


def estimate_halo_window(
    x, y, z, h, sorted_keys, box, nbr, P: int,
    margin: float = 1.4, quantum: int = 1024,
) -> int:
    """Size the static per-peer window Wmax from the current particle
    distribution (host-side, reconfiguration granularity — the halo
    discovery analog of estimate_cell_cap). Runs the shared prologue on
    the full arrays, clips candidate runs at slab boundaries, and returns
    the padded max over (dest, src) pairs of the needed row span.
    The in-step ``escaped`` guard remains the correctness backstop."""
    import numpy as np

    from sphexa_tpu.sph.pallas_pairs import group_cell_ranges

    n = x.shape[0]
    S = -(-n // P)
    ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, nbr)
    starts = np.asarray(ranges.starts)
    lens = np.asarray(ranges.lens)
    g = nbr.group
    wmax = 1
    for k in range(P):
        g0 = k * S // g
        g1 = min(((k + 1) * S + g - 1) // g, starts.shape[0])
        st = starts[g0:g1].ravel()
        ln = lens[g0:g1].ravel()
        st, ln = st[ln > 0], ln[ln > 0]
        for j in range(P):
            if j == k:
                continue
            lo_j, hi_j = j * S, (j + 1) * S
            ov = (st < hi_j) & (st + ln > lo_j)
            if not ov.any():
                continue
            a = int(np.maximum(st[ov], lo_j).min())
            b = int(np.minimum(st[ov] + ln[ov], hi_j).max())
            wmax = max(wmax, b - a)
    padded = int(-(-int(wmax * margin) // quantum) * quantum)
    return min(padded, S)


@named_phase("halo-exchange")
def global_cell_table(local_keys, level: int, axis: str) -> jax.Array:
    """Cell-starts table of the level-``level`` grid over the DISTRIBUTED
    key array: per-shard cid histogram -> psum -> exclusive cumsum.
    O(ncells) comm; replicated result (update_mpi.hpp:26-106 role)."""
    shift = KEY_DTYPE(3 * (KEY_BITS - level))
    ncells = (1 << level) ** 3
    with _stage("table"):
        cid = (local_keys >> shift).astype(jnp.int32)
        hist = jnp.zeros(ncells, jnp.int32).at[cid].add(1)
    with _stage("wire"):
        hist = jax.lax.psum(hist, axis)
    with _stage("table"):
        return jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(hist)]
        ).astype(jnp.int32)


def _split_runs(starts, lens, payloads, S: int, extra: int = 8,
                rem_payloads=None):
    """Split candidate runs that cross shard-slab boundaries.

    A run's rows must come from ONE source shard so it maps into one
    annex window. Crossing runs (a window cell or merged run straddling
    a multiple of S — at most P-1 cells globally) are clipped at the
    boundary and the remainder pieces are appended as fresh runs;
    everything is re-compacted front-first. Returns (starts, lens,
    payloads, nruns, overflow) with ``extra`` more slots per group.

    ``payloads``: per-run arrays that ride the split (the three image
    shifts; the sparse stage adds each run's first cell). A remainder
    piece inherits its run's values, or those of ``rem_payloads`` where
    it differs from the head piece (its first cell does).

    ``extra`` must scale with the mesh: one group can need up to P-1
    crossing remainders (callers pass max(8, P-1) — growing the halo
    window can never fix slot exhaustion, so under-sizing here would
    make the escape-sentinel retry loop diverge).
    """
    ng, w3 = starts.shape
    src0 = starts // S
    src1 = jnp.where(lens > 0, (starts + lens - 1) // S, src0)
    cross = (src1 > src0) & (lens > 0)
    len1 = jnp.where(cross, (src0 + 1) * S - starts, lens)
    # remainder pieces (zero-length when no crossing)
    r_start = jnp.where(cross, (src0 + 1) * S, 0)
    r_len = jnp.where(cross, lens - len1, 0)
    # a remainder could itself cross (run longer than a whole slab):
    # flagged as overflow — Wmax resizing cannot fix it, the caller must
    # reduce run_cap below S (config error, not drift)
    r_cross = jnp.any((r_len > 0) & ((r_start + r_len - 1) // S > r_start // S))

    # compact the remainders of each group into `extra` slots
    order = jnp.argsort(~(r_len > 0), axis=1, stable=True)[:, :extra]
    take = lambda a: jnp.take_along_axis(a, order, axis=1)
    e_start, e_len = take(r_start), take(r_len)
    overflow = jnp.sum(r_len > 0, axis=1) > extra

    starts = jnp.concatenate([starts, e_start], axis=1)
    lens = jnp.concatenate([jnp.where(cross, len1, lens), e_len], axis=1)
    payloads = [
        jnp.concatenate([a, take(r)], axis=1)
        for a, r in zip(payloads, rem_payloads or payloads)
    ]

    # re-compact: active runs to the front (stable keeps SFC order)
    active = lens > 0
    _, act_i, starts, lens, *payloads = jax.lax.sort(
        ((~active).astype(jnp.int32), active.astype(jnp.int32),
         starts, lens, *payloads),
        num_keys=1, dimension=1, is_stable=True,
    )
    lens = jnp.where(act_i.astype(bool), lens, 0)
    starts = jnp.where(act_i.astype(bool), starts, 0)
    nruns = jnp.sum(active, axis=1).astype(jnp.int32)
    return starts, lens, tuple(payloads), nruns, jnp.any(overflow) | r_cross


def _split_runs_cells(ranges: GroupRanges, table, S: int, P: int, c0=None):
    """``_split_runs`` of the sparse stage: also names the first cell of
    every piece. Returns (starts, lens, shifts3, nruns, overflow, c0).

    ``c0``: the unsplit runs' carried first cells. A head piece keeps its
    run's; a remainder piece starts at row (src0 + 1) * S, and the cells
    holding the P - 1 slab boundaries are all there is to search for.
    ``None``: search the table once per piece (_cells_of_runs)."""
    sh3 = (ranges.shift_x, ranges.shift_y, ranges.shift_z)
    extra = max(8, P - 1)
    if c0 is None:
        starts, lens, sh3, nruns, ovf = _split_runs(
            ranges.starts, ranges.lens, sh3, S, extra=extra)
        return (starts, lens, sh3, nruns, ovf,
                _cells_of_runs(starts, lens, table)[0])
    bcell = _cells_of_rows(
        jnp.arange(1, max(P, 2), dtype=jnp.int32) * S, table)
    rem_c0 = _select(jnp.clip(ranges.starts // S, 0, bcell.shape[0] - 1),
                     bcell)
    starts, lens, (*sh3, c0), nruns, ovf = _split_runs(
        ranges.starts, ranges.lens, sh3 + (c0,), S, extra=extra,
        rem_payloads=sh3 + (rem_c0,))
    return starts, lens, tuple(sh3), nruns, ovf, c0


def _select(idx, values):
    """``values[idx]`` for a table of a few entries (one per shard or per
    distance), as a chain of selects: a TPU gather pays per INDEX whatever
    the table holds (PERF.md PR 30), an elementwise select does not.
    ``values``: a sequence of static ints or a small 1-D array; an index
    outside it reads 0."""
    out = jnp.zeros_like(idx)
    for i in range(len(values)):
        out = jnp.where(idx == i, values[i], out)
    return out


def window_bounds(starts, lens, S: int, P: int, k, axis: str):
    """Per-source-shard row windows needed by THIS shard, then the
    all_gathered (P_dest, P_src, 2) bounds matrix (halo negotiation)."""
    active = lens > 0
    src = jnp.clip(starts // S, 0, P - 1)
    ends = starts + lens
    lo = jnp.full(P, INF32, jnp.int32)
    hi = jnp.zeros(P, jnp.int32)
    lo = lo.at[src].min(jnp.where(active, starts, INF32))
    hi = hi.at[src].max(jnp.where(active, ends, 0))
    # own slab is served locally, not through the annex
    lo = lo.at[k].set(INF32)
    hi = hi.at[k].set(0)
    mine = jnp.stack([lo, hi], axis=1)  # (P, 2)
    with _stage("wire"):
        bounds_all = jax.lax.all_gather(mine, axis)  # (P, P, 2)
    return mine, bounds_all


def _effective_lo(bounds_all, S: int, Wmax: int, P: int):
    """Deterministic serve offsets: clamp each window's lo into its
    source slab so a fixed Wmax slice stays in range. Sender and
    receiver evaluate the SAME formula on the replicated bounds."""
    lo = bounds_all[:, :, 0]  # (P_dest, P_src)
    srcs = jnp.arange(P, dtype=jnp.int32)[None, :]
    return jnp.clip(lo, srcs * S, (srcs + 1) * S - Wmax)


@named_phase("halo-exchange")
def serve_windows(fields: Sequence, bounds_all, S: int, Wmax: int,
                  P: int, k, axis: str):
    """One all_to_all exchange round: this shard serves every
    destination's window out of its slab; returns the annex — (P, Wmax)
    per field, row (j, i) holding global row lo_eff[k, j] + i."""
    with _stage("pack"):
        lo_eff = _effective_lo(bounds_all, S, Wmax, P)  # (P_dest, P_src)
        local = jnp.stack(fields, axis=1)  # (S, nf)
        nf = local.shape[1]

        def serve_one(dest):
            off = lo_eff[dest, k] - k * S
            return jax.lax.dynamic_slice(local, (off, 0), (Wmax, nf))

        send = jax.vmap(serve_one)(
            jnp.arange(P, dtype=jnp.int32))  # (P, Wmax, nf)
    with _stage("wire"):
        annex = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
    with _stage("jbuf"):
        annex = annex.reshape(P * Wmax, nf)
        return [annex[:, f] for f in range(nf)]


def shard_halo_stage(x, y, z, h, keys, box, nbr, P: int, Wmax: int,
                     axis: str, run_slots: int = 0):
    """Shared prologue of a sharded pair-op stage: global table ->
    group windows on the local slab -> localized runs + serve/jbuf
    closures. One implementation for every sharded force stage so the
    overflow contract cannot diverge between pipelines.

    The 5th element is the per-shard telemetry dict (see
    ``exchange_metrics_windowed``) — cheap in-graph scalars the driver
    fetches at its existing flush boundary (schema-v2 ``exchange``
    events); computing them here keeps the measured quantities
    definitionally identical to what the exchange actually ships."""
    from sphexa_tpu.sph.pallas_pairs import group_cell_ranges

    S = x.shape[0]
    k = jax.lax.axis_index(axis)
    table = global_cell_table(keys, nbr.level, axis)
    granges = group_cell_ranges(x, y, z, h, None, box, nbr, table=table)
    ranges, bounds, escaped = localize_ranges(granges, S, P, Wmax, k, axis,
                                              run_slots=run_slots)

    def serve(fields):
        return serve_windows(fields, bounds, S, Wmax, P, k, axis)

    metrics = exchange_metrics_windowed(bounds, Wmax, P, k)
    metrics["halo_runs"] = live_runs_max(granges)
    return ranges, serve, _jbuf, escaped, metrics


@named_phase("shard-metrics")
def live_runs_max(ranges: GroupRanges):
    """This shard's high-water of live runs a group (or near-field block),
    of the runs as the exchange RECEIVES them, before ``cut_run_slots``:
    the ``live_runs_max`` of the ``exchange`` event, what the sized
    ``run_slots`` is held against."""
    return jnp.max(ranges.ncells).astype(jnp.int32)


@named_phase("shard-metrics")
def exchange_metrics_windowed(bounds_all, Wmax: int, P: int, k):
    """Per-shard comm telemetry of the windowed exchange, from the
    already-negotiated (P_dest, P_src, 2) bounds matrix: ``halo_rows`` =
    this shard's true need (sum of its per-source window spans — the
    windowed path SHIPS (P-1) * Wmax regardless), ``halo_occ`` = the
    fullest window's span / Wmax (1.0 = the static window is exactly
    consumed; drift past it trips the escape sentinel)."""
    mine = bounds_all[k]  # (P_src, 2) — own row is [INF32, 0]
    span = jnp.maximum(mine[:, 1] - jnp.minimum(mine[:, 0], mine[:, 1]), 0)
    rows = jnp.sum(span).astype(jnp.int32)
    occ = (jnp.max(span).astype(jnp.float32)
           / jnp.float32(max(Wmax, 1)))
    return {"halo_rows": rows, "halo_occ": occ}


def fold_escape_sentinel(occ, escaped, cap: int, axis: str):
    """Escaped runs mean truncated candidates: encode as an occupancy
    overflow against the CALLER's cap so the driver re-sizes the halo
    window (the shared overflow contract of every sharded stage)."""
    occ = jnp.where(escaped, jnp.int32(cap + 1), occ)
    return jax.lax.pmax(occ, axis)


def _cells_of_rows(rows, table):
    """Index of the cell holding each global row: the last cell that
    starts at or before it (empty cells share their successor's start and
    sit below it). One binary search of the table per row."""
    c = jnp.searchsorted(table, rows, side="right").astype(jnp.int32) - 1
    return jnp.clip(c, 0, table.shape[0] - 2)


def _cells_of_runs(starts, lens, table):
    """First/last cell index of every run: runs are unions of consecutive
    cells of the level grid, so [c0, c1] brackets exactly the run's rows.
    Dead runs (len 0) return a harmless [c0, c0].

    Two searches PER RUN SLOT, for runs whose cells are not known. No
    step program has such runs: group_cell_ranges' carry their cells
    (``with_cells``; searching for those was 477 of the four-chip Sedov
    step's 1468 ms, PERF.md PR 24) and the gravity near field's carry
    their leaf (PR 29). What tests/test_halo_cells.py holds the carried
    cells to."""
    ends = jnp.where(lens > 0, starts + lens - 1, starts)
    return _cells_of_rows(starts, table), _cells_of_rows(ends, table)


def coverage_from_runs(starts, lens, table, cells=None) -> jax.Array:
    """(ncells,) bool: cells whose rows any ACTIVE candidate run touches —
    this shard's halo NEED at cell granularity (the collision-detection
    product of the reference's halo discovery, collisions.hpp:26-106,
    transposed to the replicated level grid). Interval-marked with one
    +1/-1 scatter + cumsum; gap-bridged cells inside a merged run are
    covered too (their rows ride the run's DMA window).

    ``cells``: the runs' carried ``(c0, c1)`` (group_cell_ranges
    ``with_cells``); searched for in the table when None."""
    c0, c1 = _cells_of_runs(starts, lens, table) if cells is None else cells
    active = (lens > 0).astype(jnp.int32)
    ncells = table.shape[0] - 1
    diff = jnp.zeros(ncells + 1, jnp.int32)
    diff = diff.at[c0.ravel()].add(active.ravel())
    diff = diff.at[c1.ravel() + 1].add(-active.ravel())
    return jnp.cumsum(diff)[:ncells] > 0


def _sparse_layout(covered, table, S: int, P: int):
    """Packed-annex layout for ONE destination's coverage bitmap: per
    source shard j, the rows of every covered cell clipped to j's slab,
    packed in ascending cell order. Sender and receiver evaluate this
    SAME pure function of (covered, table) — the negotiation is one
    all_gathered bitmap, no offset exchange.

    Returns (clen, poff, need): (P, ncells) clipped lens and exclusive
    packed offsets, (P,) total rows per source."""
    t0 = table[:-1][None, :]  # (1, ncells) cell row starts
    t1 = table[1:][None, :]
    slab = jnp.arange(P, dtype=jnp.int32)[:, None] * S  # (P, 1)
    lo = jnp.clip(t0, slab, slab + S)
    hi = jnp.clip(t1, slab, slab + S)
    clen = jnp.where(covered[None, :], hi - lo, 0)  # (P, ncells)
    csum = jnp.cumsum(clen, axis=1)
    return clen, csum - clen, csum[:, -1]


def _pack_rows(clen_j, poff_j, table, S: int, k, Hmax: int):
    """Local row indices (Hmax,) materializing one (dest <- this shard)
    packed buffer: position i holds local row ridx[i] of the i-th
    requested row (ascending cell order). Tail positions past the total
    repeat row 0 — never referenced by any localized run."""
    sel = clen_j > 0
    clip_lo = jnp.maximum(table[:-1], k * S) - k * S  # local row of cell
    # off[c] = clip_lo - poff: ridx[i] = i + off[cell containing i]
    off = jnp.where(sel, clip_lo - poff_j, 0)
    ncells = off.shape[0]
    cidx = jnp.arange(ncells, dtype=jnp.int32)
    INF = jnp.int32(2**30)
    _, off_c = jax.lax.sort(
        (jnp.where(sel, cidx, INF), off), num_keys=1, dimension=0,
        is_stable=True,
    )  # selected cells' offsets compacted to the front, cell order kept
    # segment id per packed position (scatter heads at distinct poff)
    heads = jnp.zeros(Hmax, jnp.int32).at[
        jnp.where(sel, poff_j, Hmax)  # OOB drops (also guards overflow)
    ].add(1)
    seg = jnp.cumsum(heads) - 1
    i = jnp.arange(Hmax, dtype=jnp.int32)
    total = jnp.sum(clen_j)
    ridx = i + off_c[jnp.clip(seg, 0, ncells - 1)]
    return jnp.where((i < total) & (seg >= 0), ridx, 0)


def chain_after(x, dep):
    """Pin a (false) data dependency of ``x`` on ``dep`` via
    ``optimization_barrier`` — the collective-serialization primitive of
    the sparse exchange. XLA:CPU's rendezvous can pair the WRONG
    collectives when two of them become runnable concurrently and the
    per-device thread pools reach them in different orders (this
    container's jax 0.4.x; the cross-routing class the CPU-mesh drain in
    Simulation._drain guards against BETWEEN programs, here WITHIN one).
    Chaining every sparse-path collective onto its predecessor pins one
    total order on every device. Free on real TPU meshes: collectives
    there execute in program order anyway."""
    return jax.lax.optimization_barrier((x, dep))[0]


@named_phase("halo-exchange")
def serve_sparse(fields: Sequence, covered_all, table, S: int,
                 hmax: Tuple[int, ...], P: int, k, axis: str,
                 token=None):
    """Sparse halo serve: P-1 ppermute rounds, round r shipping each
    shard's packed rows to its distance-r SFC successor in a buffer of
    STATIC size hmax[r-1] — per-distance sizing is what lets the comm
    volume track the true halo surface (neighbor slabs carry ~the
    surface, distant slabs only the odd Hilbert-wrap cell) instead of a
    single max window degenerating to the whole slab
    (exchange_halos.hpp:43-119 sends exact per-peer ranges the same way).
    Returns (annex fields, token): annex rows [src at distance 1 |
    distance 2 | ...] per field — row order matches
    localize_ranges_sparse's packed offsets. ``token``: optional value
    from the PREVIOUS serve; the rounds chain on it (and on each other)
    through ``chain_after`` so the P-1 independent ppermutes execute in
    one total order on every device (rendezvous-race guard)."""
    with _stage("pack"):
        local = jnp.stack(fields, axis=1)  # (S, nf)
    nf = local.shape[1]
    parts = []
    for r in range(1, P):
        with _stage("pack"):
            dest = (k + r) % P
            clen, poff = _sparse_layout_dest(covered_all, dest, table, S, k)
            ridx = _pack_rows(clen, poff, table, S, k, hmax[r - 1])
            send = local[ridx]  # (Hmax_r, nf)
            if token is not None:
                send = chain_after(send, token)
        perm = [(i, (i + r) % P) for i in range(P)]
        with _stage("wire"):
            parts.append(jax.lax.ppermute(send, axis, perm))
        token = parts[-1]
    with _stage("jbuf"):
        annex = jnp.concatenate(parts, axis=0) if parts else local[:0]
        return [annex[:, f] for f in range(nf)], token


def _sparse_layout_dest(covered_all, dest, table, S: int, k):
    """One (dest <- this shard k) column of the packed layout: clen/poff
    of dest's covered cells clipped to k's slab. poff is an exclusive
    cumsum per (dest, src) pair independently, so the src = k column
    needs only dest's bitmap — sender and receiver evaluate the same
    formula without materializing the (P, P, ncells) cube."""
    covered = jax.lax.dynamic_index_in_dim(
        covered_all, dest, axis=0, keepdims=False
    )  # (ncells,)
    t0, t1 = table[:-1], table[1:]
    lo = jnp.clip(t0, k * S, (k + 1) * S)
    hi = jnp.clip(t1, k * S, (k + 1) * S)
    clen = jnp.where(covered, hi - lo, 0)
    csum = jnp.cumsum(clen)
    return clen, csum - clen


class FrozenHalo(NamedTuple):
    """The send layout a slab froze with its persistent pair lists
    (sph/pair_lists.PairLists.halo): what ``serve_frozen`` ships every
    steady step without coverage, cell table or packing arithmetic. Built
    by ``freeze_send_layout`` under the rebuild's ``shard_map``; each
    leaf is a slab's own (global arrays: the slabs' concatenation)."""

    send: Tuple[jax.Array, ...]  # P-1 x (hmax[r-1],) int32: the slab's
    #                              rows round r ships to its distance-r
    #                              successor (``_pack_rows``' ``ridx``)
    rows: jax.Array   # (1,) int32 - remote rows this slab needed at the
    #                   build (``exchange_metrics_sparse``' halo_rows)
    occ: jax.Array    # (1,) f32 - fullest round's need / cap at the build
    runs: jax.Array   # (1,) int32 - fullest group's live runs at the build


def freeze_send_layout(covered_all, table, S: int, hmax: Tuple[int, ...],
                       P: int, k) -> Tuple[jax.Array, ...]:
    """The packed row indices of every serve round of THIS negotiation
    (``serve_sparse`` computes the same ``ridx`` per round and serve): a
    pure function of the all_gathered coverage and the replicated table,
    so what the receivers' localized runs index stays where it is for as
    long as the slabs keep their rows."""
    with _stage("pack"):
        out = []
        for r in range(1, P):
            clen, poff = _sparse_layout_dest(covered_all, (k + r) % P, table,
                                             S, k)
            out.append(_pack_rows(clen, poff, table, S, k, hmax[r - 1]))
        return tuple(out)


@named_phase("halo-exchange")
def serve_frozen(fields: Sequence, send: Tuple[jax.Array, ...], P: int,
                 axis: str, token=None):
    """``serve_sparse`` over a frozen layout: the same P-1 chained ppermute
    rounds in the same buffers, each round's rows one gather of the
    stacked fields by the frozen index. No coverage, no table, no packing
    arithmetic. Returns (annex fields, token) like ``serve_sparse``."""
    with _stage("pack"):
        local = jnp.stack(fields, axis=1)  # (S, nf)
    nf = local.shape[1]
    parts = []
    for r in range(1, P):
        with _stage("pack"):
            out = local[send[r - 1]]  # (Hmax_r, nf)
            if token is not None:
                out = chain_after(out, token)
        perm = [(i, (i + r) % P) for i in range(P)]
        with _stage("wire"):
            parts.append(jax.lax.ppermute(out, axis, perm))
        token = parts[-1]
    with _stage("jbuf"):
        annex = jnp.concatenate(parts, axis=0) if parts else local[:0]
        return [annex[:, f] for f in range(nf)], token


def frozen_halo_stage(halo: FrozenHalo, P: int, axis: str):
    """A steady list step's stand-in for ``shard_halo_stage_sparse``:
    ``(serve, jbuf, metrics)`` over the frozen layout. Nothing is
    negotiated: the rows go where the list's runs index them, the rounds
    of every serve chained into one total order across calls
    (``chain_after``), and the exchange metrics are the build's."""
    chain = {"token": None}

    def serve(fields):
        out, tok = serve_frozen(fields, halo.send, P, axis,
                                token=chain["token"])
        chain["token"] = tok
        return out

    metrics = {"halo_rows": halo.rows[0], "halo_occ": halo.occ[0],
               "halo_runs": halo.runs[0]}
    return serve, _jbuf, metrics


def _jbuf(own, halo):
    """[own slab | annex] of each field: the j-buffer a slab's localized
    runs index."""
    return tuple(jnp.concatenate([o, a]) for o, a in zip(own, halo))


@named_phase("halo-exchange")
def localize_ranges_sparse(
    ranges: GroupRanges, table, S: int, P: int, hmax: Tuple[int, ...],
    k, axis: str, cells=None, run_slots: int = 0,
) -> Tuple[GroupRanges, jax.Array, jax.Array, jax.Array]:
    """Sparse analog of ``localize_ranges``: rewrite global-row runs into
    j-buffer rows [own slab (S) | packed annex (sum(hmax))] using the
    cell-granular packed layout. Also computes and all_gathers this
    shard's coverage bitmap (the negotiation). Returns (localized
    ranges, covered_all (P, ncells), escaped, coverage bitmap).

    ``cells``: the ``(c0, c1)`` the prologue carried for ``ranges``
    (group_cell_ranges ``with_cells``). With them nothing is searched per
    run: coverage is marked from the unsplit runs (a run covers the same
    cells cut or whole — except an EMPTY cell lying exactly on the slab
    boundary that cuts it, which holds no rows and so has no entry in
    any layout), a head piece keeps its run's first cell, and a
    remainder piece starts at a slab boundary, whose cell is one of
    P - 1. ``None`` searches the table for the split pieces' cells
    (_cells_of_runs) and returns the same ranges, escapes and layouts.

    ``run_slots``: the sized high-water of live runs a group
    (sizing.device_sparse_halo; ``cut_run_slots``). The split, the
    coverage and the rewrite below, and the ranges handed to the pair
    kernels, are ``run_slots + max(8, P - 1)`` slots wide instead of the
    window's W3 + that; a group with more live runs trips ``escaped``.
    """
    if len(hmax) != P - 1:
        raise ValueError(f"hmax needs P-1={P-1} per-distance caps, got "
                         f"{len(hmax)}")
    with _stage("localize"):
        ranges, cells, slots_ovf = cut_run_slots(ranges, run_slots, cells)
        starts, lens, sh3, nruns, split_ovf, c0 = _split_runs_cells(
            ranges, table, S, P, c0=None if cells is None else cells[0])
    with _stage("cover"):
        if cells is None:
            covered = coverage_from_runs(starts, lens, table)
        else:
            covered = coverage_from_runs(ranges.starts, ranges.lens, table,
                                         cells)
    with _stage("wire"):
        covered_all = jax.lax.all_gather(covered, axis)  # (P, ncells)
    with _stage("localize"):
        out, escaped = _localize_sparse(
            ranges, starts, lens, sh3, nruns, split_ovf | slots_ovf, c0,
            covered, table, S, P, hmax, k)
    return out, covered_all, escaped, covered


def _localize_sparse(ranges, starts, lens, sh3, nruns, split_ovf, c0,
                     covered, table, S: int, P: int, hmax, k):
    """The rewrite of ``localize_ranges_sparse``: the split runs into rows
    of [own slab | packed annex] under this shard's own coverage, and the
    escape flag. Returns (localized ranges, escaped)."""
    clen, poff, need = _sparse_layout(covered, table, S, P)  # per src j
    # static per-distance caps: need from src j rides round (k - j) % P
    caps = (0,) + tuple(hmax)  # index by r
    src_j = jnp.arange(P, dtype=jnp.int32)
    r_of_j = (k - src_j) % P
    over = (need > jnp.asarray(caps, jnp.int32)[r_of_j]) & (src_j != k)
    escaped = jnp.any(over) | split_ovf

    # annex offset of distance r: S + sum of previous rounds' caps
    prefix = (0,) + tuple(int(v) for v in np.cumsum(hmax))  # [r-1]: of r

    active = lens > 0
    src = jnp.clip(starts // S, 0, P - 1)
    own = src == k
    # packed row of a run = poff[src, c0] + starts - max(table[c0], src*S):
    # everything but ``starts`` is a function of (src, c0), so ONE lookup
    # per slot into the (P * ncells,) table of that difference (a TPU
    # gather pays per index: two tables by the same index pay twice)
    ncells = table.shape[0] - 1
    run_off = (poff - jnp.maximum(table[None, :-1], src_j[:, None] * S))
    packed = run_off.reshape(-1)[src * ncells + c0] + starts
    r_run = (k - src) % P
    cap_run = _select(r_run, caps)
    # a run past its round's cap would index outside the annex: zero it
    # (escaped already tripped above via need > cap, so the step is
    # discarded and re-sized — same contract as the windowed path)
    in_cap = own | (packed + lens <= cap_run)
    local = jnp.where(
        own, starts - k * S,
        S + _select(jnp.clip(r_run - 1, 0, P - 1), prefix) + packed,
    )
    lens = jnp.where(active & in_cap, lens, 0)
    local = jnp.where(lens > 0, local, 0)

    out = GroupRanges(
        starts=local, lens=lens,
        shift_x=sh3[0], shift_y=sh3[1], shift_z=sh3[2],
        ncells=nruns, occupancy=ranges.occupancy, boxl=ranges.boxl,
    )
    return out, escaped


def shard_halo_stage_sparse(x, y, z, h, keys, box, nbr, P: int,
                            hmax: Tuple[int, ...], axis: str,
                            run_slots: int = 0, radius_pad=0.0,
                            freeze: bool = False):
    """Sparse-exchange variant of ``shard_halo_stage`` — same contract
    (ranges, serve, jbuf, escaped, metrics), comm volume sum(hmax) rows
    per serve instead of (P-1) * Wmax. The reference analog is
    exchangeHalos' per-peer leaf-range p2p (exchange_halos.hpp:43-119);
    here the range lists are implicit in the all_gathered coverage
    bitmaps + the replicated cell table, so the negotiation is
    O(P * ncells) bits. ``run_slots``: ``localize_ranges_sparse``.

    ``radius_pad``: group_cell_ranges' coverage slack, the pair lists'
    skin: windows, coverage and localized runs are then the inflated
    ones. ``freeze`` (static; the pair-list rebuild): a sixth value, the
    ``FrozenHalo`` of this negotiation, which ``frozen_halo_stage``
    serves the steady steps from."""
    from sphexa_tpu.sph.pallas_pairs import group_cell_ranges

    S = x.shape[0]
    k = jax.lax.axis_index(axis)
    table = global_cell_table(keys, nbr.level, axis)
    granges, cells = group_cell_ranges(x, y, z, h, None, box, nbr,
                                       table=table, radius_pad=radius_pad,
                                       with_cells=True)
    ranges, covered_all, escaped, covered = localize_ranges_sparse(
        granges, table, S, P, hmax, k, axis, cells=cells,
        run_slots=run_slots,
    )

    # one total order over EVERY collective this stage issues, carried
    # across serve calls: the chain seed is the negotiation all_gather's
    # output, each serve's ppermute rounds link on their predecessor
    # (chain_after — the XLA:CPU rendezvous-race guard)
    chain = {"token": covered_all}

    def serve(fields):
        out, tok = serve_sparse(fields, covered_all, table, S, hmax, P,
                                k, axis, token=chain["token"])
        chain["token"] = tok
        return out

    metrics = exchange_metrics_sparse(covered, table, S, hmax, P, k)
    metrics["halo_runs"] = live_runs_max(granges)
    if freeze:
        halo = FrozenHalo(
            send=freeze_send_layout(covered_all, table, S, hmax, P, k),
            rows=metrics["halo_rows"][None], occ=metrics["halo_occ"][None],
            runs=metrics["halo_runs"][None])
        return ranges, serve, _jbuf, escaped, metrics, halo
    return ranges, serve, _jbuf, escaped, metrics


@named_phase("shard-metrics")
def exchange_metrics_sparse(covered, table, S: int,
                            hmax: Tuple[int, ...], P: int, k):
    """Per-shard comm telemetry of the sparse exchange, from this
    shard's own coverage bitmap (the Bédorf-2014 LET comm-volume
    accounting, PAPERS.md): ``halo_rows`` = the true remote rows this
    shard needs (sum over sources of its covered cells clipped to their
    slabs — the exchange SHIPS the static sum(hmax) regardless),
    ``halo_occ`` = the fullest per-distance buffer's need / cap (1.0
    means the sized cap is exactly consumed; beyond it the escape
    sentinel discards the step)."""
    _, _, need = _sparse_layout(covered, table, S, P)  # (P_src,)
    src_j = jnp.arange(P, dtype=jnp.int32)
    own = src_j == k
    rows = jnp.sum(jnp.where(own, 0, need)).astype(jnp.int32)
    hmax_arr = jnp.asarray((1,) + tuple(hmax), jnp.int32)  # index by r
    caps = hmax_arr[(k - src_j) % P].astype(jnp.float32)
    occ = jnp.max(jnp.where(own, 0.0, need.astype(jnp.float32) / caps))
    return {"halo_rows": rows, "halo_occ": occ}


@named_phase("halo-exchange")
def localize_ranges(
    ranges: GroupRanges, S: int, P: int, Wmax: int, k, axis: str,
    run_slots: int = 0,
) -> Tuple[GroupRanges, jax.Array, jax.Array]:
    """Rewrite a GLOBAL-row GroupRanges into j-buffer rows
    [own slab (S) | annex (P * Wmax)]. Returns (localized ranges,
    all_gathered (P, P, 2) bounds matrix, escaped flag).

    Runs outside their source's served window (drift since the last
    Wmax sizing) zero out and flip ``escaped``, which the caller folds
    into the occupancy sentinel. ``run_slots``: as
    ``localize_ranges_sparse``'s.
    """
    with _stage("localize"):
        ranges, _, slots_ovf = cut_run_slots(ranges, run_slots)
        starts, lens, sh3, nruns, split_ovf = _split_runs(
            ranges.starts, ranges.lens,
            (ranges.shift_x, ranges.shift_y, ranges.shift_z), S,
            extra=max(8, P - 1),
        )
    with _stage("cover"):
        mine, bounds_all = window_bounds(starts, lens, S, P, k, axis)
    with _stage("localize"):
        out, escaped = _localize_windows(
            ranges, starts, lens, sh3, nruns, split_ovf | slots_ovf,
            bounds_all, S, P, Wmax, k)
    return out, bounds_all, escaped


def _localize_windows(ranges, starts, lens, sh3, nruns, split_ovf,
                      bounds_all, S: int, P: int, Wmax: int, k):
    """The rewrite of ``localize_ranges``: the split runs into rows of
    [own slab | annex], and the escape flag."""
    lo_eff = _effective_lo(bounds_all, S, Wmax, P)[k]  # (P_src,)

    src = jnp.clip(starts // S, 0, P - 1)
    own = src == k
    lo_run = lo_eff[src]
    in_window = own | (
        (starts >= lo_run) & (starts + lens <= lo_run + Wmax)
    )
    active = lens > 0
    escaped = jnp.any(active & ~in_window) | split_ovf

    local = jnp.where(
        own, starts - k * S, S + src * Wmax + (starts - lo_run)
    )
    lens = jnp.where(active & in_window, lens, 0)
    local = jnp.where(lens > 0, local, 0)

    out = GroupRanges(
        starts=local, lens=lens,
        shift_x=sh3[0], shift_y=sh3[1], shift_z=sh3[2],
        ncells=nruns, occupancy=ranges.occupancy, boxl=ranges.boxl,
    )
    return out, escaped
