"""Persistent neighbor lists for the Pallas pair engine.

The streaming engine (sph/pallas_pairs.py) processes ~3500 candidate
lanes per target against ~110 true neighbors — measured AT the
architectural floor of cell-run streaming (chunk quantization c ~ 5 dx
is irreducible for any particle ordering; docs/NEXT.md floor analysis).
Persistent lists break that floor by LANE COMPACTION: a cheap Mosaic
"mark" pass records, for every (target group, 128-lane candidate chunk),
which lanes fall inside the group's skin-inflated bounding box, as a
compacted per-chunk gather-index vector. The list-walk engine variant
then compacts each DMA'd chunk with an in-register lane gather
(``take_along_axis`` along lanes), merges compacted lanes into a dense
staging window with a dynamic ``pltpu.roll``, and runs the pair math only
on FULL 128-lane staging chunks — the per-target lane count drops to the
exact inflated-bbox occupancy (~(G^(1/3) + 4h/dx + skin/dx)^3, ~2.5x
fewer VPU ops than the streamed floor).

Lists persist across steps (the Verlet-list idea, re-shaped for TPU tile
granularity): they are rebuilt only when accumulated drift or smoothing-
length growth exhausts the skin — and between rebuilds the step skips
the global SFC sort AND the candidate-range prologue entirely (the
sorted order is frozen; positions drift in place). Validity is a cheap
O(N) reduction checked in-step; an invalid step is discarded and
replayed after a rebuild, exactly like a neighbor-cap overflow.

Storage: the per-chunk gather indices live in ONE flat table, a row per
chunk that KEEPS a lane, each group's rows contiguous from an 8-row tile
boundary, sized by the SUM over groups (``estimate_list_caps``). Most
candidate chunks keep nothing (Noh 1.1M: a group streams ~110 and keeps
~40, its fullest 200), so a dense (groups, slot_cap, 128) table was 82-90 %
dead slots that every build pass and every byte of the list paid for. The
small per-slot scalars (cnt, fill, emit) stay dense in SMEM.

Role-wise this replaces the reference's per-step neighbor rebuild
(cstone/traversal/find_neighbors.cuh rebuilds warp-local lists every
step — cheap on GPU SIMT, wasteful on TPU where the equivalent is the
full streaming pass).
"""

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sphexa_tpu.neighbors.cell_list import NeighborConfig
from sphexa_tpu.sfc.box import Box
from sphexa_tpu.sph.pallas_pairs import (
    LIST_ROW_TILE,
    GroupRanges,
    list_run_rows,
    _dma_rows,
    _prep_i,
    _round_up,
    engine_fold,
    group_cell_ranges,
    pack_j_fields,
)


class PairLists(NamedTuple):
    """Build-time candidate structure shared by every list-walk pair op."""

    ranges: GroupRanges   # candidate runs at build time (skin-inflated),
    #                       pruned to the chunks that keep a lane
    gidx: jax.Array       # (slots_cap + window, 128) int32 — the FLAT lane
    #                       table, one row per KEPT chunk, each group's rows
    #                       contiguous from row seg * LIST_ROW_TILE:
    #                       compacted lane gather indices, PRE-ROTATED by
    #                       the staging fill (lanes [fill, fill+cnt) mod 256
    #                       carry the selected source lanes). Sized by the
    #                       SUM over groups; the tail pad is one walk-kernel
    #                       window (slot_cap rows), so the last group's
    #                       fetch stays inside the table
    seg: jax.Array        # (NG,) int32 — a group's first row, in row tiles
    cnt: jax.Array        # (NG, S_cap) int32 — selected lanes per chunk
    fill: jax.Array       # (NG, S_cap) int32 — staging fill before chunk
    emit: jax.Array       # (NG, S_cap) int32 0/1 — chunk completes a full
    #                       128-lane staging chunk
    tail: jax.Array       # (NG,) int32 — flush lanes after the last chunk
    overflow: jax.Array   # () int32 — 1 if any group needed > S_cap slots
    #                       or the groups together > slots_cap rows
    slot_need: jax.Array  # () int32 — most chunk slots any group needed
    #                       (the rebuild's event reports it beside the cap)
    slots_live: jax.Array  # () int32 — table rows the kept chunks needed,
    #                       each group's rounded up to the LIST_ROW_TILE
    chunks_live: jax.Array  # () int32 — kept chunks = chunk visits a pass
    runs_live: jax.Array  # () int32 — runs = tiles of ``list_run_rows``
    #                       rows a pass fetches; chunks_live over
    #                       (runs_live x rows) is the share of the fetched
    #                       rows a lane is taken from
    lanes_total: jax.Array  # () int64-ish f32 — sum of cnt (diagnostics)
    xb: jax.Array         # build positions + smoothing lengths: the
    yb: jax.Array         # validity reduction compares current state
    zb: jax.Array         # against these (Verlet skin condition)
    hb: jax.Array
    skin: jax.Array       # () f32 — the coverage slack baked into ranges
    halo: object = None   # a mesh slab's lists: the send layout frozen with
    #                       them (parallel/exchange.FrozenHalo); the runs
    #                       above then index [own rows | served halo rows]

    @property
    def slot_cap(self) -> int:
        return self.cnt.shape[1]

    @property
    def slots_cap(self) -> int:
        return self.gidx.shape[0] - _round_up(self.slot_cap, LIST_ROW_TILE)


def list_slack(x, y, z, h, lists: PairLists):
    """Remaining skin fraction in [-inf, 1]: positive = the build-time
    candidate coverage (bbox inflated by 2*h_build + skin) still covers
    every current 2h_i sphere, which holds while
    2*(max h-growth + max drift) <= skin.

    Drift is measured UNFOLDED: a particle wrapping the periodic box
    shows a ~L jump and correctly forces a rebuild (its build-time image
    shift no longer resolves its pairs). The host watches the slack to
    rebuild PROACTIVELY before a step would have to be discarded."""
    dx = x - lists.xb
    dy = y - lists.yb
    dz = z - lists.zb
    d2 = dx * dx + dy * dy + dz * dz
    drift = jnp.sqrt(jnp.max(d2))
    growth = jnp.maximum(jnp.max(h - lists.hb), 0.0)
    used = 2.0 * (growth + drift)
    return (lists.skin - used) / jnp.maximum(lists.skin, 1e-30)


def lists_valid(x, y, z, h, lists: PairLists):
    """Verlet-skin validity (see list_slack). The boundary (zero used
    skin, e.g. right after a rebuild with list_skin_rel=0) is VALID."""
    return list_slack(x, y, z, h, lists) >= 0.0


def _stream_marks(common, jref, buf, sems, on_chunk):
    """Shared body of the two mark kernels: stream one group's candidate
    runs with a minimal body (inflated-bbox lane test) and hand every
    chunk's lane mask to ``on_chunk(slot, mask)``; returns the number of
    chunks streamed. ``common``: the refs of ``_mark_specs``; a run's
    copy is the rows of a ``buf`` slot (the widest run the pass streams)."""
    (starts, lens, shx_r, shy_r, shz_r, ncells, skin_s,
     xi_r, yi_r, zi_r, hi_r) = common
    R = buf.shape[1]
    nc_g = ncells[0, 0, 0]

    def dma(w, slot):
        row_s = starts[0, 0, w] // 128
        return pltpu.make_async_copy(
            jref.at[pl.ds(row_s, R), :, :],
            buf.at[slot], sems.at[slot],
        )

    @pl.when(nc_g > 0)
    def _():
        dma(0, 0).start()

    xi = xi_r[0, 0][:, None]
    yi = yi_r[0, 0][:, None]
    zi = zi_r[0, 0][:, None]
    hi = hi_r[0, 0][:, None]
    # group bbox inflated by the build search radius (2*max h + skin):
    # the EXACT volume the walk engine's compacted lanes cover
    r = 2.0 * jnp.max(hi) + skin_s[0, 0, 0]
    glo_x, ghi_x = jnp.min(xi) - r, jnp.max(xi) + r
    glo_y, ghi_y = jnp.min(yi) - r, jnp.max(yi) + r
    glo_z, ghi_z = jnp.min(zi) - r, jnp.max(zi) + r
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    def cell_body(w, slot_base):
        slot = w % 2

        @pl.when(w + 1 < nc_g)
        def _():
            dma(w + 1, 1 - slot).start()

        dma(w, slot).wait()
        s = starts[0, 0, w]
        ln = lens[0, 0, w]
        shx = shx_r[0, 0, w]
        shy = shy_r[0, 0, w]
        shz = shz_r[0, 0, w]
        row0 = s // 128
        off = s - row0 * 128
        nch = (off + ln + 127) // 128

        def chunk_body(t, _c):
            part = buf[slot, t]  # (8, 128): rows 0-2 = x, y, z
            jx = part[0][None, :] + shx
            jy = part[1][None, :] + shy
            jz = part[2][None, :] + shz
            cand = (row0 + t) * 128 + lane
            mask = (
                (cand >= s) & (cand < s + ln)
                & (jx >= glo_x) & (jx <= ghi_x)
                & (jy >= glo_y) & (jy <= ghi_y)
                & (jz >= glo_z) & (jz <= ghi_z)
            )
            on_chunk(slot_base + t, mask)
            return _c

        jax.lax.fori_loop(0, nch, chunk_body, 0)
        return slot_base + nch

    return jax.lax.fori_loop(0, nc_g, cell_body, 0)


def _smem_spec(shape):
    return pl.BlockSpec(shape, lambda g: (g, 0, 0),
                        memory_space=pltpu.SMEM)


def _mark_specs(cfg: NeighborConfig, ranges: GroupRanges, i_fields,
                skin, run_rows: int):
    """The two mark kernels' common inputs, the first ``len(args)`` of
    each: (in_specs, args), and the scratch both stream through, a copy
    of ``run_rows`` rows a run."""
    num_groups = ranges.num_groups
    w3 = ranges.starts.shape[1]
    G = cfg.group
    smem3 = lambda a: a.reshape(num_groups, 1, w3)
    in_specs = (
        [_smem_spec((1, 1, w3)) for _ in range(5)]  # starts, lens, shifts
        + [
            _smem_spec((1, 1, 1)),   # ncells
            pl.BlockSpec((1, 1, 1), lambda g: (0, 0, 0),
                         memory_space=pltpu.SMEM),  # skin
        ]
        + [
            pl.BlockSpec((1, 1, G), lambda g: (g, 0, 0))
            for _ in range(4)   # x, y, z, h
        ]
    )
    args = (
        smem3(ranges.starts), smem3(ranges.lens),
        smem3(ranges.shift_x), smem3(ranges.shift_y),
        smem3(ranges.shift_z),
        ranges.ncells.reshape(num_groups, 1, 1),
        jnp.asarray(skin, jnp.float32).reshape(1, 1, 1),
        *[a.reshape(num_groups, 1, G) for a in i_fields],
    )
    scratch = [
        pltpu.VMEM((2, run_rows, 8, 128), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    return in_specs, args, scratch


def _count_marks(cfg: NeighborConfig, slot_cap: int, interpret: bool,
                 ranges: GroupRanges, i_fields, j_packed, skin):
    """Mosaic count pass over the CANDIDATE runs: marked lanes of every
    chunk, (NG, slot_cap) int32, and every group's chunk total. The lane
    masks stay in VMEM, a row per chunk; what leaves is their row sums,
    taken for the whole group at once and laid along lanes by one
    transposed-operand matmul (ones . masks^T; 0/1 operands, exact at
    any MXU precision), so no value crosses from the vector to the
    scalar unit per chunk."""
    spad = _round_up(slot_cap, 128)
    num_groups = ranges.num_groups
    in_specs, args, scratch = _mark_specs(cfg, ranges, i_fields, skin,
                                          _dma_rows(cfg.dma_cap))
    ncommon = len(args)

    def kernel(*refs):
        jref, cnt_out, total_out, buf, sems, marks = refs[ncommon:]

        def on_chunk(slot_i, mask):
            @pl.when(slot_i < slot_cap)
            def _():
                marks[pl.ds(slot_i, 1), :] = mask.astype(jnp.float32)

        # dead slots must read as empty
        marks[...] = jnp.zeros((spad, 128), jnp.float32)
        total_out[0, 0, 0] = _stream_marks(
            refs[:ncommon], jref, buf, sems, on_chunk)
        sums = jax.lax.dot_general(
            jnp.ones((8, 128), jnp.float32), marks[...],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (8, spad), rows alike
        cnt_out[0] = sums[:1].astype(jnp.int32)

    cnt, total = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(num_groups,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, 1, spad), lambda g: (g, 0, 0)),
                _smem_spec((1, 1, 1)),
            ],
            scratch_shapes=scratch + [pltpu.VMEM((spad, 128), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((num_groups, 1, spad), jnp.int32),
            jax.ShapeDtypeStruct((num_groups, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args, j_packed)
    return cnt[:, 0, :slot_cap], total.reshape(-1)


def _mark_rows(cfg: NeighborConfig, slot_cap: int, rows: int,
               interpret: bool, ranges: GroupRanges, i_fields, j_packed,
               skin, fill, seg, ntile):
    """Mosaic mark pass over the PRUNED runs (every chunk of them keeps a
    lane; a run is a tile of ``list_run_rows`` rows, and so is its copy
    here): write each kept chunk's lane BITS, with its staging fill in
    the bits above, to row ``seg * LIST_ROW_TILE + k`` of the flat
    ``(rows, 128)`` table. A group's rows are staged in VMEM and leave
    in ``ntile`` sublane-tile DMAs (an output BLOCK per group would
    overlap its neighbours' segments); rows no group owns stay zero.
    Compaction indices and pre-rotation are batched XLA post-passes."""
    swin = _round_up(slot_cap, LIST_ROW_TILE)
    num_groups = ranges.num_groups
    in_specs, args, scratch = _mark_specs(cfg, ranges, i_fields, skin,
                                          list_run_rows(cfg))
    ncommon = len(args)

    def kernel(*refs):
        # (`tab` aliases `_zeros`, which the kernel does not touch)
        (fill_r, seg_r, ntile_r, _zeros, jref, tab,
         buf, sems, stage, osem) = refs[ncommon:]

        def on_chunk(slot_i, mask):
            @pl.when(slot_i < slot_cap)
            def _():
                stage[pl.ds(slot_i, 1), :] = (
                    mask.astype(jnp.int32) + 2 * fill_r[0, 0, slot_i])

        # rows past the kept count inside the last tile must read empty
        stage[...] = jnp.zeros((swin, 128), jnp.int32)
        _stream_marks(refs[:ncommon], jref, buf, sems, on_chunk)
        seg_g = seg_r[0, 0, 0]
        nt = ntile_r[0, 0, 0]

        def tile(i):
            src = pl.multiple_of(i * LIST_ROW_TILE, LIST_ROW_TILE)
            dst = pl.multiple_of((seg_g + i) * LIST_ROW_TILE, LIST_ROW_TILE)
            return pltpu.make_async_copy(
                stage.at[pl.ds(src, LIST_ROW_TILE), :],
                tab.at[pl.ds(dst, LIST_ROW_TILE), :], osem)

        def start(i, c):
            tile(i).start()
            return c

        def wait(i, c):
            tile(i).wait()
            return c

        jax.lax.fori_loop(0, nt, start, 0)
        jax.lax.fori_loop(0, nt, wait, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(num_groups,),
            in_specs=in_specs + [
                _smem_spec((1, 1, slot_cap)),  # fill
                _smem_spec((1, 1, 1)),         # seg
                _smem_spec((1, 1, 1)),         # ntile
                pl.BlockSpec(memory_space=pl.ANY),  # zero table
                pl.BlockSpec(memory_space=pl.ANY),  # j_packed
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch + [
                pltpu.VMEM((swin, 128), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        input_output_aliases={ncommon + 3: 0},
        interpret=interpret,
    )(*args, fill.reshape(num_groups, 1, slot_cap),
      seg.reshape(num_groups, 1, 1), ntile.reshape(num_groups, 1, 1),
      jnp.zeros((rows, 128), jnp.int32), j_packed)


def _run_chunks(starts, lens):
    """128-lane chunks each candidate run streams (0 for an empty slot)."""
    return jnp.where(lens > 0, (starts % 128 + lens + 127) // 128, 0)


def _prune_empty_chunks(ranges: GroupRanges, cnt, slot_cap: int,
                        run_rows: int):
    """Rebuild the candidate runs to exclude chunks with NO marked lane:
    every engine pass then neither DMAs nor iterates them (the measured
    per-chunk base cost is ~115 ns even when the math is skipped).

    New runs are consecutive kept-chunk intervals WITHIN one original
    run, cut again at every ``run_rows``-th chunk (``list_run_rows``:
    the list kernels fetch a run as one tile of that many rows, so a run
    never streams more), with exact particle bounds (the intersection of
    the original [s, s+len) with the piece's rows) — never merged across
    original runs, so the in-run candidate mask admits exactly the
    original run's particles and no cross-run double counting can occur.
    Dropped chunks had no lane inside any group's inflated bbox, so no
    pair is lost; the cut moves no chunk, so the chunk sequence and
    everything indexed by it (cnt, fill, emit, tail, the lane table) are
    what the un-cut prune gives. A piece holds a kept chunk, so a group
    has at most as many runs as kept chunks (<= slot_cap, the run axis
    of the new ranges). Returns (new_ranges, cnt) with the kept slots' counts
    compacted to the front and zeros behind them (the compacted chunk
    sequence preserves original order, so staging fills computed on the
    zero-preserving cumsum are unchanged).
    """
    starts, lens = ranges.starts, ranges.lens
    ng, w3 = starts.shape
    s_idx = jnp.arange(slot_cap, dtype=jnp.int32)

    # slot -> (run w, chunk c, row, shift, exact bounds). A slot lies in
    # exactly one run (none past the group's total, where nothing below
    # is read), so a run's value reaches its slots as a masked sum over
    # the runs: one fused compare-select-reduce per array, exact for the
    # f32 shifts (x + 0). Not take_along_axis: XLA's minor-axis gather
    # took 49-97 ms for each of these at 17k groups x 296 slots, 485 of a
    # rebuild's 900 ms (PERF.md, PR 28).
    nch_w = _run_chunks(starts, lens)                          # (NG, W3)
    cum_w = jnp.cumsum(nch_w, axis=1) - nch_w                  # exclusive
    s3 = s_idx[None, :, None]
    in_run = ((cum_w[:, None, :] <= s3)
              & (s3 < (cum_w + nch_w)[:, None, :]))  # (NG, S_cap, W3)
    take = lambda a: jnp.sum(
        jnp.where(in_run, a[:, None, :], jnp.zeros((), a.dtype)), axis=2)
    s_w = take(starts)
    ln_w = take(lens)
    c_of_s = s_idx[None, :] - take(cum_w)
    row_s = s_w // 128 + c_of_s
    lo_s = jnp.maximum(s_w, row_s * 128)
    hi_s = jnp.minimum(s_w + ln_w, (row_s + 1) * 128)
    total = jnp.sum(nch_w, axis=1)  # (NG,)

    kept = (cnt > 0) & (s_idx[None, :] < total[:, None])
    kept_prev = jnp.concatenate(
        [jnp.zeros((ng, 1), bool), kept[:, :-1]], axis=1
    )
    head = kept & ((c_of_s == 0) | ~kept_prev)
    # ... and a tile's worth of chunks further on inside an interval
    since = s_idx[None, :] - jax.lax.cummax(
        jnp.where(head, s_idx[None, :], -1), axis=1)
    head = kept & (head | (since % run_rows == 0))

    # run end = hi of the last consecutive kept slot (reverse scan, the
    # _merge_runs pattern)
    end_eff = jnp.where(kept, hi_s, -1)
    head_next = jnp.concatenate(
        [head[:, 1:], jnp.ones((ng, 1), bool)], axis=1
    )

    def rstep(carry, inp):
        e_w, hn_w = inp
        r = jnp.maximum(e_w, jnp.where(hn_w, jnp.int32(-1), carry))
        return r, r

    xs_r = (end_eff[:, ::-1].T, head_next[:, ::-1].T)
    _, r_t = jax.lax.scan(rstep, jnp.full_like(end_eff[:, 0], -1), xs_r)
    run_end = r_t.T[:, ::-1]

    shx_s = take(ranges.shift_x)
    shy_s = take(ranges.shift_y)
    shz_s = take(ranges.shift_z)
    INF = jnp.int32(2**30)
    _, hk_i, hs_r, hlen, cshx, cshy, cshz = jax.lax.sort(
        (jnp.where(head, s_idx[None, :], INF), head.astype(jnp.int32),
         lo_s, run_end - lo_s, shx_s, shy_s, shz_s),
        num_keys=1, dimension=1, is_stable=True,
    )
    hk = hk_i.astype(bool)
    new_ranges = GroupRanges(
        starts=jnp.where(hk, hs_r, 0),
        lens=jnp.where(hk, hlen, 0),
        shift_x=jnp.where(hk, cshx, 0.0),
        shift_y=jnp.where(hk, cshy, 0.0),
        shift_z=jnp.where(hk, cshz, 0.0),
        ncells=jnp.sum(head, axis=1).astype(jnp.int32),
        occupancy=ranges.occupancy,
        boxl=ranges.boxl,
    )
    # kept slots' counts compacted to the front, original order preserved
    # (the sort carries them: gathering them by the sorted slot indices
    # afterwards was 49 ms more of XLA's minor-axis gather)
    _, cnt = jax.lax.sort(
        (jnp.where(kept, s_idx[None, :], INF), jnp.where(kept, cnt, 0)),
        num_keys=1, dimension=1, is_stable=True,
    )
    return new_ranges, cnt


def _table_segments(cnt, slots_cap: int):
    """Where each group's rows lie in the flat table: its kept chunks
    (compacted to the front of ``cnt``, so they are the slots with a
    count) take whole LIST_ROW_TILEs from the exclusive cumsum of the
    groups before it. Returns ``(seg, ntile, slots_live)``: first row in
    tiles, tiles to write, rows needed in all. Past the budget a group
    writes what still fits and fetches from the pad (nothing out of
    bounds) and the caller's sentinel discards the build."""
    cap_tiles = slots_cap // LIST_ROW_TILE
    tiles = (jnp.sum((cnt > 0).astype(jnp.int32), axis=1)
             + LIST_ROW_TILE - 1) // LIST_ROW_TILE
    seg = jnp.cumsum(tiles) - tiles
    slots_live = (seg[-1] + tiles[-1]) * LIST_ROW_TILE
    return (jnp.minimum(seg, cap_tiles),
            jnp.clip(cap_tiles - seg, 0, tiles),
            slots_live.astype(jnp.int32))


#: rows the rotation post-pass takes at a time; the flat table's row
#: budget is a whole number of them
LIST_TABLE_TILE = 8192


def _rotation_rows(rows, live):
    """Flat mark rows (lane bits + 2 * staging fill) -> PRE-ROTATED
    compaction indices, in place, LIST_TABLE_TILE rows at a time over
    the ``live`` rows only (the rest of the budget is never read and
    stays zero; the transient is a tile, not a second table). Per tile
    ONE batched 128-wide sort: lane l's destination slot is (fill +
    rank-among-selected) % 128 when marked, and the remaining slots (in
    wrap order) when not — all 128 keys are distinct, so sorting (dst,
    lane) scatters each lane to its exact slot. This folds the staging
    rotation into the sort: both a minor-axis take_along_axis here
    (measured 6.4 s at 1M — XLA's pathological gather) and a per-chunk
    pltpu.roll in the walk kernel (measured 90 ns/chunk) disappear."""
    T = LIST_TABLE_TILE
    lane = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (T, 128))

    def tile(i, tab):
        blk = jax.lax.dynamic_slice(tab, (i * T, 0), (T, 128))
        bits = blk & 1
        fill = blk[:, :1] >> 1
        rank1 = jnp.cumsum(bits, axis=1) - bits   # rank among selected
        rank0 = lane - rank1                      # rank among unselected
        cnt = jnp.sum(bits, axis=1, keepdims=True)
        dst = jnp.where(bits > 0, fill + rank1, fill + cnt + rank0) % 128
        _, rot = jax.lax.sort((dst, lane), num_keys=1, dimension=1)
        return jax.lax.dynamic_update_slice(tab, rot, (i * T, 0))

    ntile = jnp.minimum((live + T - 1) // T, rows.shape[0] // T)
    return jax.lax.fori_loop(0, ntile, tile, rows)


def build_pair_lists(
    x, y, z, h, sorted_keys, box: Box, cfg: NeighborConfig,
    skin, slot_cap: int, slots_cap: int, interpret: bool = False,
    table=None, ranges=None, jdata=None,
) -> PairLists:
    """Build the persistent lists from SFC-SORTED arrays (jit-safe).

    ``ranges`` + ``jdata`` (a mesh slab, under ``shard_map``): the i-side
    is the slab's own rows, ``jdata`` the ``(x, y, z)`` of its j-buffer
    [own | served halo rows] and ``ranges`` its candidate runs localized
    into that buffer, from a halo stage run with ``radius_pad = skin``
    (parallel/exchange.shard_halo_stage_sparse). Without them the j-side
    is the i-side and the runs are found here: one device.

    ``skin`` (traced f32) is the coverage slack; ``slot_cap`` the static
    per-group chunk-slot budget and ``slots_cap`` the static row budget
    of the flat lane table, taken up to a whole LIST_TABLE_TILE (both
    sized at configure time, guarded by the ``overflow`` sentinel like
    every other static cap). Nothing of shape
    (groups, slot_cap, 128) exists at any point: a count pass over the
    candidate runs, the prune, then a mark pass over the pruned runs
    that writes kept rows only."""
    if engine_fold(box, cfg):
        raise ValueError(
            "persistent lists need per-cell image shifts; the tiny-grid "
            "fold mode streams instead (lists are a large-N optimization)")
    if ranges is None:
        ranges = group_cell_ranges(
            x, y, z, h, sorted_keys, box, cfg, table=table, radius_pad=skin,
        )
    i_fields = _prep_i(x, y, z, h, (), cfg.group)
    jp = pack_j_fields(jdata or (x, y, z), cfg.dma_cap)
    cnt, total = _count_marks(cfg, slot_cap, interpret, ranges, i_fields,
                              jp, skin)

    # drop empty chunks from the runs (the engines then neither DMA nor
    # iterate them) and compact the per-slot counts to the new order
    ranges, cnt = _prune_empty_chunks(ranges, cnt, slot_cap,
                                      list_run_rows(cfg))

    # staging bookkeeping, precomputed so the walk kernel carries no
    # sequential fill state: fill before chunk s = (exclusive cumsum of
    # cnt) mod 128; a chunk emits a full staging chunk iff fill+cnt >= 128
    # (cnt <= 128 crosses at most one boundary per chunk)
    csum = jnp.cumsum(cnt, axis=1)
    excl = csum - cnt
    fill = excl % 128
    emit = ((fill + cnt) >= 128).astype(jnp.int32)
    tail = csum[:, -1] % 128
    slot_need = jnp.max(total).astype(jnp.int32)

    slots_cap = _round_up(slots_cap, LIST_TABLE_TILE)
    seg, ntile, slots_live = _table_segments(cnt, slots_cap)
    overflow = (slot_need > slot_cap) | (slots_live > slots_cap)

    rows = _mark_rows(
        cfg, slot_cap, slots_cap + _round_up(slot_cap, LIST_ROW_TILE),
        interpret, ranges, i_fields, jp, skin, fill, seg, ntile)
    gidx = _rotation_rows(rows, jnp.minimum(slots_live, slots_cap))
    return PairLists(
        ranges=ranges, gidx=gidx, seg=seg,
        cnt=cnt, fill=fill, emit=emit,
        tail=tail, overflow=overflow.astype(jnp.int32),
        slot_need=slot_need, slots_live=slots_live,
        chunks_live=jnp.sum((cnt > 0).astype(jnp.int32)),
        runs_live=jnp.sum(ranges.ncells),
        lanes_total=jnp.sum(csum[:, -1].astype(jnp.float32)),
        xb=x, yb=y, zb=z, hb=h,
        skin=jnp.asarray(skin, jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _slot_need(x, y, z, h, sorted_keys, box, cfg, skin):
    ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg,
                               radius_pad=skin)
    per_group = jnp.sum(_run_chunks(ranges.starts, ranges.lens), axis=1)
    return jnp.max(per_group), jnp.sum(_round_up(per_group, LIST_ROW_TILE))


def estimate_list_caps(
    x, y, z, h, sorted_keys, box: Box, cfg: NeighborConfig, skin: float,
    margin: float = 1.3, quantum: int = 8,
) -> tuple:
    """Host-side sizing of the two static list budgets from the current
    (SFC-sorted) distribution — configure-time, like cell caps; the
    build-time ``overflow`` sentinel guards outgrowth. Returns
    ``(slot_cap, slots_cap)``: chunk slots per group from the MOST
    candidate chunks any group streams, rows of the flat lane table from
    their SUM over groups. The sum bounds the kept chunks from above at
    every build (a kept chunk is a candidate chunk), and unlike the kept
    count it hardly moves as the particles leave the grid's alignment."""
    from sphexa_tpu.neighbors.cell_list import pad_cap

    need, total = (int(v) for v in _slot_need(
        x, y, z, h, sorted_keys, box, cfg, jnp.float32(skin)))
    return (pad_cap(need, margin, quantum),
            pad_cap(total, margin, LIST_TABLE_TILE))
