"""Pallas TPU engine for SPH pair interactions: stream candidate cells
through VMEM per target group.

TPU-native re-design of the hot j-loops following the reference's GPU
strategy (cstone/traversal/find_neighbors.cuh: 64-particle warp targets,
neighbors found on the fly inside each kernel, no stored lists) mapped to
the TPU memory system:

- targets are groups of G = 128 SFC-consecutive particles (one VMEM block);
- the group's candidate cells are found in a jax-side prologue
  (``group_cell_ranges``): the static ``window^3`` block of grid cells
  covering the group's search extent is CULLED by exact cell-AABB vs
  group-bbox distance and COMPACTED, so the kernel loops over only the
  ~dozen cells that can actually contain neighbors (the analog of the
  reference's per-warp tree traversal pruning, find_neighbors.cuh:45-82);
- every surviving cell's particles are CONTIGUOUS in the SFC-sorted
  arrays, and all the op's j-side fields are pre-packed into ONE
  interleaved (rows, nfields, 128) HBM buffer, so each cell is ONE
  dynamic-slice DMA into a VMEM ring buffer regardless of how many fields
  the op consumes — no XLA gathers anywhere, no per-field DMA storms;
- the pair physics runs chunk-by-chunk on (G, 128) tiles on the VPU while
  the next cell's DMA is in flight (double buffering); the number of
  128-wide chunks per cell is dynamic (ceil(len/128)), so padded cap
  slack costs no FLOPs;
- periodic images are handled by a per-cell precomputed shift (each
  window cell corresponds to exactly one box image), replacing per-pair
  minimum-image folds;
- each op instantiates the shared engine with its own per-pair math and
  accumulators, fusing neighbor search INTO the op (the reference GPU
  does exactly this, SURVEY.md §2 'neighbors recomputed on the fly').

The XLA gather-based path (neighbors/cell_list.py + the ops' j-loops)
remains the portable fallback; this engine is used on TPU where the
gather rate, not FLOPs, limits throughput.
"""

import functools
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sphexa_tpu.dtypes import KEY_BITS, KEY_DTYPE
from sphexa_tpu.neighbors.cell_list import NeighborConfig, _window_offsets
from sphexa_tpu.sfc.box import BoundaryType, Box
from sphexa_tpu.util.device import on_tpu
from sphexa_tpu.util.phases import named_phase, stage_scope
from sphexa_tpu.sfc.hilbert import hilbert_encode
from sphexa_tpu.sfc.morton import morton_encode
from sphexa_tpu.sph.kernels import (
    dterh_poly_eval,
    kernel_dterh_coeffs,
    kernel_poly_coeffs,
    sinc_poly_eval,
)

GROUP = 128  # default targets per group (NeighborConfig.group overrides)


class PairGeom(NamedTuple):
    """Per-(target, candidate) geometry handed to the pair body."""

    rx: jax.Array     # (G, 128) x_i - x_j, image-resolved
    ry: jax.Array
    rz: jax.Array
    d2: jax.Array     # squared distance
    mask: jax.Array   # valid pair: in-range candidate, within 2h_i, not self


class GroupRanges(NamedTuple):
    """Compacted candidate-cell lists of every target group (the engine's
    shared prologue output; one per step, consumed by all pair ops)."""

    starts: jax.Array     # (NG, W3) int32 — sorted-array offset of cell w
    lens: jax.Array       # (NG, W3) int32 — particles in cell w (<= cap)
    shift_x: jax.Array    # (NG, W3) f32 — periodic image offset of cell w
    shift_y: jax.Array
    shift_z: jax.Array
    ncells: jax.Array     # (NG,) int32 — cells surviving the cull
    occupancy: jax.Array  # () int32 — cap/window overflow diagnostic
    boxl: jax.Array       # (3,) f32 — fold periods (1e30 on open dims);
    # consumed only when the engine runs in fold mode (see engine_fold)

    @property
    def num_groups(self) -> int:
        return self.starts.shape[0]


def engine_fold(box: Box, cfg: NeighborConfig) -> bool:
    """Static choice of the kernel's periodic-image strategy.

    Per-cell shifts are exact when every needed cell *instance* fits in
    the window (guaranteed by the window_ok guard whenever
    window < ncell). When the window spans the whole grid — the tiny-grid
    escape hatch where window_ok is forced true — a single instance per
    wrapped cell cannot represent both images a target may need, so the
    kernel must fall back to the per-pair minimum-image fold (and the
    prologue must not distance-cull cells, since the kept instance's AABB
    says nothing about its other image)."""
    any_periodic = any(b == BoundaryType.periodic for b in box.boundaries)
    return any_periodic and cfg.window >= (1 << cfg.level)


@named_phase("neighbors")
def group_cell_ranges(
    x, y, z, h, sorted_keys, box: Box, cfg: NeighborConfig,
    table=None, radius_pad=0.0, with_cells: bool = False,
):
    """Candidate cells of every group, culled and compacted.

    Vectorized over all groups (the jax-side prologue all pair ops
    share). A window cell survives when it (a) exists (periodic images
    de-aliased, open-boundary cells inside the grid), (b) is non-empty,
    and (c) its AABB intersects the group's bbox inflated by the group's
    search radius 2*max(h). Survivors are compacted to the front so the
    kernel's cell loop trips only ``ncells`` times. ``occupancy`` encodes
    the cap AND window guards exactly like find_neighbors.

    ``table``: optional externally built cell-starts table of the
    level-``cfg.level`` grid, (ncell^3 + 1,) int32 of sorted-array
    offsets. Under shard_map the table is GLOBAL (psum of per-shard cid
    histograms, parallel/exchange.py) while x/y/z/h are the local slab:
    the returned ranges are then global rows of the distributed array.
    When given, ``sorted_keys`` may be None (the deep-grid searchsorted
    fallback needs keys and is unavailable).

    ``with_cells`` (static; set by the callers that map runs back onto
    the cell grid — the sparse halo stage and its sizing — never a user
    option): also return ``(c0, c1)``, (NG, W3) int32 grid-cell indices of
    each compacted run's first and last kept cell, 0 on dead slots. The
    cell index is in hand when the table is read, and rides the sorts and
    scans below as payload, so nobody has to search the table for it
    again (exchange._cells_of_runs). Returns ``(GroupRanges, (c0, c1))``
    then, a plain GroupRanges otherwise: without it the prologue lowers
    exactly as it did before the payloads existed.
    """
    n = x.shape[0]
    level = cfg.level
    shift = KEY_DTYPE(3 * (KEY_BITS - level))
    ncell = 1 << level
    encode = hilbert_encode if cfg.curve == "hilbert" else morton_encode
    edge = box.lengths / ncell
    periodic = box.periodic_mask

    with stage_scope("neighbors", "windows"):
        g = cfg.group
        num_groups = -(-n // g)
        pad = num_groups * g - n
        gather_pad = lambda a: jnp.concatenate([a, jnp.broadcast_to(a[-1:], (pad,))]) if pad else a
        xg = gather_pad(x).reshape(num_groups, g)
        yg = gather_pad(y).reshape(num_groups, g)
        zg = gather_pad(z).reshape(num_groups, g)
        hg = gather_pad(h).reshape(num_groups, g)

        lo = jnp.stack([xg.min(1), yg.min(1), zg.min(1)], axis=1)  # (NG, 3)
        hi = jnp.stack([xg.max(1), yg.max(1), zg.max(1)], axis=1)
        # radius_pad: extra coverage slack (the list-build skin) so candidate
        # runs stay valid while particles drift between list rebuilds
        radius = 2.0 * hg.max(1) + radius_pad  # (NG,)
        box_lo = jnp.stack([box.lo[0], box.lo[1], box.lo[2]])
        base = jnp.floor((lo - radius[:, None] - box_lo) / edge).astype(jnp.int32)
        need = jnp.floor((hi + radius[:, None] - box_lo) / edge).astype(jnp.int32)
        # open dims: cells outside [0, ncell) don't exist — slide the window
        # inside the grid (never loses coverage); a window spanning the whole
        # grid always covers
        base = jnp.where(
            periodic[None, :], base,
            jnp.clip(base, 0, max(0, ncell - cfg.window)),
        )
        need_eff = jnp.where(periodic[None, :], need, jnp.minimum(need, ncell - 1))
        window_ok = jnp.all((need_eff - base + 1 <= cfg.window) | (cfg.window >= ncell))

        offsets = jnp.asarray(_window_offsets(cfg.window))  # (W3, 3)
        cells = base[:, None, :] + offsets[None, :, :]  # (NG, W3, 3) unwrapped
        in_range = (cells >= 0) & (cells < ncell)
        unique = offsets[None, :, :] < ncell
        cell_ok = jnp.all(
            jnp.where(periodic[None, None, :], unique, in_range), axis=-1
        )  # (NG, W3)

        use_table = table is not None or ncell**3 <= 4 * max(n, 1024)
        if not use_table:
            lookup = jnp.where(
                periodic[None, None, :], jnp.mod(cells, ncell),
                jnp.clip(cells, 0, ncell - 1),
            )
            ckey = encode(
                lookup[..., 0].astype(KEY_DTYPE),
                lookup[..., 1].astype(KEY_DTYPE),
                lookup[..., 2].astype(KEY_DTYPE),
                bits=level,
            )
    with stage_scope("neighbors", "cell-ranges"):
        if use_table:
            # ONE cell-starts table for the whole grid (a binary search per
            # window cell into the N-element key array dominated the
            # prologue), read per group as blocks of its grid-ordered copy
            # (_window_cell_ranges)
            if table is None:
                cid = (sorted_keys >> shift).astype(jnp.int32)  # ascending
                table = jnp.searchsorted(
                    cid, jnp.arange(ncell**3 + 1, dtype=jnp.int32)
                ).astype(jnp.int32)
            # slots that cell_ok masks read whatever the block holds there
            # (a wrapped copy, or the empty pad past an open edge); nothing
            # downstream reads them: both compactions and ``occupancy``
            # select on ``keep``
            start, raw_len, cell = _window_cell_ranges(
                table, base, level, cfg.window, encode,
                tuple(b == BoundaryType.periodic for b in box.boundaries),
                with_cells,
            )
        else:
            # deep grids (possible when a caller bypasses the occupancy-driven
            # level heuristic): the table would be O(8^level) — search instead
            start = jnp.searchsorted(sorted_keys, ckey << shift).astype(jnp.int32)
            end = jnp.searchsorted(
                sorted_keys, (ckey + KEY_DTYPE(1)) << shift
            ).astype(jnp.int32)
            raw_len = end - start
            cell = ckey.astype(jnp.int32) if with_cells else None
        lens = jnp.where(cell_ok, jnp.minimum(raw_len, cfg.cap), 0)

        if engine_fold(box, cfg):
            # tiny-grid fallback: the kernel min-image-folds every pair, so
            # image-position culling is meaningless — keep all non-empty cells
            keep = cell_ok & (lens > 0)
            shifts = jnp.zeros(cells.shape, jnp.float32)
        else:
            # cull: drop cells whose AABB (at their image position) cannot
            # contain any neighbor of the group — exact box-vs-box distance
            # test against the group bbox inflated by its search radius
            cell_lo = (
                box_lo[None, None, :] + cells.astype(jnp.float32) * edge[None, None, :]
            )
            cell_hi = cell_lo + edge[None, None, :]
            r = radius[:, None, None]
            overlap = jnp.all(
                (cell_hi >= lo[:, None, :] - r) & (cell_lo <= hi[:, None, :] + r),
                axis=-1,
            )  # (NG, W3)
            keep = cell_ok & overlap & (lens > 0)

            # each window cell corresponds to exactly ONE box image: its offset
            # resolves periodicity for every pair in the cell (no per-pair fold)
            img = jnp.floor_divide(cells, ncell).astype(jnp.float32)  # (NG, W3, 3)
            shifts = img * box.lengths[None, None, :]

        if cfg.run_cap > 0:
            # merge SFC-adjacent survivors into long streamed runs (fewer,
            # fuller chunks; see _merge_runs)
            starts_c, lens_c, sh, ncells, run_cells = _merge_runs(
                start, lens, keep, shifts, cfg.run_cap, cfg.gap, cell=cell
            )
        else:
            # compact survivors to the front (stable: preserves SFC cell order)
            _, kc_i, starts_c, lens_s, shx_c, shy_c, shz_c, *cell_c = jax.lax.sort(
                ((~keep).astype(jnp.int32), keep.astype(jnp.int32), start, lens,
                 shifts[..., 0], shifts[..., 1], shifts[..., 2])
                + (() if cell is None else (cell,)),
                num_keys=1, dimension=1, is_stable=True,
            )
            keep_c = kc_i.astype(bool)
            lens_c = jnp.where(keep_c, lens_s, 0)
            # dead slots DMA row 0 harmlessly (len 0 masks every pair)
            starts_c = jnp.where(keep_c, starts_c, 0)
            sh = [jnp.where(keep_c, a, 0.0) for a in (shx_c, shy_c, shz_c)]
            ncells = jnp.sum(keep, axis=1).astype(jnp.int32)
            # an unmerged run IS one cell: first == last
            run_cells = tuple(jnp.where(keep_c, c, 0) for c in cell_c) * 2

        # cap overflow only matters for cells the kernel will visit: a culled
        # cell's clipped length truncates nothing
        occupancy = jnp.where(
            window_ok,
            jnp.max(jnp.where(keep, raw_len, 0)),
            jnp.int32(cfg.cap + 1),
        )

    # fold periods: open dims get an effectively-infinite period so the
    # fold is a no-op there (only consumed in fold mode)
    boxl = jnp.where(box.periodic_mask, box.lengths, jnp.float32(1e30))

    ranges = GroupRanges(
        starts=starts_c, lens=lens_c,
        shift_x=sh[0], shift_y=sh[1], shift_z=sh[2],
        ncells=ncells, occupancy=occupancy, boxl=boxl.astype(jnp.float32),
    )
    return (ranges, run_cells) if with_cells else ranges


def _window_cell_ranges(table, base, level: int, window: int, encode, wraps,
                        with_cells: bool):
    """``(start, raw_len, key)`` of every slot of every group's window,
    each (NG, W3) int32 in _window_offsets' slot order (x slowest, z
    fastest), read from the cell-starts ``table`` (SFC-key order) as
    BLOCKS of a grid-ordered copy of it; ``key`` (the slot's index in
    ``table``) is None without ``with_cells``.

    A group's window is a contiguous W x W x W block of the cell grid;
    only the curve key in between made it W^3 scattered reads, and a TPU
    gather pays per INDEX, not per value. So, once per call:

    1. ``grid[cx, cy, cz] = table[encode(cx, cy, cz)]`` (and the cell's
       length, and the key itself): ncell^3 indices, the keys from an iota
       on the device;
    2. every axis padded so that a block never leaves the array: a
       periodic axis (``wraps[d]``, static) with its wrapped copies, to
       ``ncell + window - 1`` (``base mod ncell`` is the block's corner
       there); an open one, whose ``base`` the caller has already clipped
       into ``[0, max(0, ncell - window)]``, with empty cells to
       ``max(ncell, window)``;
    3. the (y, z) planes of every possible block as ROWS: shifted static
       slices, ``rows[x, y, z] = grid[x, y:y+W, z:z+W]``, W^2 values wide
       and already in slot order;
    4. per group, its W planes are W rows: W indices a group instead of
       W^3 (or twice that), and (NG, W, W^2) IS (NG, W3).

    Everything is derived from ``table`` and ``base``, so under shard_map
    the tables vary as the (global, replicated) table does and the result
    as the local slab's groups do; no collective."""
    ncell = 1 << level
    cx, cy, cz = (
        jax.lax.broadcasted_iota(KEY_DTYPE, (ncell,) * 3, d) for d in range(3)
    )
    key = encode(cx, cy, cz, bits=level).astype(jnp.int32)
    start = table[key]
    chans = [start, table[key + 1] - start] + ([key] if with_cells else [])

    # distinct block corners per axis, and the padded extent they need
    corners = [ncell if w else max(1, ncell - window + 1) for w in wraps]
    _, ny, nz = corners
    corner = jnp.stack(
        [jnp.mod(base[:, d], ncell) if wraps[d] else base[:, d]
         for d in range(3)], axis=1)
    plane = jnp.arange(window, dtype=jnp.int32)
    row = ((corner[:, 0, None] + plane[None, :]) * ny
           + corner[:, 1, None]) * nz + corner[:, 2, None]  # (NG, W)

    def pad(g, d):
        extent = corners[d] + window - 1
        if wraps[d]:
            reps, rest = divmod(extent, ncell)
            return jnp.concatenate(
                [g] * reps + [jax.lax.slice_in_dim(g, 0, rest, axis=d)], axis=d)
        return jnp.pad(g, [(0, extent - ncell if a == d else 0)
                           for a in range(3)])

    out = []
    for g in chans:
        for d in range(3):
            g = pad(g, d)
        g = jnp.stack([g[:, :, k:k + nz] for k in range(window)], axis=-1)
        g = jnp.stack([g[:, j:j + ny] for j in range(window)], axis=-2)
        rows = g.reshape(-1, window * window)  # (Px * ny * nz, W^2)
        out.append(rows[row].reshape(base.shape[0], window**3))
    return out[0], out[1], (out[2] if with_cells else None)


def _merge_runs(start, lens, keep, shifts, run_cap: int, gap: int,
                cell=None):
    """Merge kept cells into contiguous streamed RUNS per group.

    The SFC sort makes spatially adjacent cells often key-adjacent, so
    their sorted-array ranges concatenate; merging them (and bridging
    key gaps of up to ``gap`` slots) turns many short cell DMAs with
    mostly-padded 128-lane chunks into few long runs with full chunks.
    Gap slots are pure bounded waste-work, never spurious physics: a gap
    particle belongs to a culled or out-of-window cell, and any such
    cell's AABB — at the single image position the window block can
    contain (window < ncell) — lies outside the group's inflated search
    bbox, so the particle cannot pass the distance mask under the run's
    shift; in fold mode (window >= ncell) every non-empty cell is kept,
    so gaps contain no particles at all. Runs never span different box
    images and are clipped to ``run_cap`` slots (the engine's static DMA
    window, NeighborConfig.dma_cap).

    ``cell``: optional (ng, w3) int32 index of every slot's cell in the
    table the starts were read from (ascending with ``start`` over kept
    cells). When given, each run's first and last kept cell ride along as
    two more int32 payloads of the sorts and the reverse scan.

    Returns (starts, lens, [shift_x, shift_y, shift_z], nruns, cells),
    shaped like the unmerged compaction; ``cells`` is ``(c0, c1)`` (0 on
    dead slots), or ``()`` without ``cell``.
    """
    ng, w3 = start.shape
    INF = jnp.int32(2**30)
    # variadic sort carries every payload through the sorting network —
    # argsort + take_along_axis would pay ~6 full-array gathers instead
    _, s, l, ki, shx, shy, shz, *c = jax.lax.sort(
        (jnp.where(keep, start, INF), start, lens, keep.astype(jnp.int32),
         shifts[..., 0], shifts[..., 1], shifts[..., 2])
        + (() if cell is None else (cell,)),
        num_keys=1, dimension=1,
    )
    k = ki.astype(bool)
    # unkept tail entries must not extend any run's end (nor its last
    # cell: the table is monotone, so the maximum end and the maximum
    # cell index of a run belong to the same kept cell)
    tails = [jnp.where(k, a, -1) for a in [s + l] + c]

    # forward scan: mark run heads (kept cells that cannot join the
    # running span: image mismatch, gap too wide, or span over run_cap)
    def fstep(carry, inp):
        run_start, prev_end, px, py, pz = carry
        s_w, l_w, k_w, sx, sy, sz = inp
        same = (sx == px) & (sy == py) & (sz == pz)
        join = (
            k_w & same
            & (s_w - prev_end <= gap)
            & (s_w + l_w - run_start <= run_cap)
        )
        new_start = jnp.where(join, run_start, s_w)
        carry = (
            jnp.where(k_w, new_start, run_start),
            jnp.where(k_w, s_w + l_w, prev_end),
            jnp.where(k_w, sx, px),
            jnp.where(k_w, sy, py),
            jnp.where(k_w, sz, pz),
        )
        return carry, k_w & ~join

    # inits derived from the inputs so their varying-manual-axes match
    # under shard_map (a plain jnp.zeros carry is rejected by check_vma)
    init = (
        jnp.zeros_like(s[:, 0]),
        jnp.full_like(s[:, 0], -INF),
        jnp.zeros_like(shx[:, 0]),
        jnp.zeros_like(shy[:, 0]),
        jnp.zeros_like(shz[:, 0]),
    )
    xs = tuple(a.T for a in (s, l, k, shx, shy, shz))
    _, is_head_t = jax.lax.scan(fstep, init, xs)
    is_head = is_head_t.T  # (ng, w3)

    # reverse scan: each run head's END = max cell end before the next head
    head_next = jnp.concatenate(
        [is_head[:, 1:], jnp.ones((ng, 1), bool)], axis=1
    )
    def rstep(carry, inp):
        hn_w = inp[-1]
        r = tuple(
            jnp.maximum(t_w, jnp.where(hn_w, jnp.int32(-1), t_c))
            for t_w, t_c in zip(inp[:-1], carry)
        )
        return r, r

    xs_r = tuple(a[:, ::-1].T for a in tails + [head_next])
    _, r_t = jax.lax.scan(
        rstep, tuple(jnp.full_like(a[:, 0], -1) for a in tails), xs_r
    )
    run_end, *run_c1 = (a.T[:, ::-1] for a in r_t)

    # compact heads to the front (stable: preserves key order)
    _, hk_i, hs_r, hlen, cshx, cshy, cshz, *hc = jax.lax.sort(
        ((~is_head).astype(jnp.int32), is_head.astype(jnp.int32), s,
         run_end - s, shx, shy, shz, *c, *run_c1),
        num_keys=1, dimension=1, is_stable=True,
    )
    hk = hk_i.astype(bool)
    hs = jnp.where(hk, hs_r, 0)
    hl = jnp.where(hk, hlen, 0)
    sh = [jnp.where(hk, a, 0.0) for a in (cshx, cshy, cshz)]
    nruns = jnp.sum(is_head, axis=1).astype(jnp.int32)
    return hs, hl, sh, nruns, tuple(jnp.where(hk, a, 0) for a in hc)


def _round_up(v: int, q: int) -> int:
    return -(-v // q) * q


#: rows of one int32 sublane tile: a group's segment of the persistent
#: lists' flat lane table (sph/pair_lists.py) starts on one and is a whole
#: number of them, so the build's tile DMAs and the walk's element-offset
#: fetch both stay aligned
LIST_ROW_TILE = 8


#: a RUN of the persistent lists is a tile of at most this many 128-lane
#: chunks: the build cuts the pruned runs to it (pair_lists.
#: _prune_empty_chunks) and the list kernel below fetches exactly that
#: many rows a run, whatever the un-pruned runs' width (``_dma_rows``: 13
#: at run_cap 1536, for the 3.2 chunks a pruned run kept). Chosen on the
#: chip (PERF.md, PR 40), one value for every list op and configuration
LIST_RUN_ROWS = 4

#: tiles in the list kernel's ring: LIST_RING - 1 runs' copies in flight
#: while one is walked
LIST_RING = 4


def list_run_rows(cfg: NeighborConfig) -> int:
    """Rows a run of the persistent lists streams at most = rows the list
    kernel fetches a run: never more than ``pack_j_fields``' tail pad."""
    return min(LIST_RUN_ROWS, _dma_rows(cfg.dma_cap))


def _ring_ahead(w, slot, ring: int):
    """Slot of the run ``ring - 1`` ahead of run ``w`` (whose slot is
    ``w % ring``) in a ring of ``ring`` buffers: the one run ``w - 1``
    left (``1 - slot`` in a ring of two: the streamed engine's own form,
    kept to the equation: LOWERING_LOCK.json holds its lowering)."""
    return 1 - slot if ring == 2 else (w + (ring - 1)) % ring


def _dma_rows(cap: int) -> int:
    """Rows of 128 covering any cell range [s, s+len<=cap): the range
    starts at lane offset s%128 inside row s//128 and extends at most
    127+cap slots, i.e. ceil((127+cap)/128) rows. SINGLE source of truth —
    the kernel's transfer shape and pack_j_fields' tail padding must
    agree or the DMA reads out of bounds."""
    return -(-(127 + cap) // 128)


def pack_j_fields(fields: Sequence[jax.Array], cap: int,
                  nf_min: int = 0) -> jax.Array:
    """Interleave the j-side fields into one (rows, nf_pad, 128) HBM
    buffer: slot j of field f lives at [j // 128, f, j % 128], so one
    dynamic row-slice DMA fetches EVERY field of a candidate cell.
    The tail is padded by a full DMA window so a range starting at the
    last particle still reads in-bounds garbage (masked); nf is padded
    to the f32 sublane quantum. ``nf_min``: minimum field rows (the
    list-walk engine stages one extra in-kernel row for the candidate's
    global index)."""
    n = fields[0].shape[0]
    nf = len(fields)
    nf_pad = _round_up(max(nf, nf_min), 8)
    rows = -(-n // 128) + _dma_rows(cap)
    flat = jnp.zeros((nf_pad, rows * 128), jnp.float32)
    flat = flat.at[:nf, :n].set(jnp.stack(fields))
    return flat.reshape(nf_pad, rows, 128).transpose(1, 0, 2)


def chunk_aabb_table(x, y, z, cap: int) -> jax.Array:
    """Per-128-chunk bounding boxes of the sorted coordinate arrays,
    (rows, 128) f32 rows [lo_x, lo_y, lo_z, hi_x, hi_y, hi_z, 0...] — the
    engine's chunk-cull input (row r covers sorted slots [128r, 128r+128)).
    lane-padded to 128. Rows match pack_j_fields' padded row count; tail
    rows get an empty (inverted) box so they never pass the cull."""
    n = x.shape[0]
    rows_n = -(-n // 128)
    rows = rows_n + _dma_rows(cap)
    pad = rows_n * 128 - n
    def padded(a, fill):
        a = jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)]) if pad else a
        return a.reshape(rows_n, 128)
    BIG = jnp.float32(1e30)
    lo = [jnp.min(padded(a, BIG), axis=1) for a in (x, y, z)]
    hi = [jnp.max(padded(a, -BIG), axis=1) for a in (x, y, z)]
    tbl = jnp.stack(lo + hi, axis=1)  # (rows_n, 6)
    tail = jnp.tile(
        jnp.asarray([[BIG, BIG, BIG, -BIG, -BIG, -BIG]], jnp.float32),
        (rows - rows_n, 1),
    )
    tbl = jnp.concatenate([tbl, tail], axis=0)
    # minor dim padded to the 128-lane tile (Mosaic DMAs cannot slice a
    # narrower HBM minor dimension)
    return jnp.pad(tbl, ((0, 0), (0, 122)))


def pallas_interpret() -> bool:
    """Run Mosaic kernels in interpret mode off-TPU (single policy for
    every engine consumer — SPH ops, gravity, analysis).

    Read at TRACE time inside the jitted steps and not part of their
    cache key: a trace taken under one answer is replayed under the
    other. Harmless because the answer is a function of the platform,
    which cannot change within a process (util.device.device_info)."""
    return not on_tpu()


def group_pair_engine(
    pair_body: Callable,
    finalize: Callable,
    num_i: int,
    num_j: int,
    num_acc: int,
    cfg: NeighborConfig,
    fold: bool = False,
    interpret: bool = False,
    pair_cutoff: bool = True,
    chunk_skip: Optional[bool] = None,
    want_nc: bool = True,
    sym_jf: Optional[int] = None,
):
    """Build a pallas_call for one SPH pair op.

    - ``pair_body(geom, i_fields, j_fields, accs) -> accs``: per-chunk pair
      math on (G, 128) tiles; i_fields are (G, 1) columns, j_fields are
      (1, 128) rows; accs is a tuple of (G, 128) f32 LANE-WISE partial
      accumulators — the body adds/maxes elementwise and must NOT reduce
      (cross-lane reductions inside the chunk loop cost more than the pair
      math; the epilogue reduces once).
    - ``finalize(i_fields, accs, nc) -> outs``: per-target epilogue; accs
      arrive unreduced (G, 128), nc is the reduced (G, 1) neighbor count;
      outs is a tuple of (G,) arrays (f32), one per output.
    - ``num_i``/``num_j``: how many target/candidate fields the op reads
      (x, y, z are always fields 0-2 on both sides; h is i-field 3).
    - ``pair_cutoff``: include the d2 < (2 h_i)^2 support test in the
      pair mask (SPH); gravity's near field keeps every ranged pair.
    - ``sym_jf``: j-field index of inv_h2j; when set the mask ALSO
      requires d2 < (2 h_j)^2 — the min-h symmetric cutoff that makes
      the momentum/energy pairing exactly antisymmetric (SimConstants
      .sym_pairs rationale; a strict subset of the i-cutoff, so the
      prologue's candidate coverage is unaffected).
    - ``chunk_skip``: cull whole 128-candidate chunks whose bbox misses
      the group's inflated bbox (defaults to ``pair_cutoff and not
      fold``); only meaningful for cutoff ops — gravity's near field has
      no distance cutoff, so every chunk contributes.
    - ``want_nc``: accumulate per-target neighbor counts (the trailing
      output). Ops that ignore the counts pass False and save the
      count's read-modify-write in every chunk.
    - returns fn(ranges, i_fields(NG,G) x num_i, j_packed, i_offset,
      allow_self) -> (outs (NG, G) x num_out, nc (NG, G)); ``allow_self``
      (traced bool) admits the self-index pair — replica-image passes of
      periodic gravity need it.
    """
    R = _dma_rows(cfg.dma_cap)
    RING = 2                 # run buffers: one walked, the rest in flight
    nf_pad = _round_up(num_j, 8)
    if chunk_skip is None:
        # bitmask bits live in one int32, so the DMA window must fit 31
        # chunks; beyond that (huge run_cap) the cull is simply skipped
        chunk_skip = pair_cutoff and not fold and R <= 31
    elif chunk_skip and R > 31:
        raise ValueError(
            f"chunk_skip needs a DMA window of <= 31 chunks (got {R}); "
            "the per-run cull verdicts are bits of one int32"
        )

    def kernel(*refs):
        starts, lens, shx_r, shy_r, shz_r, ncells, boxl, ioff, aself = refs[:9]
        i_refs = refs[9 : 9 + num_i]
        jref = refs[9 + num_i]
        nj_in = 11 + num_i if chunk_skip else 10 + num_i
        aabb_ref = refs[10 + num_i] if chunk_skip else None
        out_refs = refs[nj_in : -2]
        nc_ref = refs[-2]
        (buf, sems, acc_refs, ncacc_ref, abuf, asems) = refs[-1]

        gi = pl.program_id(0)
        G = cfg.group

        nc_g = ncells[0, 0, 0]

        def dma(w, slot):
            row_s = starts[0, 0, w] // 128
            return pltpu.make_async_copy(
                jref.at[pl.ds(row_s, R), :, :], buf.at[slot], sems.at[slot]
            )

        def dma_aabb(w, slot):
            row_s = starts[0, 0, w] // 128
            return pltpu.make_async_copy(
                aabb_ref.at[pl.ds(row_s, R), :], abuf.at[slot], asems.at[slot]
            )

        # the group's first RING - 1 copies start before anything else
        for w0 in range(RING - 1):
            @pl.when(w0 < nc_g)
            def _():
                dma(w0, w0).start()
                if chunk_skip:
                    dma_aabb(w0, w0).start()

        i_fields = [r[0, 0][:, None] for r in i_refs]  # (G, 1) each
        xi, yi, zi, hi = i_fields[:4]
        # group bbox inflated by the search radius, for the per-chunk cull
        # (recomputed from the i-fields already in VMEM — no new inputs);
        # matches the prologue's cell cull exactly: radius = 2 * max h_i
        if chunk_skip:
            g_r = 2.0 * jnp.max(hi)
            g_lo = (jnp.min(xi) - g_r, jnp.min(yi) - g_r, jnp.min(zi) - g_r)
            g_hi = (jnp.max(xi) + g_r, jnp.max(yi) + g_r, jnp.max(zi) + g_r)
        # global index of the first target: shard offset + group offset
        # (candidate indices are GLOBAL sorted-array positions, so the
        # self-pair test must compare in global index space)
        tgt_idx = (
            ioff[0, 0, 0] + gi * G
            + jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)
        )
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        h4 = 4.0 * hi * hi
        lx, ly, lz = boxl[0, 0, 0], boxl[0, 0, 1], boxl[0, 0, 2]

        def _prefetch(w, slot):
            @pl.when(w + (RING - 1) < nc_g)
            def _():
                dma(w + (RING - 1), _ring_ahead(w, slot, RING)).start()
                if chunk_skip:
                    dma_aabb(w + (RING - 1),
                             _ring_ahead(w, slot, RING)).start()

        def cell_body(w, carry):
            slot = w % RING
            _prefetch(w, slot)
            dma(w, slot).wait()

            s = starts[0, 0, w]
            ln = lens[0, 0, w]
            shx = shx_r[0, 0, w]
            shy = shy_r[0, 0, w]
            shz = shz_r[0, 0, w]
            row0 = s // 128
            off = s - row0 * 128
            nch = (off + ln + 127) // 128

            if chunk_skip:
                # once-per-run chunk cull: compare every chunk's AABB row
                # (DMAed alongside the j-fields) against the group's
                # inflated bbox, pack the verdicts into ONE scalar bitmask;
                # the chunk loop then tests a single bit per chunk instead
                # of paying cross-lane reductions on the candidate data
                dma_aabb(w, slot).wait()
                ab = abuf[slot]  # (R, 128)
                hit_rows = (
                    (ab[:, 3:4] + shx >= g_lo[0]) & (ab[:, 0:1] + shx <= g_hi[0])
                    & (ab[:, 4:5] + shy >= g_lo[1]) & (ab[:, 1:2] + shy <= g_hi[1])
                    & (ab[:, 5:6] + shz >= g_lo[2]) & (ab[:, 2:3] + shz <= g_hi[2])
                )  # (R, 1)
                pow2 = jnp.left_shift(
                    jnp.int32(1),
                    jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0),
                )
                # (AABB rows beyond the run's nch describe the NEXT run's
                # rows: the chunk loop stops at nch and never tests them)
                bits = jnp.sum(jnp.where(hit_rows, pow2, 0))

            def chunk_math(t):
                chunk = buf[slot, t]  # (nf_pad, 128)
                j_fields = [chunk[f][None, :] for f in range(num_j)]
                if fold:
                    # tiny-grid path: shifts are all zero, fold per pair
                    jx, jy, jz = j_fields[0], j_fields[1], j_fields[2]
                    rx = xi - jx
                    ry = yi - jy
                    rz = zi - jz
                    rx = rx - lx * jnp.round(rx / lx)
                    ry = ry - ly * jnp.round(ry / ly)
                    rz = rz - lz * jnp.round(rz / lz)
                else:
                    jx = j_fields[0] + shx
                    jy = j_fields[1] + shy
                    jz = j_fields[2] + shz
                    rx = xi - jx
                    ry = yi - jy
                    rz = zi - jz
                d2 = rx * rx + ry * ry + rz * rz
                cand = (row0 + t) * 128 + lane
                mask = (cand >= s) & (cand < s + ln)
                if pair_cutoff:
                    mask = mask & (d2 < h4)
                if sym_jf is not None:
                    mask = mask & (d2 * j_fields[sym_jf] < 4.0)
                mask = mask & ((cand != tgt_idx) | (aself[0, 0, 0] != 0))
                geom = PairGeom(rx=rx, ry=ry, rz=rz, d2=d2, mask=mask)
                # accumulators live in VMEM scratch (read-modify-write):
                # a skipped chunk touches nothing, and the fori carries
                # stay scalar so Mosaic never spills vector loop state
                accs = tuple(r[...] for r in acc_refs)
                accs = pair_body(geom, i_fields, j_fields, accs)
                for r, a in zip(acc_refs, accs):
                    r[...] = a
                if want_nc:
                    ncacc_ref[...] = ncacc_ref[...] + mask.astype(jnp.int32)

            def chunk_body(t, carry2):
                if not chunk_skip:
                    chunk_math(t)
                    return carry2

                # the chunk's AABB verdict is one bit of the run's bitmask —
                # skipping the (G, 128) tile's pair math for gap-bridged /
                # overshoot chunks costs one scalar test
                @pl.when((jax.lax.shift_right_logical(bits, t) & 1) != 0)
                def _():
                    chunk_math(t)

                return carry2

            return jax.lax.fori_loop(0, nch, chunk_body, carry)

        for r in acc_refs:
            r[...] = jnp.zeros((G, 128), jnp.float32)
        ncacc_ref[...] = jnp.zeros((G, 128), jnp.int32)
        jax.lax.fori_loop(0, nc_g, cell_body, 0)
        accs = tuple(r[...] for r in acc_refs)

        nc_acc = jnp.sum(ncacc_ref[...], axis=1, keepdims=True)
        outs = finalize(i_fields, accs, nc_acc)
        for r, o in zip(out_refs, outs):
            r[0, 0] = o.reshape(G)
        nc_ref[0, 0] = nc_acc.reshape(G)

    def scalar_kernel(*refs):
        # scratch unpack shim: keep kernel() readable
        # buf, sems, accs x num_acc, nc[, aabb buf, aabb sems]
        ns = num_acc + (5 if chunk_skip else 3)
        buf, sems = refs[-ns], refs[-ns + 1]
        if chunk_skip:
            acc_refs = refs[-ns + 2 : -3]
            kernel(*refs[:-ns],
                   (buf, sems, acc_refs, refs[-3], refs[-2], refs[-1]))
        else:
            acc_refs = refs[-ns + 2 : -1]
            kernel(*refs[:-ns], (buf, sems, acc_refs, refs[-1], None, None))

    def call(ranges: GroupRanges, i_fields: Sequence, j_packed,
             i_offset=0, allow_self=False, aabb=None):
        if chunk_skip and aabb is None:
            raise ValueError("chunk_skip engine needs the chunk AABB table")
        num_groups = ranges.num_groups
        # run-slot width comes from the ranges themselves: the sharded
        # path appends boundary-split slots beyond the window block
        w3 = ranges.starts.shape[1]
        ioff = jnp.asarray(i_offset, jnp.int32).reshape(1, 1, 1)
        aself = jnp.asarray(allow_self, jnp.int32).reshape(1, 1, 1)
        smem3 = lambda a: a.reshape(num_groups, 1, w3)
        starts = smem3(ranges.starts)
        lens = smem3(ranges.lens)
        shx = smem3(ranges.shift_x)
        shy = smem3(ranges.shift_y)
        shz = smem3(ranges.shift_z)
        ncells = ranges.ncells.reshape(num_groups, 1, 1)
        boxl = ranges.boxl.reshape(1, 1, 3)
        G = cfg.group
        i_fields = [a.reshape(num_groups, 1, G) for a in i_fields]
        num_out_arrays = len(
            finalize(
                [jnp.zeros((G, 1))] * num_i,
                tuple(jnp.zeros((G, 1)) for _ in range(num_acc)),
                jnp.zeros((G, 1), jnp.int32),
            )
        )
        smem_spec = lambda shape: pl.BlockSpec(
            shape, lambda g: (g, 0, 0), memory_space=pltpu.SMEM
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(num_groups,),
            in_specs=[
                smem_spec((1, 1, w3)),  # starts
                smem_spec((1, 1, w3)),  # lens
                smem_spec((1, 1, w3)),  # shift x/y/z
                smem_spec((1, 1, w3)),
                smem_spec((1, 1, w3)),
                smem_spec((1, 1, 1)),   # ncells
                pl.BlockSpec((1, 1, 3), lambda g: (0, 0, 0),
                             memory_space=pltpu.SMEM),  # boxl
                pl.BlockSpec((1, 1, 1), lambda g: (0, 0, 0),
                             memory_space=pltpu.SMEM),  # i_offset
                pl.BlockSpec((1, 1, 1), lambda g: (0, 0, 0),
                             memory_space=pltpu.SMEM),  # allow_self
            ]
            + [
                pl.BlockSpec((1, 1, G), lambda g: (g, 0, 0))
                for _ in range(num_i)
            ]
            + [pl.BlockSpec(memory_space=pl.ANY)]
            + ([pl.BlockSpec(memory_space=pl.ANY)] if chunk_skip else []),
            out_specs=[
                pl.BlockSpec((1, 1, G), lambda g: (g, 0, 0))
                for _ in range(num_out_arrays)
            ]
            + [pl.BlockSpec((1, 1, G), lambda g: (g, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((RING, R, nf_pad, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((RING,)),
            ]
            + [pltpu.VMEM((G, 128), jnp.float32) for _ in range(num_acc)]
            + [pltpu.VMEM((G, 128), jnp.int32)]
            + (
                [pltpu.VMEM((2, R, 128), jnp.float32),
                 pltpu.SemaphoreType.DMA((2,))]
                if chunk_skip else []
            ),
        )
        out_shape = [
            jax.ShapeDtypeStruct((num_groups, 1, G), jnp.float32)
            for _ in range(num_out_arrays)
        ] + [jax.ShapeDtypeStruct((num_groups, 1, G), jnp.int32)]
        args = (
            (starts, lens, shx, shy, shz, ncells, boxl, ioff, aself)
            + (*i_fields, j_packed)
            + ((aabb,) if chunk_skip else ())
        )
        outs = pl.pallas_call(
            scalar_kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(*args)
        return outs

    return call


def group_pair_engine_lists(
    pair_body: Callable,
    finalize: Callable,
    num_i: int,
    num_j: int,
    num_acc: int,
    cfg: NeighborConfig,
    interpret: bool = False,
    pair_cutoff: bool = True,
    want_nc: bool = True,
    sym_jf: Optional[int] = None,
):
    """List-walk variant of ``group_pair_engine``: the same run-by-run
    streaming, but every chunk's candidate lanes are COMPACTED with the
    persistent lists' per-chunk gather indices (sph/pair_lists.py) and
    merged into a dense 256-lane staging window; the pair math fires only
    on FULL 128-lane staging chunks (plus one flush). Per-target lane
    count drops from the streamed-chunk floor to the exact inflated-bbox
    occupancy (~2.5x fewer VPU ops on the measured Sedov configs).

    Contract differences from the streaming engine:
    - call(lists, i_fields, j_packed, i_offset, allow_self) — runs come
      from lists.ranges (build-time, skin-inflated), and a run is a TILE:
      at most ``list_run_rows`` chunks, fetched as exactly that many rows
      into a ring of ``LIST_RING`` tiles, so a run's chunk loop is a
      static unroll whose lane gathers are issued together;
    - the gather indices come from the lists' FLAT table (one row per
      kept chunk, sized by the sum over groups): a group's rows are a
      window of slot_cap rows from its segment's first row, so chunk k
      of the group is row k of the block;
    - no fold mode (lists are disabled on tiny grids), no chunk pairing,
      no AABB chunk-skip (the cnt>0 test replaces it at zero DMA cost);
    - the candidate's GLOBAL sorted-array index is staged as an f32 row
      (exact for n < 2^24; the HBM-headroom bound is 8M rows/chip), so
      the self-pair and shard-offset tests read it from staging.
    """
    RR = list_run_rows(cfg)
    RING = LIST_RING
    nf_pad = _round_up(num_j + 1, 8)  # +1: staged global-index row
    IDXR = num_j                       # sublane row of the staged index

    def kernel(*refs):
        # refs[0]: the scalar-prefetched segment offsets, read by the
        # lane table's index map alone
        (starts, lens, shx_r, shy_r, shz_r, ncells, ioff, aself,
         cnt_r, fill_r, emit_r, tail_r) = refs[1:13]
        i_refs = refs[13 : 13 + num_i]
        jref = refs[13 + num_i]
        gidx_ref = refs[14 + num_i]
        out_refs = refs[15 + num_i : -2]
        nc_ref = refs[-2]
        (buf, sems, acc_refs, ncacc_ref, stage) = refs[-1]

        gi = pl.program_id(0)
        G = cfg.group
        nc_g = ncells[0, 0, 0]

        def dma(w, slot):
            row_s = starts[0, 0, w] // 128
            return pltpu.make_async_copy(
                jref.at[pl.ds(row_s, RR), :, :],
                buf.at[slot], sems.at[slot],
            )

        # the group's first RING - 1 tiles are on their way before
        # anything else
        for w0 in range(RING - 1):
            @pl.when(w0 < nc_g)
            def _():
                dma(w0, w0).start()

        i_fields = [r[0, 0][:, None] for r in i_refs]  # (G, 1) each
        xi, yi, zi, hi = i_fields[:4]
        tgt_f = (
            ioff[0, 0, 0] + gi * G
            + jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)
        ).astype(jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        lane_f = jax.lax.broadcasted_iota(jnp.int32, (nf_pad, 128), 1)
        subl = jax.lax.broadcasted_iota(jnp.int32, (nf_pad, 128), 0)
        h4 = 4.0 * hi * hi

        def stage_math(valid):
            st = stage[:, :128]  # (nf_pad, 128) value read
            j_fields = [st[f][None, :] for f in range(num_j)]
            cand_f = st[IDXR][None, :]
            jx, jy, jz = j_fields[0], j_fields[1], j_fields[2]
            rx = xi - jx
            ry = yi - jy
            rz = zi - jz
            d2 = rx * rx + ry * ry + rz * rz
            mask = jnp.broadcast_to(lane < valid, d2.shape)
            if pair_cutoff:
                mask = mask & (d2 < h4)
            if sym_jf is not None:
                mask = mask & (d2 * j_fields[sym_jf] < 4.0)
            mask = mask & ((cand_f != tgt_f) | (aself[0, 0, 0] != 0))
            geom = PairGeom(rx=rx, ry=ry, rz=rz, d2=d2, mask=mask)
            accs = tuple(r[...] for r in acc_refs)
            accs = pair_body(geom, i_fields, j_fields, accs)
            for r, a in zip(acc_refs, accs):
                r[...] = a
            if want_nc:
                ncacc_ref[...] = ncacc_ref[...] + mask.astype(jnp.int32)

        def _prefetch(w, slot):
            @pl.when(w + (RING - 1) < nc_g)
            def _():
                dma(w + (RING - 1), _ring_ahead(w, slot, RING)).start()

        s_last = cnt_r.shape[-1] - 1

        def _walk_tile(slot, slot_base, nch, row0, shx, shy, shz):
            # image-resolve the coordinate rows: one (nf_pad, 1) shift
            # column a run
            shift_col = jnp.where(
                subl[:, :1] == 0, shx,
                jnp.where(subl[:, :1] == 1, shy,
                          jnp.where(subl[:, :1] == 2, shz, 0.0)),
            )
            # first every chunk's compaction, independent of each other
            # and of the staging window: one scheduling region, so the
            # tile's gathers overlap. A chunk past the run's own (the
            # tile's rows behind it) takes a zero count: it merges
            # nothing and emits nothing
            chunks = []
            for t in range(RR):
                si = jnp.minimum(slot_base + t, s_last)
                live = t < nch
                cnt = jnp.where(live, cnt_r[0, 0, si], 0)
                fill = fill_r[0, 0, si]
                # gidx arrives PRE-ROTATED by the staging fill, so the
                # compaction + rotation is ONE lane gather
                gi_row = gidx_ref[si][None, :]  # (1, 128) int32
                rolled = jnp.take_along_axis(
                    buf[slot, t],
                    jnp.broadcast_to(gi_row, (nf_pad, 128)), axis=1,
                ) + shift_col
                # the global-index row: one sublane select
                idx_f = ((row0 + t) * 128 + gi_row).astype(jnp.float32)
                rolled = jnp.where(
                    subl == IDXR, jnp.broadcast_to(idx_f, rolled.shape),
                    rolled,
                )
                chunks.append((si, live, cnt, fill, rolled))
            # then the merges into the staging window, in order
            for si, live, cnt, fill, rolled in chunks:
                m0 = (lane_f >= fill) & (lane_f < fill + cnt)
                m1 = lane_f < (fill + cnt - 128)
                stage[:, :128] = jnp.where(m0, rolled, stage[:, :128])
                stage[:, 128:] = jnp.where(m1, rolled, stage[:, 128:])

                @pl.when(live & (emit_r[0, 0, si] > 0))
                def _():
                    stage_math(jnp.int32(128))
                    stage[:, :128] = stage[:, 128:]
                    stage[:, 128:] = jnp.zeros((nf_pad, 128), jnp.float32)

        def cell_body(w, slot_base):
            slot = w % RING
            _prefetch(w, slot)
            dma(w, slot).wait()
            s = starts[0, 0, w]
            ln = lens[0, 0, w]
            row0 = s // 128
            nch = (s - row0 * 128 + ln + 127) // 128
            _walk_tile(slot, slot_base, nch, row0,
                       shx_r[0, 0, w], shy_r[0, 0, w], shz_r[0, 0, w])
            return slot_base + nch

        stage[...] = jnp.zeros((nf_pad, 256), jnp.float32)
        for r in acc_refs:
            r[...] = jnp.zeros((G, 128), jnp.float32)
        ncacc_ref[...] = jnp.zeros((G, 128), jnp.int32)
        jax.lax.fori_loop(0, nc_g, cell_body, 0)

        tail = tail_r[0, 0, 0]

        @pl.when(tail > 0)
        def _():
            stage_math(tail)

        accs = tuple(r[...] for r in acc_refs)
        nc_acc = jnp.sum(ncacc_ref[...], axis=1, keepdims=True)
        outs = finalize(i_fields, accs, nc_acc)
        for r, o in zip(out_refs, outs):
            r[0, 0] = o.reshape(G)
        nc_ref[0, 0] = nc_acc.reshape(G)

    def scalar_kernel(*refs):
        ns = num_acc + 4  # buf, sems, accs x num_acc, ncacc, stage
        buf, sems = refs[-ns], refs[-ns + 1]
        acc_refs = refs[-ns + 2 : -2]
        kernel(*refs[:-ns], (buf, sems, acc_refs, refs[-2], refs[-1]))

    def call(lists, i_fields: Sequence, j_packed,
             i_offset=0, allow_self=False):
        ranges = lists.ranges
        num_groups = ranges.num_groups
        w3 = ranges.starts.shape[1]
        S_cap = lists.slot_cap
        ioff = jnp.asarray(i_offset, jnp.int32).reshape(1, 1, 1)
        aself = jnp.asarray(allow_self, jnp.int32).reshape(1, 1, 1)
        smem3 = lambda a: a.reshape(num_groups, 1, -1)
        G = cfg.group
        i_fields = [a.reshape(num_groups, 1, G) for a in i_fields]
        num_out_arrays = len(
            finalize(
                [jnp.zeros((G, 1))] * num_i,
                tuple(jnp.zeros((G, 1)) for _ in range(num_acc)),
                jnp.zeros((G, 1), jnp.int32),
            )
        )
        smem_spec = lambda shape: pl.BlockSpec(
            shape, lambda g, seg: (g, 0, 0), memory_space=pltpu.SMEM
        )
        rep_spec = lambda shape: pl.BlockSpec(
            shape, lambda g, seg: (0, 0, 0), memory_space=pltpu.SMEM
        )
        vmem_spec = lambda: pl.BlockSpec((1, 1, G), lambda g, seg: (g, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,         # lists.seg
            grid=(num_groups,),
            in_specs=[
                smem_spec((1, 1, w3)),     # starts
                smem_spec((1, 1, w3)),     # lens
                smem_spec((1, 1, w3)),     # shift x/y/z
                smem_spec((1, 1, w3)),
                smem_spec((1, 1, w3)),
                smem_spec((1, 1, 1)),      # ncells
                rep_spec((1, 1, 1)),       # i_offset
                rep_spec((1, 1, 1)),       # allow_self
                smem_spec((1, 1, S_cap)),  # cnt
                smem_spec((1, 1, S_cap)),  # fill
                smem_spec((1, 1, S_cap)),  # emit
                smem_spec((1, 1, 1)),      # tail
            ]
            + [vmem_spec() for _ in range(num_i)]
            + [
                pl.BlockSpec(memory_space=pl.ANY),             # j_packed
                # gidx: the group's rows of the flat table, an element-
                # offset window of slot_cap rows from its segment's
                # first row (auto-pipelined across grid steps like any
                # block; Mosaic must see the offset divide the tile)
                pl.BlockSpec(
                    (pl.Element(_round_up(S_cap, LIST_ROW_TILE)),
                     pl.Element(128)),
                    lambda g, seg: (seg[g] * LIST_ROW_TILE, 0)),
            ],
            out_specs=[vmem_spec() for _ in range(num_out_arrays + 1)],
            scratch_shapes=[
                pltpu.VMEM((RING, RR, nf_pad, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((RING,)),
            ]
            + [pltpu.VMEM((G, 128), jnp.float32) for _ in range(num_acc)]
            + [pltpu.VMEM((G, 128), jnp.int32)]
            + [pltpu.VMEM((nf_pad, 256), jnp.float32)],
        )
        out_shape = [
            jax.ShapeDtypeStruct((num_groups, 1, G), jnp.float32)
            for _ in range(num_out_arrays)
        ] + [jax.ShapeDtypeStruct((num_groups, 1, G), jnp.int32)]
        args = (
            lists.seg,
            smem3(ranges.starts), smem3(ranges.lens),
            smem3(ranges.shift_x), smem3(ranges.shift_y),
            smem3(ranges.shift_z),
            ranges.ncells.reshape(num_groups, 1, 1), ioff, aself,
            smem3(lists.cnt), smem3(lists.fill), smem3(lists.emit),
            lists.tail.reshape(num_groups, 1, 1),
            *i_fields, j_packed, lists.gidx,
        )
        outs = pl.pallas_call(
            scalar_kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(*args)
        return outs

    return call


def _prep_i(x, y, z, h, extra_i, group: int = GROUP):
    """Block the target-side fields (NG, group); tail groups re-read the
    last particle (masked out by the self/index tests)."""
    n = x.shape[0]
    num_groups = -(-n // group)
    pad_i = num_groups * group - n

    def block_i(a):
        a = jnp.concatenate([a, jnp.broadcast_to(a[-1:], (pad_i,))]) if pad_i else a
        return a.reshape(num_groups, group)

    return [block_i(a) for a in (x, y, z, h, *extra_i)]


# W on (G, 128) tiles from u = d2/h^2: 14 FMAs, no sqrt/sin/div
# (shared evaluator — both backends compute identical W)
_w_poly = sinc_poly_eval


def _op_aabb(jfields: Sequence, box: Box, cfg: NeighborConfig):
    """Chunk-AABB cull table for an op's j-side source arrays (None when
    the engine runs without the cull: fold mode or oversized DMA window).
    All ops of one step build it from the same coordinates inside one jit,
    so XLA CSE collapses the copies."""
    if engine_fold(box, cfg) or _dma_rows(cfg.dma_cap) > 31:
        return None
    return chunk_aabb_table(jfields[0], jfields[1], jfields[2], cfg.dma_cap)


#: THE place where a pair op's kernel is chosen: op -> (kernel on the
#: persistent lists, AABB chunk cull when streamed). "walk" is
#: ``group_pair_engine_lists``, the one list kernel there is: the kept
#: lanes compacted into full staging chunks. No argument, flag or
#: environment variable overrides a row; a by-hand bench patches it.
PAIR_OP_ENGINE = {
    # one staged sublane tile (4-5 j-fields + the index row): math on the
    # 10 staged chunks a group beats math on its 34 whole kept ones. Walk
    # against the mark-bit chunk skip PR 43 deleted, ms a pass on a v5e at
    # Sedov 160^3 / wind-shock -n 100 / Noh 1.1M: density 261.6 / 307.2 /
    # 59.1 against 311.0 / 360.7 / 66.8, iad 296.8 / 350.8 / 68.5 against
    # 396.2 / 458.7 / 86.6, gradh 298.3 / 350.4 / 68.3 against 400.5 /
    # 464.4 / 86.7 (PERF.md section 5). Streamed, the chunk cull lost on
    # the cheap ops (ROADMAP, dead ends on record)
    "density": ("walk", False),  # pallas_xmass rides it
    "iad": ("walk", False),
    "gradh": ("walk", False),
    "momentum-energy-std": ("walk", True),
    "divv-curlv": ("walk", True),
    "av-switches": ("walk", True),
    "momentum-energy-ve": ("walk", True),
}


def _run_pair_op(op: str, pair_body: Callable, finalize: Callable,
                 i_fields: Sequence, jfields: Sequence, *, num_acc: int,
                 box: Box, cfg: NeighborConfig, ranges, lists, i_offset,
                 interpret: bool, want_nc: bool = False,
                 sym_jf: Optional[int] = None):
    """Everything after an op's fields are ready: build the kernel its
    ``PAIR_OP_ENGINE`` row names, pack the j-fields for it (the walk
    stages the candidate's index as one more row), call it. Returns the
    kernel's raw (NG, 1, G) outputs, the neighbour counts last, and the
    occupancy of the ranges it ran over."""
    on_lists, cull = PAIR_OP_ENGINE[op]
    num_j = len(jfields)
    dims = dict(num_i=len(i_fields), num_j=num_j, num_acc=num_acc, cfg=cfg,
                interpret=interpret, want_nc=want_nc, sym_jf=sym_jf)
    if lists is None:
        engine = group_pair_engine(
            pair_body, finalize, fold=engine_fold(box, cfg),
            chunk_skip=None if cull else False, **dims)
        outs = engine(ranges, i_fields, pack_j_fields(jfields, cfg.dma_cap),
                      i_offset,
                      aabb=_op_aabb(jfields, box, cfg) if cull else None)
        return outs, ranges.occupancy
    if on_lists != "walk":
        raise ValueError(f"{op}: no list kernel {on_lists!r}")
    engine = group_pair_engine_lists(pair_body, finalize, **dims)
    outs = engine(
        lists, i_fields,
        pack_j_fields(jfields, cfg.dma_cap, nf_min=num_j + 1), i_offset)
    return outs, lists.ranges.occupancy


@named_phase("density")
def pallas_density(
    x, y, z, h, m, sorted_keys, box: Box, const, cfg: NeighborConfig,
    ranges=None, interpret: bool = False, jdata=None, i_offset=0,
    lists=None,
):
    """rho_i = K h_i^-3 (m_i + sum_j m_j W(|r_ij|/h_i)) + neighbor counts.

    Pallas instantiation of hydro_std.compute_density (density.hpp:41) with
    the search fused in. Returns (rho (n,), nc (n,), occupancy).

    Under shard_map, the i-side arrays are the local slab while ``jdata``
    supplies the GLOBAL (all-gathered) candidate arrays (x, y, z, m) that
    ``sorted_keys``/``ranges`` index into, and ``i_offset`` is the slab's
    global start index (for the self-pair test).

    ``lists``: persistent PairLists (sph/pair_lists.py) — the list-walk
    engine replaces the streaming engine and ``sorted_keys``/``ranges``
    are unused (candidate runs come from the build-time lists).
    """
    n = x.shape[0]
    coeffs = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)

    if ranges is None and lists is None:
        ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg)

    def pair_body(geom, i_fields, j_fields, accs):
        (rho_sum,) = accs
        inv_h2 = i_fields[4]
        mj = j_fields[3]
        w = _w_poly(geom.d2 * inv_h2, coeffs)
        return (rho_sum + jnp.where(geom.mask, mj * w, 0.0),)

    def finalize(i_fields, accs, nc):
        hi = i_fields[3]
        mi = i_fields[5]
        rho_sum = jnp.sum(accs[0], axis=1, keepdims=True)
        rho = K * (mi + rho_sum) / (hi * hi * hi)
        return (rho,)

    i_fields = _prep_i(x, y, z, h, (1.0 / (h * h), m), cfg.group)
    (rho, nc), occ = _run_pair_op(
        "density", pair_body, finalize, i_fields, jdata or (x, y, z, m),
        num_acc=1, box=box, cfg=cfg, ranges=ranges, lists=lists,
        i_offset=i_offset, interpret=interpret, want_nc=True)
    return rho.reshape(-1)[:n], nc.reshape(-1)[:n], occ


def _iad_tau_terms(geom):
    """The six r (x) r products of the IAD moment matrix, in the order
    (11, 12, 13, 22, 23, 33)."""
    return (
        geom.rx * geom.rx, geom.rx * geom.ry, geom.rx * geom.rz,
        geom.ry * geom.ry, geom.ry * geom.rz, geom.rz * geom.rz,
    )


def _iad_invert(hi, K: float, t11, t12, t13, t22, t23, t33):
    """(G, 1) reduced moments tau -> the six components of its inverse
    scaled by h^3/K (shared epilogue of pallas_iad and the fused
    pallas_iad_divv_curlv)."""
    # exponent renormalization (iad_kern.hpp ilogb/ldexp trick) via
    # exp2/log2 — exact because the factor cancels in adj/det
    exp_of = lambda v: jnp.where(
        v != 0.0, jnp.floor(jnp.log2(jnp.abs(v) + 1e-45)), 0.0
    )
    esum = (exp_of(t11) + exp_of(t12) + exp_of(t13)
            + exp_of(t22) + exp_of(t23) + exp_of(t33))
    norm = jnp.exp2(-jnp.floor(esum / 6.0))
    t11, t12, t13 = t11 * norm, t12 * norm, t13 * norm
    t22, t23, t33 = t22 * norm, t23 * norm, t33 * norm
    det = (t11 * t22 * t33 + 2.0 * t12 * t23 * t13
           - t11 * t23 * t23 - t22 * t13 * t13 - t33 * t12 * t12)
    factor = norm * (hi * hi * hi) / (det * K)
    return (
        (t22 * t33 - t23 * t23) * factor,
        (t13 * t23 - t33 * t12) * factor,
        (t12 * t23 - t22 * t13) * factor,
        (t11 * t33 - t13 * t13) * factor,
        (t13 * t12 - t11 * t23) * factor,
        (t11 * t22 - t12 * t12) * factor,
    )


@named_phase("iad")
def pallas_iad(
    x, y, z, h, vol, sorted_keys, box: Box, const, cfg: NeighborConfig,
    ranges=None, interpret: bool = False, jdata=None, i_offset=0,
    lists=None,
):
    """IAD tensor components (hydro_std.compute_iad, iad_kern.hpp) with the
    neighbor search fused in. ``vol`` is the per-particle volume estimate
    (m/rho std, xm/kx VE). Returns (c11..c33, occupancy).

    Under shard_map, ``jdata = (x, y, z, vol)`` supplies the GLOBAL
    j-side arrays (making the local ``vol`` argument j-side-dead) and
    ``i_offset`` the slab's global start index — same contract as
    pallas_density."""
    n = x.shape[0]
    coeffs = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)

    if ranges is None and lists is None:
        ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg)

    def pair_body_lanes(geom, i_fields, j_fields, accs):
        inv_h2 = i_fields[4]
        vj = j_fields[3]
        w = _w_poly(geom.d2 * inv_h2, coeffs)
        vw = jnp.where(geom.mask, vj * w, 0.0)
        return tuple(
            acc + t * vw for acc, t in zip(accs, _iad_tau_terms(geom))
        )

    def finalize(i_fields, accs, nc):
        t11, t12, t13, t22, t23, t33 = (
            jnp.sum(a, axis=1, keepdims=True) for a in accs
        )
        return _iad_invert(i_fields[3], K, t11, t12, t13, t22, t23, t33)

    # NOTE: an MXU variant (second moments around the group center via one
    # (G,128)x(128,16) dot_general per chunk, engine commit 42af8de)
    # hook) measured SLOWER than the lane path on v5e (484 vs 434 ms/step,
    # Sedov 100^3): the per-chunk NT-dot relayout exceeds the ~20 VPU ops
    # it saves. Revisit if Mosaic grows a cheap lane-contraction.
    i_fields = _prep_i(x, y, z, h, (1.0 / (h * h),), cfg.group)
    (*cs, _nc), occ = _run_pair_op(
        "iad", pair_body_lanes, finalize, i_fields,
        jdata or (x, y, z, vol), num_acc=6, box=box, cfg=cfg, ranges=ranges,
        lists=lists, i_offset=i_offset, interpret=interpret)
    return tuple(c.reshape(-1)[:n] for c in cs), occ


@named_phase("momentum-energy")
def pallas_momentum_energy_std(
    x, y, z, vx, vy, vz, h, m, rho, p, c,
    c11, c12, c13, c22, c23, c33,
    sorted_keys, box: Box, const, cfg: NeighborConfig,
    ranges=None, interpret: bool = False, jdata=None, i_offset=0,
    lists=None,
):
    """Pressure-gradient accelerations + energy rate + Courant dt
    (hydro_std.compute_momentum_energy_std, momentum_energy_kern.hpp:12-134)
    with the neighbor search fused in. Returns (ax, ay, az, du, min_dt, occ).

    The per-particle ratios the reference computes per PAIR
    (momentum_energy_kern.hpp: p/rho^2, m/rho, 1/h^3) are precombined into
    the i-columns / packed j-fields here, so the inner tile math has no
    divisions and a single rsqrt.
    """
    n = x.shape[0]
    coeffs = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)
    k_cour = float(const.k_cour)

    if ranges is None and lists is None:
        ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg)

    def pair_body(geom, i_fields, j_fields, accs):
        momx, momy, momz, energy, maxvs = accs
        (xi, yi, zi, hi, inv_h2i, inv_h3i, vxi, vyi, vzi, ci, pro_i, mi_roi,
         c11i, c12i, c13i, c22i, c23i, c33i) = i_fields
        (cx, cy, cz, inv_h2j, vxj, vyj, vzj, cj, mj, mjroj3, pjroj,
         c11j, c12j, c13j, c22j, c23j, c33j) = j_fields

        w_i = _w_poly(geom.d2 * inv_h2i, coeffs) * inv_h3i
        # support clamp inside _w_poly zeroes pairs beyond 2 h_j, matching
        # the reference's table lookup clamp
        mjw = mjroj3 * _w_poly(geom.d2 * inv_h2j, coeffs)  # m_j/rho_j W_j

        # self/masked pairs have d2 = 0 -> rsqrt = inf -> NaNs confined to
        # masked lanes; every accumulation below selects on geom.mask
        inv_dist = jax.lax.rsqrt(geom.d2)
        vx_ij = vxi - vxj
        vy_ij = vyi - vyj
        vz_ij = vzi - vzj
        rv = geom.rx * vx_ij + geom.ry * vy_ij + geom.rz * vz_ij
        w_ij = rv * inv_dist

        # Monaghan constant-alpha AV, halved per pair (kernels.hpp:60-84)
        cij = ci + cj
        v_signal = 0.5 * cij - 2.0 * w_ij
        visc = 0.5 * jnp.where(w_ij < 0.0, -v_signal * w_ij, 0.0)

        maxvs = jnp.maximum(
            maxvs, jnp.where(geom.mask, cij - 3.0 * w_ij, 0.0)
        )

        tA1_i = c11i * geom.rx + c12i * geom.ry + c13i * geom.rz
        tA2_i = c12i * geom.rx + c22i * geom.ry + c23i * geom.rz
        tA3_i = c13i * geom.rx + c23i * geom.ry + c33i * geom.rz
        tA1_j = c11j * geom.rx + c12j * geom.ry + c13j * geom.rz
        tA2_j = c12j * geom.rx + c22j * geom.ry + c23j * geom.rz
        tA3_j = c13j * geom.rx + c23j * geom.ry + c33j * geom.rz

        mj_pro_i = mj * pro_i
        vmi = visc * mi_roi
        a = w_i * (mj_pro_i + vmi)
        b = mjw * (pjroj + visc)
        mm = geom.mask
        momx = momx + jnp.where(mm, a * tA1_i + b * tA1_j, 0.0)
        momy = momy + jnp.where(mm, a * tA2_i + b * tA2_j, 0.0)
        momz = momz + jnp.where(mm, a * tA3_i + b * tA3_j, 0.0)

        a_e = w_i * (2.0 * mj_pro_i + vmi)
        b_e = visc * mjw
        energy = energy + jnp.where(
            mm,
            vx_ij * (a_e * tA1_i + b_e * tA1_j)
            + vy_ij * (a_e * tA2_i + b_e * tA2_j)
            + vz_ij * (a_e * tA3_i + b_e * tA3_j),
            0.0,
        )
        return momx, momy, momz, energy, maxvs

    def finalize(i_fields, accs, nc):
        hi = i_fields[3]
        ci = i_fields[9]
        momx, momy, momz, energy, maxvs = accs
        red = lambda a: jnp.sum(a, axis=1, keepdims=True)
        du = -K * 0.5 * red(energy)
        mv = jnp.max(maxvs, axis=1, keepdims=True)
        v = jnp.where(mv > 0.0, mv, ci)
        dt_i = k_cour * hi / v
        return (K * red(momx), K * red(momy), K * red(momz), du, dt_i)

    inv_h2 = 1.0 / (h * h)
    inv_h3 = inv_h2 / h
    i_fields = _prep_i(
        x, y, z, h,
        (inv_h2, inv_h3, vx, vy, vz, c, p / (rho * rho), m / rho,
         c11, c12, c13, c22, c23, c33),
        cfg.group,
    )
    if jdata is None:
        jfields = (x, y, z, inv_h2, vx, vy, vz, c, m,
                   m / (rho * h * h * h), p / rho,
                   c11, c12, c13, c22, c23, c33)
    else:
        (xj, yj, zj, hj, vxj, vyj, vzj, mj, rhoj, pj, cj,
         j11, j12, j13, j22, j23, j33) = jdata
        jfields = (xj, yj, zj, 1.0 / (hj * hj), vxj, vyj, vzj, cj, mj,
                   mj / (rhoj * hj * hj * hj), pj / rhoj,
                   j11, j12, j13, j22, j23, j33)
    sym = 3 if getattr(const, "sym_pairs", True) else None
    f = lambda a: a.reshape(-1)[:n]
    (ax, ay, az, du, dt_i, _nc), occ = _run_pair_op(
        "momentum-energy-std", pair_body, finalize, i_fields, jfields,
        num_acc=5, box=box, cfg=cfg, ranges=ranges, lists=lists,
        i_offset=i_offset, interpret=interpret, sym_jf=sym)
    return f(ax), f(ay), f(az), f(du), jnp.min(f(dt_i)), occ


# ---------------------------------------------------------------------------
# VE pipeline ops (sph/hydro_ve counterparts with the search fused in).
# The reference's flagship propagator is VE (main/src/propagator/
# ve_hydro.hpp:51); every op below mirrors its hydro_ve kernel
# (xmass_kern.hpp, ve_def_gradh_kern.hpp, divv_curlv_kern.hpp,
# av_switches_kern.hpp, momentum_energy_kern.hpp) with the same
# precombined-ratio strategy as the std momentum op.
# ---------------------------------------------------------------------------


@named_phase("xmass")
def pallas_xmass(
    x, y, z, h, m, sorted_keys, box: Box, const, cfg: NeighborConfig,
    ranges=None, interpret: bool = False, jdata=None, i_offset=0,
    lists=None,
):
    """Generalized volume element xm_i = m_i / rho0_i (xmass_kern.hpp:50-79)
    + neighbor counts. rho0 is exactly the std kernel-summed density, so
    this delegates to pallas_density. Returns (xm (n,), nc (n,), occ)."""
    rho0, nc, occ = pallas_density(
        x, y, z, h, m, sorted_keys, box, const, cfg,
        ranges=ranges, interpret=interpret, jdata=jdata, i_offset=i_offset,
        lists=lists,
    )
    return m / rho0, nc, occ


@named_phase("gradh")
def pallas_ve_def_gradh(
    x, y, z, h, m, xm, sorted_keys, box: Box, const, cfg: NeighborConfig,
    ranges=None, interpret: bool = False, jdata=None, i_offset=0,
    lists=None,
):
    """VE normalization kx + grad-h correction (ve_def_gradh_kern.hpp:43-90)
    with the search fused in. Returns ((kx, gradh), occupancy).

    Under shard_map, ``jdata = (x, y, z, m, xm)`` supplies the j-side
    candidate arrays (slab + halo annex) the ranges index into — same
    contract as pallas_density."""
    n = x.shape[0]
    wc = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    dc = kernel_dterh_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)

    if ranges is None and lists is None:
        ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg)

    def pair_body(geom, i_fields, j_fields, accs):
        kxs, who, wro = accs
        inv_h2 = i_fields[4]
        mj = j_fields[3]
        xmj = j_fields[4]
        u = geom.d2 * inv_h2
        w = _w_poly(u, wc)
        dterh = dterh_poly_eval(u, dc)
        mm = geom.mask
        kxs = kxs + jnp.where(mm, xmj * w, 0.0)
        who = who + jnp.where(mm, xmj * dterh, 0.0)
        wro = wro + jnp.where(mm, mj * dterh, 0.0)
        return kxs, who, wro

    def finalize(i_fields, accs, nc):
        hi = i_fields[3]
        mi = i_fields[5]
        xmi = i_fields[6]
        red = lambda a: jnp.sum(a, axis=1, keepdims=True)
        h3inv = 1.0 / (hi * hi * hi)
        kx = (xmi + red(accs[0])) * K * h3inv
        whomega = (-3.0 * xmi + red(accs[1])) * K * h3inv / hi
        wrho0 = (-3.0 * mi + red(accs[2])) * K * h3inv / hi
        whomega = whomega * mi / xmi + (kx - K * xmi * h3inv) * wrho0
        rho = kx * mi / xmi
        dhdrho = -hi / (rho * 3.0)
        gradh = 1.0 - dhdrho * whomega
        return (kx, gradh)

    i_fields = _prep_i(x, y, z, h, (1.0 / (h * h), m, xm), cfg.group)
    f = lambda a: a.reshape(-1)[:n]
    (kx, gradh, _nc), occ = _run_pair_op(
        "gradh", pair_body, finalize, i_fields, jdata or (x, y, z, m, xm),
        num_acc=3, box=box, cfg=cfg, ranges=ranges, lists=lists,
        i_offset=i_offset, interpret=interpret)
    return (f(kx), f(gradh)), occ


@named_phase("divv-curlv")
def pallas_iad_divv_curlv(
    x, y, z, vx, vy, vz, h, kx, xm,
    sorted_keys, box: Box, const, cfg: NeighborConfig,
    ranges=None, with_gradv: bool = False, interpret: bool = False,
    jdata=None, i_offset=0, lists=None,
):
    """IAD tensor AND velocity divergence/curl through the IAD gradient in
    ONE neighbour pass (iad_kern.hpp + divv_curlv_kern.hpp:43-120; the
    reference launches them as one kernel too, iad_divv_curlv.hpp),
    optionally the full symmetrized velocity-gradient tensor for avClean.

    The gradient reads the TARGET's matrix only, and linearly:
    dv_ab = sum_j xm_j v_ji,a tA_b with tA = -(C_i r_ij) W, so
    dv_ab = -sum_c C_i,bc M_ac with M_ac = sum_j xm_j W v_ji,a r_ij,c.
    The pass accumulates the six IAD moments tau and the nine raw
    moments M over the same geometry and W; the epilogue inverts tau
    into C once per target and contracts it with M.

    Returns ((c11..c33), outs, occupancy), outs = (divv, curlv[,
    dv11..dv33]); ``with_gradv`` changes the outputs only.

    Under shard_map, ``jdata = (x, y, z, xm/kx, xm, vx, vy, vz)`` supplies
    the j-side candidate arrays — same contract as pallas_density."""
    n = x.shape[0]
    wc = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)

    if ranges is None and lists is None:
        ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg)

    def pair_body(geom, i_fields, j_fields, accs):
        inv_h2 = i_fields[4]
        vxi, vyi, vzi = i_fields[6], i_fields[7], i_fields[8]
        volj, xmj, vxj, vyj, vzj = j_fields[3:8]
        w = _w_poly(geom.d2 * inv_h2, wc)
        # tau exactly as pallas_iad forms it (bitwise the same C)
        vw = jnp.where(geom.mask, volj * w, 0.0)
        taus = tuple(
            acc + t * vw for acc, t in zip(accs[:6], _iad_tau_terms(geom))
        )
        mw = jnp.where(geom.mask, xmj, 0.0) * w
        qs = (mw * (vxj - vxi), mw * (vyj - vyi), mw * (vzj - vzi))
        # M_ac in the order (x1, x2, x3, y1, ..., z3)
        qr = [q * r for q in qs for r in (geom.rx, geom.ry, geom.rz)]
        return taus + tuple(acc + t for acc, t in zip(accs[6:], qr))

    def finalize(i_fields, accs, nc):
        hi, knorm = i_fields[3], i_fields[5]
        red = lambda a: jnp.sum(a, axis=1, keepdims=True)
        cs = _iad_invert(hi, K, *(red(a) for a in accs[:6]))
        c11, c12, c13, c22, c23, c33 = cs
        crows = ((c11, c12, c13), (c12, c22, c23), (c13, c23, c33))
        # negated projection: the VE kernels use tA = -(C r) W
        # (iad_project sign=-1, divv_curlv_kern.hpp)
        m = [red(a) for a in accs[6:]]
        (dvx1, dvx2, dvx3), (dvy1, dvy2, dvy3), (dvz1, dvz2, dvz3) = (
            [-(cb[0] * m[a] + cb[1] * m[a + 1] + cb[2] * m[a + 2])
             for cb in crows]
            for a in (0, 3, 6)
        )
        divv = knorm * (dvx1 + dvy2 + dvz3)
        cx_ = dvz2 - dvy3
        cy_ = dvx3 - dvz1
        cz_ = dvy1 - dvx2
        curlv = knorm * jnp.sqrt(cx_ * cx_ + cy_ * cy_ + cz_ * cz_)
        if not with_gradv:
            return cs + (divv, curlv)
        return cs + (
            divv, curlv,
            knorm * dvx1, knorm * (dvx2 + dvy1), knorm * (dvx3 + dvz1),
            knorm * dvy2, knorm * (dvy3 + dvz2), knorm * dvz3,
        )

    knorm = K / (h * h * h * kx)
    i_fields = _prep_i(
        x, y, z, h, (1.0 / (h * h), knorm, vx, vy, vz), cfg.group
    )
    (*outs, _nc), occ = _run_pair_op(
        "divv-curlv", pair_body, finalize, i_fields,
        jdata or (x, y, z, xm / kx, xm, vx, vy, vz), num_acc=15, box=box,
        cfg=cfg, ranges=ranges, lists=lists, i_offset=i_offset,
        interpret=interpret)
    outs = tuple(a.reshape(-1)[:n] for a in outs)
    return outs[:6], outs[6:], occ


@named_phase("av-switches")
def pallas_av_switches(
    x, y, z, vx, vy, vz, h, c, kx, xm, divv, alpha,
    c11, c12, c13, c22, c23, c33,
    sorted_keys, box: Box, dt, const, cfg: NeighborConfig,
    ranges=None, interpret: bool = False, jdata=None, i_offset=0,
    lists=None,
):
    """Per-particle viscosity switch evolution (av_switches_kern.hpp:43-137)
    with the search fused in. Returns (alpha_new (n,), occupancy).

    Under shard_map, ``jdata = (x, y, z, c, vx, vy, vz, xm/kx, divv)``
    supplies the j-side candidate arrays — same contract as
    pallas_density."""
    n = x.shape[0]
    wc = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)
    alphamax = float(const.alphamax)
    alphamin = float(const.alphamin)
    decay_c = float(const.decay_constant)

    if ranges is None and lists is None:
        ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg)

    def pair_body(geom, i_fields, j_fields, accs):
        vs_max, gdx, gdy, gdz = accs
        (xi, yi, zi, hi, inv_h2, kh3, ci, divvi,
         c11i, c12i, c13i, c22i, c23i, c33i) = i_fields[:14]
        vxi, vyi, vzi = i_fields[14], i_fields[15], i_fields[16]
        (cx, cy, cz, cj, vxj, vyj, vzj, volj, divvj) = j_fields[:9]

        # negated projection (iad_project sign=-1, av_switches_kern.hpp)
        w = -_w_poly(geom.d2 * inv_h2, wc) * kh3
        vx_ij = vxi - vxj
        vy_ij = vyi - vyj
        vz_ij = vzi - vzj
        rv = geom.rx * vx_ij + geom.ry * vy_ij + geom.rz * vz_ij
        inv_dist = jax.lax.rsqrt(geom.d2)
        vsig = jnp.where(rv < 0.0, ci + cj - 3.0 * rv * inv_dist, 0.0)
        vs_max = jnp.maximum(vs_max, jnp.where(geom.mask, vsig, 0.0))

        tA1 = (c11i * geom.rx + c12i * geom.ry + c13i * geom.rz) * w
        tA2 = (c12i * geom.rx + c22i * geom.ry + c23i * geom.rz) * w
        tA3 = (c13i * geom.rx + c23i * geom.ry + c33i * geom.rz) * w
        factor = jnp.where(geom.mask, volj * (divvi - divvj), 0.0)
        gdx = gdx + factor * tA1
        gdy = gdy + factor * tA2
        gdz = gdz + factor * tA3
        return vs_max, gdx, gdy, gdz

    def finalize(i_fields, accs, nc):
        hi = i_fields[3]
        ci = i_fields[6]
        divvi = i_fields[7]
        alpha_i = i_fields[17]
        dt_b = i_fields[18]
        vs = jnp.max(accs[0], axis=1, keepdims=True)
        red = lambda a: jnp.sum(a, axis=1, keepdims=True)
        gdx, gdy, gdz = red(accs[1]), red(accs[2]), red(accs[3])
        vijsignal = jnp.maximum(vs, 1e-40 * ci)
        graddivv = jnp.sqrt(gdx * gdx + gdy * gdy + gdz * gdz)
        a_const = hi * hi * graddivv
        alphaloc = jnp.where(
            divvi < 0.0,
            alphamax * a_const
            / (a_const + hi * jnp.abs(divvi) + 0.05 * ci),
            0.0,
        )
        decay = hi / (decay_c * vijsignal)
        target = jnp.maximum(alphaloc, alphamin)
        alphadot = (target - alpha_i) / decay
        alpha_decayed = alpha_i + alphadot * dt_b
        return (jnp.where(alphaloc >= alpha_i, alphaloc, alpha_decayed),)

    # dt rides along as a constant i-field: one (1, 1, G) block DMA per
    # group (~256 B) — not worth a second engine scalar-operand mechanism
    dt_b = jnp.broadcast_to(jnp.asarray(dt, jnp.float32), x.shape)
    i_fields = _prep_i(
        x, y, z, h,
        (1.0 / (h * h), K / (h * h * h), c, divv,
         c11, c12, c13, c22, c23, c33, vx, vy, vz, alpha, dt_b),
        cfg.group,
    )
    (alpha_new, _nc), occ = _run_pair_op(
        "av-switches", pair_body, finalize, i_fields,
        jdata or (x, y, z, c, vx, vy, vz, xm / kx, divv), num_acc=4, box=box,
        cfg=cfg, ranges=ranges, lists=lists, i_offset=i_offset,
        interpret=interpret)
    return alpha_new.reshape(-1)[:n], occ


@named_phase("momentum-energy")
def pallas_momentum_energy_ve(
    x, y, z, vx, vy, vz, h, m, prho, c, kx, xm, alpha,
    c11, c12, c13, c22, c23, c33,
    sorted_keys, box: Box, const, cfg: NeighborConfig, nc=None,
    gradv=None, ranges=None, interpret: bool = False,
    jdata=None, i_offset=0, lists=None,
):
    """VE momentum + energy (momentum_energy_kern.hpp:65-222) with the
    search fused in: Atwood-ramped crossed/uncrossed volume elements,
    per-particle alpha viscosity, optional avClean gradV correction.
    Returns (ax, ay, az, du, min_dt, occupancy).

    The Atwood ramp's per-pair powers xm^(2-sigma) xm_j^sigma are
    evaluated as xm_i^2 exp(sigma (ln xm_j - ln xm_i)) with the logs
    precomputed per particle — one exp per pair side instead of pow().

    Under shard_map, ``jdata = (x, y, z, h, vx, vy, vz, c, alpha, m, xm,
    kx, prho, c11..c33[, gv11..gv33])`` supplies the RAW j-side candidate
    arrays (derived per-j ratios are computed here); the trailing gradv
    fields are present iff avClean. Same contract as pallas_density."""
    n = x.shape[0]
    wc = kernel_poly_coeffs(float(const.sinc_index), const.kernel_choice)
    K = float(const.K)
    k_cour = float(const.k_cour)
    at_min = float(const.at_min)
    at_max = float(const.at_max)
    ramp = float(const.ramp)
    av_clean = gradv is not None

    if ranges is None and lists is None:
        ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg)

    def pair_body(geom, i_fields, j_fields, accs):
        momx, momy, momz, energy, avisc_e, maxvs = accs
        (xi, yi, zi, hi, inv_h2i, inv_h3i, vxi, vyi, vzi, ci, ali,
         xmi, xm2i, lxi, rhoi, irhoi, prhoi,
         c11i, c12i, c13i, c22i, c23i, c33i) = i_fields[:23]
        (cx, cy, cz, inv_h2j, inv_h3j, vxj, vyj, vzj, cj, alj,
         mj, xmj, xm2j, lxj, rhoj, irhoj, prhoj,
         c11j, c12j, c13j, c22j, c23j, c33j) = j_fields[:23]

        u_i = geom.d2 * inv_h2i
        u_j = geom.d2 * inv_h2j
        # negative normalization bakes the VE kernels' tA = -(C r) W
        # projection sign into w (iad_project sign=-1)
        w_i = -_w_poly(u_i, wc) * inv_h3i
        w_j = -_w_poly(u_j, wc) * inv_h3j

        vx_ij = vxi - vxj
        vy_ij = vyi - vyj
        vz_ij = vzi - vzj
        rv = geom.rx * vx_ij + geom.ry * vy_ij + geom.rz * vz_ij
        inv_dist = jax.lax.rsqrt(geom.d2)

        if av_clean:
            eta_crit = i_fields[23]
            gvi = i_fields[24:30]
            gvj = j_fields[23:29]
            sym = lambda gv: (
                geom.rx * (gv[0] * geom.rx + gv[1] * geom.ry + gv[2] * geom.rz)
                + geom.ry * (gv[3] * geom.ry + gv[4] * geom.rz)
                + geom.rz * (gv[5] * geom.rz)
            )
            d1 = sym(gvi)
            d2_ = sym(gvj)
            eta_ab = jnp.minimum(jnp.sqrt(u_i), jnp.sqrt(u_j))
            eta_diff = 5.0 * (eta_ab - eta_crit)
            d3 = jnp.where(
                eta_ab < eta_crit, jnp.exp(-(eta_diff * eta_diff)), 1.0
            )
            A = jnp.where(d2_ != 0.0, d1 / d2_, 0.0)
            Ap1 = 1.0 + A
            phi = 0.5 * d3 * jnp.clip(4.0 * A / (Ap1 * Ap1), 0.0, 1.0)
            rv = rv - phi * (d1 + d2_)

        w_ij = rv * inv_dist
        # per-particle-alpha Monaghan AV (kernels.hpp:60-84)
        cij = ci + cj
        v_sig = 0.25 * (ali + alj) * cij - 2.0 * w_ij
        visc = jnp.where(w_ij < 0.0, -v_sig * w_ij, 0.0)
        maxvs = jnp.maximum(
            maxvs, jnp.where(geom.mask, 0.5 * cij - 2.0 * w_ij, 0.0)
        )

        tA1_i = (c11i * geom.rx + c12i * geom.ry + c13i * geom.rz) * w_i
        tA2_i = (c12i * geom.rx + c22i * geom.ry + c23i * geom.rz) * w_i
        tA3_i = (c13i * geom.rx + c23i * geom.ry + c33i * geom.rz) * w_i
        tA1_j = (c11j * geom.rx + c12j * geom.ry + c13j * geom.rz) * w_j
        tA2_j = (c12j * geom.rx + c22j * geom.ry + c23j * geom.rz) * w_j
        tA3_j = (c13j * geom.rx + c23j * geom.ry + c33j * geom.rz) * w_j

        # Atwood ramp between uncrossed (xm_i^2, xm_j^2) and crossed
        # (xm_i xm_j) volume elements
        atwood = jnp.abs(rhoi - rhoj) / (rhoi + rhoj)
        sigma = ramp * (atwood - at_min)
        dl = lxj - lxi
        a_ramp = xm2i * jnp.exp(sigma * dl)
        b_ramp = xm2j * jnp.exp(-sigma * dl)
        crossed = xmi * xmj
        a_mom = jnp.where(
            atwood < at_min, xm2i,
            jnp.where(atwood > at_max, crossed, a_ramp),
        )
        b_mom = jnp.where(
            atwood < at_min, xm2j,
            jnp.where(atwood > at_max, crossed, b_ramp),
        )

        a_visc = mj * irhoi * visc
        b_visc = mj * irhoj * visc
        avx = 0.5 * (a_visc * tA1_i + b_visc * tA1_j)
        avy = 0.5 * (a_visc * tA2_i + b_visc * tA2_j)
        avz = 0.5 * (a_visc * tA3_i + b_visc * tA3_j)
        mm = geom.mask
        avisc_e = avisc_e + jnp.where(
            mm, avx * vx_ij + avy * vy_ij + avz * vz_ij, 0.0
        )
        energy = energy + jnp.where(
            mm,
            mj * a_mom * (vx_ij * tA1_i + vy_ij * tA2_i + vz_ij * tA3_i),
            0.0,
        )
        mom_i = mj * prhoi * a_mom
        mom_j = mj * prhoj * b_mom
        momx = momx + jnp.where(mm, mom_i * tA1_i + mom_j * tA1_j + avx, 0.0)
        momy = momy + jnp.where(mm, mom_i * tA2_i + mom_j * tA2_j + avy, 0.0)
        momz = momz + jnp.where(mm, mom_i * tA3_i + mom_j * tA3_j + avz, 0.0)
        return momx, momy, momz, energy, avisc_e, maxvs

    def finalize(i_fields, accs, nc_):
        hi = i_fields[3]
        ci = i_fields[9]
        prhoi = i_fields[16]
        momx, momy, momz, energy, avisc_e, maxvs = accs
        red = lambda a: jnp.sum(a, axis=1, keepdims=True)
        avisc = jnp.maximum(red(avisc_e), 0.0)
        du = K * (prhoi * red(energy) + 0.5 * avisc)
        mv = jnp.max(maxvs, axis=1, keepdims=True)
        v = jnp.where(mv > 0.0, mv, ci)
        dt_i = k_cour * hi / v
        return (-K * red(momx), -K * red(momy), -K * red(momz), du, dt_i)

    inv_h2 = 1.0 / (h * h)
    inv_h3 = inv_h2 / h
    rho = kx * m / xm
    inv_rho = 1.0 / rho
    lx = jnp.log(xm)
    extra_i = [inv_h2, inv_h3, vx, vy, vz, c, alpha, xm, xm * xm, lx,
               rho, inv_rho, prho, c11, c12, c13, c22, c23, c33]
    if av_clean:
        eta_crit = jnp.cbrt(
            32.0 * np.pi / 3.0 / (nc.astype(jnp.float32) + 1.0)
        )
        extra_i = extra_i + [eta_crit] + list(gradv)
    if jdata is None:
        jfields = [x, y, z, inv_h2, inv_h3, vx, vy, vz, c, alpha, m, xm,
                   xm * xm, lx, rho, inv_rho, prho,
                   c11, c12, c13, c22, c23, c33]
        if av_clean:
            jfields = jfields + list(gradv)
    else:
        (xj, yj, zj, hj, vxj, vyj, vzj, cj, alj, mj, xmj, kxj, prhoj,
         j11, j12, j13, j22, j23, j33, *gvj) = jdata
        inv_h2j = 1.0 / (hj * hj)
        rhoj = kxj * mj / xmj
        jfields = [xj, yj, zj, inv_h2j, inv_h2j / hj, vxj, vyj, vzj, cj,
                   alj, mj, xmj, xmj * xmj, jnp.log(xmj), rhoj, 1.0 / rhoj,
                   prhoj, j11, j12, j13, j22, j23, j33]
        if av_clean:
            jfields = jfields + list(gvj)
    i_fields = _prep_i(x, y, z, h, tuple(extra_i), cfg.group)
    sym = 3 if getattr(const, "sym_pairs", True) else None
    f = lambda a: a.reshape(-1)[:n]
    (ax, ay, az, du, dt_i, _nc), occ = _run_pair_op(
        "momentum-energy-ve", pair_body, finalize, i_fields, jfields,
        num_acc=6, box=box, cfg=cfg, ranges=ranges, lists=lists,
        i_offset=i_offset, interpret=interpret, sym_jf=sym)
    return f(ax), f(ay), f(az), f(du), jnp.min(f(dt_i)), occ
