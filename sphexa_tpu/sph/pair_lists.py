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
    GroupRanges,
    _dma_rows,
    _prep_i,
    engine_fold,
    group_cell_ranges,
    pack_j_fields,
)


class PairLists(NamedTuple):
    """Build-time candidate structure shared by every list-walk pair op."""

    ranges: GroupRanges   # candidate runs at build time (skin-inflated)
    gidx: jax.Array       # (NG, S_cap, 128) int32 — per-chunk compacted
    #                       lane gather indices, PRE-ROTATED by the
    #                       staging fill (lanes [fill, fill+cnt) mod 256
    #                       carry the selected source lanes)
    cnt: jax.Array        # (NG, S_cap) int32 — selected lanes per chunk
    fill: jax.Array       # (NG, S_cap) int32 — staging fill before chunk
    emit: jax.Array       # (NG, S_cap) int32 0/1 — chunk completes a full
    #                       128-lane staging chunk
    tail: jax.Array       # (NG,) int32 — flush lanes after the last chunk
    overflow: jax.Array   # () int32 — 1 if any group needed > S_cap slots
    slot_need: jax.Array  # () int32 — most chunk slots any group needed
    #                       (overflow = slot_need > S_cap; the rebuild's
    #                       event reports it beside the cap)
    lanes_total: jax.Array  # () int64-ish f32 — sum of cnt (diagnostics)
    xb: jax.Array         # build positions + smoothing lengths: the
    yb: jax.Array         # validity reduction compares current state
    zb: jax.Array         # against these (Verlet skin condition)
    hb: jax.Array
    skin: jax.Array       # () f32 — the coverage slack baked into ranges

    @property
    def slot_cap(self) -> int:
        return self.gidx.shape[1]


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


def _mark_kernel_builder(cfg: NeighborConfig, slot_cap: int,
                         interpret: bool):
    """Mosaic mark pass: stream the build-time candidate runs once with a
    minimal body (inflated-bbox lane test) and write each chunk's lane
    BITS; counts/compaction/rotation are batched XLA post-passes."""
    R = _dma_rows(cfg.dma_cap)
    G = cfg.group

    def kernel(starts, lens, shx_r, shy_r, shz_r, ncells, skin_s,
               xi_r, yi_r, zi_r, hi_r, jref,
               gidx_out, total_out,
               buf, sems):
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
                # the kernel emits BITS only; counts, compaction indices
                # and pre-rotation are cheap batched XLA (a 128-wide sort
                # beats in-register rank conversion ~5x at build time)
                slot_i = slot_base + t

                @pl.when(slot_i < slot_cap)
                def _():
                    gidx_out[0, pl.ds(slot_i, 1)] = mask.astype(jnp.int32)

                return _c

            jax.lax.fori_loop(0, nch, chunk_body, 0)
            return slot_base + nch

        # dead slots must read as empty (no bits set)
        gidx_out[...] = jnp.zeros((1, slot_cap, 128), jnp.int32)
        total = jax.lax.fori_loop(0, nc_g, cell_body, 0)
        total_out[0, 0, 0] = total

    def call(ranges: GroupRanges, i_fields, j_packed, skin):
        num_groups = ranges.num_groups
        w3 = ranges.starts.shape[1]
        i_fields = [a.reshape(num_groups, 1, G) for a in i_fields]
        smem3 = lambda a: a.reshape(num_groups, 1, w3)
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
                pl.BlockSpec((1, 1, 1), lambda g: (0, 0, 0),
                             memory_space=pltpu.SMEM),  # skin
            ]
            + [
                pl.BlockSpec((1, 1, G), lambda g: (g, 0, 0))
                for _ in range(4)   # x, y, z, h
            ]
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, slot_cap, 128), lambda g: (g, 0, 0)),
                pl.BlockSpec((1, 1, 1), lambda g: (g, 0, 0),
                             memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, R, 8, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        out_shape = [
            jax.ShapeDtypeStruct((num_groups, slot_cap, 128), jnp.int32),
            jax.ShapeDtypeStruct((num_groups, 1, 1), jnp.int32),
        ]
        skin_s = jnp.asarray(skin, jnp.float32).reshape(1, 1, 1)
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(smem3(ranges.starts), smem3(ranges.lens),
          smem3(ranges.shift_x), smem3(ranges.shift_y),
          smem3(ranges.shift_z),
          ranges.ncells.reshape(num_groups, 1, 1), skin_s,
          *i_fields, j_packed)

    return call


def _prune_empty_chunks(ranges: GroupRanges, cnt, slot_cap: int):
    """Rebuild the candidate runs to exclude chunks with NO marked lane:
    every engine pass then neither DMAs nor iterates them (the measured
    per-chunk base cost is ~115 ns even when the math is skipped).

    New runs are maximal consecutive kept-chunk intervals WITHIN one
    original run, with exact particle bounds (the intersection of the
    original [s, s+len) with the kept rows) — never merged across
    original runs, so the in-run candidate mask admits exactly the
    original run's particles and no cross-run double counting can occur.
    Dropped chunks had no lane inside any group's inflated bbox, so no
    pair is lost. Returns (new_ranges, perm) where perm[k] is the
    ORIGINAL slot index of new slot k (for compacting the per-slot
    arrays; the compacted chunk sequence preserves original order, so
    staging fills computed on the zero-preserving cumsum are unchanged).
    """
    starts, lens = ranges.starts, ranges.lens
    ng, w3 = starts.shape
    s_idx = jnp.arange(slot_cap, dtype=jnp.int32)

    # slot -> (run w, chunk c, row, shift, exact bounds)
    off = starts % 128
    nch_w = jnp.where(lens > 0, (off + lens + 127) // 128, 0)  # (NG, W3)
    cum_w = jnp.cumsum(nch_w, axis=1) - nch_w                  # exclusive
    w_of_s = jnp.sum(
        (cum_w[:, None, :] <= s_idx[None, :, None]).astype(jnp.int32)
        & (nch_w[:, None, :] > 0), axis=2,
    ) - 1  # (NG, S_cap); -1 for slots before any run (none) / past-end dup
    w_of_s = jnp.clip(w_of_s, 0, w3 - 1)
    take = lambda a: jnp.take_along_axis(a, w_of_s, axis=1)
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
    # kept slots compacted to the front, original order preserved
    _, perm = jax.lax.sort(
        (jnp.where(kept, s_idx[None, :], INF),
         jnp.broadcast_to(s_idx[None, :], kept.shape)),
        num_keys=1, dimension=1, is_stable=True,
    )
    return new_ranges, perm


def build_pair_lists(
    x, y, z, h, sorted_keys, box: Box, cfg: NeighborConfig,
    skin, slot_cap: int, interpret: bool = False, table=None,
) -> PairLists:
    """Build the persistent lists from SFC-SORTED arrays (jit-safe).

    ``skin`` (traced f32) is the coverage slack; ``slot_cap`` the static
    per-group chunk-slot budget (sized at configure time, guarded by the
    ``overflow`` sentinel like every other static cap)."""
    if engine_fold(box, cfg):
        raise ValueError(
            "persistent lists need per-cell image shifts; the tiny-grid "
            "fold mode streams instead (lists are a large-N optimization)")
    ranges = group_cell_ranges(
        x, y, z, h, sorted_keys, box, cfg, table=table, radius_pad=skin,
    )
    i_fields = _prep_i(x, y, z, h, (), cfg.group)
    jp = pack_j_fields((x, y, z), cfg.dma_cap)
    mark = _mark_kernel_builder(cfg, slot_cap, interpret)
    bits, total = mark(ranges, i_fields, jp, skin)
    total = total.reshape(-1)
    cnt = jnp.sum(bits, axis=-1)

    # drop empty chunks from the runs (the engines then neither DMA nor
    # iterate them) and compact the per-slot arrays to the new order
    ranges, perm = _prune_empty_chunks(ranges, cnt, slot_cap)
    cnt = jnp.take_along_axis(cnt, perm, axis=1)
    bits = jnp.take_along_axis(bits, perm[:, :, None], axis=1)

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
    overflow = slot_need > slot_cap

    # PRE-ROTATED compaction indices in ONE batched 128-wide sort: lane
    # l's destination slot is (fill + rank-among-selected) % 128 when
    # marked, and the remaining slots (in wrap order) when not — all 128
    # keys are distinct, so sorting (dst, lane) scatters each lane to its
    # exact slot. This folds the staging rotation into the sort: both a
    # minor-axis take_along_axis here (measured 6.4 s at 1M — XLA's
    # pathological gather) and a per-chunk pltpu.roll in the walk kernel
    # (measured 90 ns/chunk) disappear.
    lane = jnp.broadcast_to(
        jnp.arange(128, dtype=jnp.int32), bits.shape
    )
    rank1 = jnp.cumsum(bits, axis=2) - bits   # rank among selected
    rank0 = lane - rank1                      # rank among unselected
    dst = jnp.where(
        bits > 0, fill[:, :, None] + rank1,
        fill[:, :, None] + cnt[:, :, None] + rank0,
    ) % 128
    _, rot = jax.lax.sort((dst, lane), num_keys=1, dimension=2)
    return PairLists(
        ranges=ranges, gidx=rot, cnt=cnt, fill=fill, emit=emit,
        tail=tail, overflow=overflow.astype(jnp.int32),
        slot_need=slot_need,
        lanes_total=jnp.sum(csum[:, -1].astype(jnp.float32)),
        xb=x, yb=y, zb=z, hb=h,
        skin=jnp.asarray(skin, jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _slot_need(x, y, z, h, sorted_keys, box, cfg, skin):
    ranges = group_cell_ranges(x, y, z, h, sorted_keys, box, cfg,
                               radius_pad=skin)
    off = ranges.starts % 128
    nch = jnp.where(ranges.lens > 0, (off + ranges.lens + 127) // 128, 0)
    return jnp.max(jnp.sum(nch, axis=1))


def estimate_slot_cap(
    x, y, z, h, sorted_keys, box: Box, cfg: NeighborConfig, skin: float,
    margin: float = 1.3, quantum: int = 8,
) -> int:
    """Host-side sizing of the static per-group chunk-slot budget from
    the current (SFC-sorted) distribution — configure-time, like cell
    caps; the build-time ``overflow`` sentinel guards outgrowth."""
    from sphexa_tpu.neighbors.cell_list import pad_cap

    need = int(_slot_need(x, y, z, h, sorted_keys, box, cfg,
                          jnp.float32(skin)))
    return pad_cap(need, margin, quantum)
