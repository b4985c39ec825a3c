"""Cell-list neighbor search over SFC-sorted particle arrays.

Design (SURVEY.md §7 'cell-list/gather formulation', reshaped for TPU
memory bandwidth like the reference's warp-centric traversal,
cstone/traversal/find_neighbors.cuh TravConfig):

1. Particles arrive sorted by SFC key. A uniform grid at octree level
   ``L`` is implied by the key hierarchy: the level-``L`` cell of a
   particle is the top ``3L`` bits of its key — cell membership ranges in
   the sorted array are two ``searchsorted`` calls, no bucket structure.
2. Particles are processed in *target groups* of ``group`` SFC-consecutive
   particles (the analog of the reference's 64-particle GPU targets,
   find_neighbors.cuh:45-82). Each group computes its bounding box once,
   expands it by the search radius, and gathers ONE shared candidate set
   from the static ``window^3`` cell block covering it — amortizing the
   range lookups and candidate gathers over the whole group instead of
   paying 27 gathers per particle.
3. Candidates are filtered by ``|r_ij| < 2 h_i``; the first ``ngmax`` hits
   per particle are compacted with a masked cumsum + scatter (matching the
   reference's first-found truncation semantics, findneighbors.hpp:96-172
   — no distance sort).

Correctness guards (all surfaced as diagnostics, re-checked by the
caller): cell occupancy <= cap, and the window block must cover every
group's search extent (``window_ok``); either failing triggers a
reconfiguration exactly like the reference's traversal-stack overflow.
"""

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sphexa_tpu.dtypes import KEY_BITS, KEY_DTYPE
from sphexa_tpu.sfc.box import Box, apply_pbc_xyz
from sphexa_tpu.sfc.hilbert import hilbert_encode
from sphexa_tpu.sfc.keys import coords_to_igrid
from sphexa_tpu.sfc.morton import morton_encode
from sphexa_tpu.util.phases import named_phase, stage_scope


@dataclasses.dataclass(frozen=True)
class NeighborConfig:
    """Static configuration of the neighbor search (hashable, jit-safe)."""

    level: int  # octree level of the cell grid
    cap: int  # max particles gathered per cell
    ngmax: int = 150  # max neighbors kept per particle (reference ngmax);
    # NOTE: only the list-building XLA path truncates at ngmax (the
    # reference's memory-bound semantics, findneighbors.hpp) — the pallas
    # engine sums over ALL neighbors within 2h (physically the more
    # accurate behavior; lists never materialize there)
    block: int = 2048  # particles per processing chunk (memory bound)
    curve: str = "hilbert"
    group: int = 64  # particles per target group (TravConfig targetSize)
    window: int = 4  # cells per dimension of the group candidate block
    # pallas engine: merge SFC-adjacent candidate cells into one streamed
    # run of at most run_cap slots, bridging key-space gaps up to ``gap``
    # particles (gap particles are legitimate extra candidates — masked by
    # the distance test, or genuine neighbors counted once). 0 disables.
    run_cap: int = 0
    gap: int = 0

    @property
    def num_candidates(self) -> int:
        return self.window**3 * self.cap

    @property
    def dma_cap(self) -> int:
        """Largest candidate span one kernel DMA must cover (cells when
        merging is off, merged runs when on). SINGLE source of truth for
        the engine's transfer shape and the packed-buffer tail pad."""
        return max(self.cap, self.run_cap)


def choose_grid_level(box_lengths, h_max: float) -> int:
    """Deepest grid level whose cell edge still covers the 2h search radius.

    With cell edge >= 2*h_max, a group window of
    ceil(extent/edge) + 2 cells per dimension covers every interaction
    sphere of the group.
    """
    min_extent = float(np.min(np.asarray(box_lengths)))
    if h_max <= 0:
        return KEY_BITS
    level = int(np.floor(np.log2(min_extent / (2.0 * h_max))))
    return max(1, min(KEY_BITS, level))


def pad_cap(occ: int, margin: float = 1.3, quantum: int = 8) -> int:
    """Pad an observed max cell occupancy into a static cap: the margin
    absorbs particle motion between reconfigurations; the quantum rounds up
    so small occupancy drifts do not change the static cap (and thus do
    not recompile). SINGLE source of truth for the sizing constants."""
    return max(quantum, int(np.ceil(occ * margin / quantum) * quantum))


def window_cells(ext: float, radius: float, edge: float, ncell: int,
                 margin_cells: int = 1) -> int:
    """Cells needed along one dimension to cover a group extent + search
    radius, clamped to the grid (whole-grid coverage always suffices)."""
    return min(int(np.ceil((ext + radius) / edge)) + 1 + margin_cells, ncell)


def estimate_cell_cap(keys, level: int, margin: float = 1.3, quantum: int = 8) -> int:
    """Max level-``level`` cell occupancy of ``keys``, padded with slack
    (host-side helper run at (re)configuration time)."""
    shift = 3 * (KEY_BITS - level)
    cells = np.asarray(keys, dtype=np.uint64) >> np.uint64(shift)
    occ = int(np.bincount(cells.astype(np.int64)).max()) if len(cells) else 1
    return pad_cap(occ, margin, quantum)


def estimate_group_window(
    x, y, z, h, box_lengths, level: int, group: int, margin_cells: int = 1
) -> int:
    """Cells per dimension needed to cover any group's search extent.

    Host-side sizing: per dimension, ceil((max group extent + 2*2h)/edge_d)
    + 1 (+margin for drift), clamped to the grid size — a window spanning
    the whole grid always covers (essential for thin-slab boxes whose
    per-dim edges differ wildly). The window_ok diagnostic remains the
    runtime guard.
    """
    ncell = 1 << level
    edges = np.asarray(box_lengths, np.float64) / ncell  # (3,)
    n = len(np.asarray(x))
    ng = -(-n // group)
    pad = ng * group - n
    radius = 2.0 * 2.0 * float(np.max(np.asarray(h)))
    need = 1
    for a, edge in zip((x, y, z), edges):
        a = np.asarray(a)
        if pad:
            a = np.concatenate([a, np.repeat(a[-1], pad)])
        g = a.reshape(ng, group)
        ext = float((g.max(axis=1) - g.min(axis=1)).max())
        need = max(need, window_cells(ext, radius, edge, ncell, margin_cells))
    return need


@functools.lru_cache(maxsize=None)
def _window_offsets(window: int) -> np.ndarray:
    """(window^3, 3) integer offsets of the group candidate cell block."""
    r = np.arange(window, dtype=np.int32)
    return np.stack(np.meshgrid(r, r, r, indexing="ij"), axis=-1).reshape(-1, 3)


@functools.partial(jax.jit, static_argnames=("cfg",))
@named_phase("neighbors")
def find_neighbors(
    x, y, z, h, sorted_keys, box: Box, cfg: NeighborConfig
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Neighbor lists for all particles.

    Arguments are the SFC-sorted particle arrays and their keys. Returns:

    - ``nidx`` (N, ngmax) int32: neighbor indices, first-found order;
      invalid slots hold the particle's own index (safe to gather, must be
      masked);
    - ``nmask`` (N, ngmax) bool: validity of each slot;
    - ``nc`` (N,) int32: true neighbor count within 2h (excluding self, may
      exceed ngmax — used by the smoothing-length update like the
      reference's nc field);
    - ``occupancy`` () int32: an overflow diagnostic encoding BOTH guards:
      the densest cell seen, or cap+1 if some group's search extent
      outgrew the window block. If > cfg.cap the config must be re-sized
      and the search re-run.
    """
    n = x.shape[0]
    level = cfg.level
    shift = KEY_DTYPE(3 * (KEY_BITS - level))
    ncell = 1 << level
    encode = hilbert_encode if cfg.curve == "hilbert" else morton_encode
    edge = box.lengths / ncell  # (3,)
    periodic = box.periodic_mask

    g = cfg.group
    num_groups = -(-n // g)
    idx_groups = jnp.arange(num_groups * g, dtype=jnp.int32).reshape(num_groups, g)
    offsets = jnp.asarray(_window_offsets(cfg.window))  # (W3, 3)

    def process_group(idx):
        idx = jnp.minimum(idx, n - 1)  # padded tail re-processes the last row
        gx, gy, gz, gh = x[idx], y[idx], z[idx], h[idx]

        with stage_scope("neighbors", "windows"):
            lo = jnp.stack([jnp.min(gx), jnp.min(gy), jnp.min(gz)])
            hi = jnp.stack([jnp.max(gx), jnp.max(gy), jnp.max(gz)])
            radius = 2.0 * jnp.max(gh)
            # first cell of the window block: floor((lo - 2h) / edge)
            box_lo = jnp.stack([box.lo[0], box.lo[1], box.lo[2]])
            base = jnp.floor((lo - radius - box_lo) / edge).astype(jnp.int32)
            # window must cover hi + radius: last needed cell index
            need = jnp.floor((hi + radius - box_lo) / edge).astype(jnp.int32)
            # open dims: slide the window inside the existing grid (coverage
            # is never lost — cells outside [0, ncell) don't exist); a window
            # spanning the whole grid always covers
            base = jnp.where(
                periodic, base, jnp.clip(base, 0, max(0, ncell - cfg.window))
            )
            need_eff = jnp.where(periodic, need, jnp.minimum(need, ncell - 1))
            window_ok = jnp.all(
                (need_eff - base + 1 <= cfg.window) | (cfg.window >= ncell)
            )

            cells = base[None, :] + offsets  # (W3, 3)
            wrapped = jnp.mod(cells, ncell)
            in_range = (cells >= 0) & (cells < ncell)
            # periodic dims wrap but must not alias (offsets beyond the grid
            # revisit the same cells — drop them); open dims clip-and-exclude
            unique = offsets < ncell
            cell_ok = jnp.all(
                jnp.where(periodic[None, :], unique, in_range), axis=-1
            )  # (W3,)
            cells = jnp.where(periodic[None, :], wrapped, jnp.clip(cells, 0, ncell - 1))

            ckey = encode(
                cells[:, 0].astype(KEY_DTYPE),
                cells[:, 1].astype(KEY_DTYPE),
                cells[:, 2].astype(KEY_DTYPE),
                bits=level,
            )
        with stage_scope("neighbors", "cell-ranges"):
            start = jnp.searchsorted(sorted_keys, ckey << shift).astype(jnp.int32)
            end = jnp.searchsorted(sorted_keys, (ckey + KEY_DTYPE(1)) << shift).astype(
                jnp.int32
            )
            occupancy = jnp.max(end - start)

        cand = start[:, None] + jnp.arange(cfg.cap, dtype=jnp.int32)  # (W3, cap)
        cand_ok = (cand < end[:, None]) & cell_ok[:, None]
        cand = jnp.clip(cand, 0, n - 1).reshape(-1)  # (C,) shared by the group
        cand_ok = cand_ok.reshape(-1)

        cx, cy, cz = x[cand], y[cand], z[cand]  # ONE gather for the whole group
        dx, dy, dz = apply_pbc_xyz(
            box,
            gx[:, None] - cx[None, :],
            gy[:, None] - cy[None, :],
            gz[:, None] - cz[None, :],
        )
        d2 = dx * dx + dy * dy + dz * dz  # (g, C)

        r2 = (2.0 * gh) ** 2
        hit = cand_ok[None, :] & (d2 < r2[:, None]) & (cand[None, :] != idx[:, None])

        # first-ngmax compaction WITHOUT scatter (TPU scatters serialize):
        # inclusive hit-count cumsum per row, then the k-th neighbor is the
        # first candidate slot where the count reaches k+1 — a batched
        # binary search (pure gathers)
        csum = jnp.cumsum(hit.astype(jnp.int32), axis=-1)  # (g, C)
        nc = csum[:, -1]
        ks = jnp.arange(1, cfg.ngmax + 1, dtype=jnp.int32)  # (ngmax,)
        slot = jax.vmap(
            lambda row: jnp.searchsorted(row, ks, side="left")
        )(csum)  # (g, ngmax)
        nmask = ks[None, :] <= nc[:, None]
        nidx = jnp.where(
            nmask, cand[jnp.minimum(slot, cand.shape[0] - 1)], idx[:, None]
        )
        return nidx, nmask, nc, occupancy, window_ok

    # honor the caller's transient-memory bound: ~block particles per chunk
    chunk = max(1, cfg.block // g)
    pad_groups = -(-num_groups // chunk) * chunk - num_groups
    idx_groups = jnp.concatenate(
        [idx_groups, jnp.broadcast_to(idx_groups[-1:], (pad_groups, g))]
    ) if pad_groups else idx_groups
    batched = idx_groups.reshape(-1, chunk, g)

    def one_chunk(ig):
        return jax.vmap(process_group)(ig)

    nidx, nmask, nc, occ, wok = jax.lax.map(one_chunk, batched)
    nidx = nidx.reshape(-1, cfg.ngmax)[:n]
    nmask = nmask.reshape(-1, cfg.ngmax)[:n]
    nc = nc.reshape(-1)[:n]
    # fold the window guard into the occupancy diagnostic: a blown window
    # reports cap+1, forcing the caller to reconfigure
    occupancy = jnp.where(jnp.all(wok), jnp.max(occ), jnp.int32(cfg.cap + 1))
    return nidx, nmask, nc, occupancy
