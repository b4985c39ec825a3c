"""The prologue reads each group's window from a GRID-ordered copy of the
cell-starts table as blocks (pallas_pairs._window_cell_ranges) instead of
one lookup per window slot through the SFC key.

Pinned here: ``GroupRanges`` and ``with_cells``' ``(c0, c1)`` equal, element
for element, a plain numpy prologue written below (loop the window's cells,
encode the key, read ``table[k]`` / ``table[k + 1]``, cull, compact or
merge) on every kind of box and window the callers have, and the lowering
holds no per-slot lookup and no per-slot key encode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from sphexa_tpu.devtools.primitives import walk_eqns
from sphexa_tpu.dtypes import KEY_BITS, KEY_DTYPE
from sphexa_tpu.neighbors.cell_list import NeighborConfig
from sphexa_tpu.sfc.box import BoundaryType, Box
from sphexa_tpu.sfc.hilbert import hilbert_encode
from sphexa_tpu.sfc.keys import compute_sfc_keys
from sphexa_tpu.sfc.morton import morton_encode
from sphexa_tpu.sph import pallas_pairs as pp

N = 2048
PER, OPEN = BoundaryType.periodic, BoundaryType.open
F = np.float32


def _cloud(box: Box, level: int, curve: str, edges: bool, seed: int = 7):
    """SFC-sorted cloud filling ``box`` (half in a blob, half uniform;
    ``edges``: a sixth pressed against each face of the x axis, so that
    window bases fall below 0 and on the last cell) + its cell table."""
    rng = np.random.default_rng(seed)
    u = np.concatenate([
        np.clip(rng.normal(0.4, 0.08, (N // 2, 3)), 0.0, 0.999),
        rng.random((N - N // 2, 3)) * 0.999,
    ])
    if edges:
        u[: N // 6, 0] = rng.random(N // 6) * 0.02
        u[N // 6: N // 3, 0] = 0.999 - rng.random(N // 6) * 0.02
    lo, hi = np.asarray(box.lo), np.asarray(box.hi)
    pos = (lo + u * (hi - lo)).astype(F)
    x, y, z = (jnp.asarray(pos[:, d]) for d in range(3))
    keys = compute_sfc_keys(x, y, z, box, curve=curve)
    order = jnp.argsort(keys)
    x, y, z, keys = x[order], y[order], z[order], keys[order]
    cid = (keys >> KEY_DTYPE(3 * (KEY_BITS - level))).astype(jnp.int32)
    table = jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        jnp.cumsum(jnp.zeros((1 << level) ** 3, jnp.int32).at[cid].add(1)),
    ]).astype(jnp.int32)
    return x, y, z, table


def _reference(x, y, z, h, table, box: Box, cfg: NeighborConfig,
               radius_pad: float = 0.0):
    """The prologue in plain numpy, one group and one window cell at a
    time. Returns (fields of GroupRanges as a dict, c0, c1, bases)."""
    x, y, z, h, table = (np.asarray(a) for a in (x, y, z, h, table))
    encode = hilbert_encode if cfg.curve == "hilbert" else morton_encode
    n, g, W, ncell = len(x), cfg.group, cfg.window, 1 << cfg.level
    ng, w3 = -(-n // g), W ** 3
    per = [b == PER for b in box.boundaries]
    box_lo = np.asarray(box.lo, F)
    lengths = np.asarray(box.hi, F) - box_lo
    edge = lengths / F(ncell)
    fold = any(per) and W >= ncell
    # key of every grid cell, through the program's own curve
    c = np.arange(ncell, dtype=np.uint32)
    cx, cy, cz = np.meshgrid(c, c, c, indexing="ij")
    key = np.asarray(encode(jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(cz),
                            bits=cfg.level)).astype(np.int64)

    out = {k: np.zeros((ng, w3), np.int32) for k in ("starts", "lens")}
    out.update({k: np.zeros((ng, w3), F)
                for k in ("shift_x", "shift_y", "shift_z")})
    out["ncells"] = np.zeros(ng, np.int32)
    c0, c1 = np.zeros((ng, w3), np.int32), np.zeros((ng, w3), np.int32)
    bases, window_ok, occupancy = [], True, 0
    for gi in range(ng):
        idx = np.minimum(np.arange(gi * g, (gi + 1) * g), n - 1)
        lo = np.stack([x[idx].min(), y[idx].min(), z[idx].min()])
        hi = np.stack([x[idx].max(), y[idx].max(), z[idx].max()])
        radius = F(2.0) * h[idx].max() + F(radius_pad)
        base = np.floor((lo - radius - box_lo) / edge).astype(np.int32)
        need = np.floor((hi + radius - box_lo) / edge).astype(np.int32)
        for d in range(3):
            if not per[d]:
                base[d] = np.clip(base[d], 0, max(0, ncell - W))
                need[d] = min(need[d], ncell - 1)
        window_ok &= bool(np.all((need - base + 1 <= W) | (W >= ncell)))
        bases.append(base.copy())
        kept = []  # (start, len, shift(3), key) in slot order
        for off in np.ndindex(W, W, W):
            cell = base + np.asarray(off, np.int32)
            if not all(off[d] < ncell if per[d] else 0 <= cell[d] < ncell
                       for d in range(3)):
                continue
            k = key[tuple(np.mod(cell, ncell))]
            raw = int(table[k + 1] - table[k])
            if raw <= 0:
                continue
            shift = np.zeros(3, F)
            if not fold:
                cell_lo = box_lo + cell.astype(F) * edge
                cell_hi = cell_lo + edge
                if not np.all((cell_hi >= lo - radius)
                              & (cell_lo <= hi + radius)):
                    continue
                shift = np.floor_divide(cell, ncell).astype(F) * lengths
            occupancy = max(occupancy, raw)
            kept.append((int(table[k]), min(raw, cfg.cap), shift, int(k)))
        if cfg.run_cap > 0:
            runs = []  # [start, end, shift, first cell, last cell]
            prev_end = None
            for s, ln, sh, k in sorted(kept, key=lambda t: t[0]):
                r = runs[-1] if runs else None
                if (r is not None and np.array_equal(sh, r[2])
                        and s - prev_end <= cfg.gap
                        and s + ln - r[0] <= cfg.run_cap):
                    r[1], r[4] = max(r[1], s + ln), max(r[4], k)
                else:
                    runs.append([s, s + ln, sh, k, k])
                prev_end = s + ln
            rows = [(r[0], r[1] - r[0], r[2], r[3], r[4]) for r in runs]
        else:
            rows = [(s, ln, sh, k, k) for s, ln, sh, k in kept]
        out["ncells"][gi] = len(rows)
        for w, (s, ln, sh, k0, k1) in enumerate(rows):
            out["starts"][gi, w], out["lens"][gi, w] = s, ln
            out["shift_x"][gi, w], out["shift_y"][gi, w], out["shift_z"][gi, w] = sh
            c0[gi, w], c1[gi, w] = k0, k1
    out["occupancy"] = np.int32(occupancy if window_ok else cfg.cap + 1)
    out["boxl"] = np.where(per, lengths, F(1e30)).astype(F)
    return out, c0, c1, np.stack(bases)


def _box(kind: str) -> Box:
    if kind == "stretched":  # windshock-cooling-4m's 4:1:1 periodic box
        return Box.create(0.0, 4.0, 0.0, 1.0, 0.0, 1.0, boundary=PER)
    bounds = {"periodic": PER, "open": OPEN,
              "mixed": (PER, OPEN, PER), "mixed-2": (OPEN, OPEN, PER)}[kind]
    return Box.create(-0.5, 0.5, boundary=bounds)


def _nbr(level, window, run_cap=0, curve="hilbert", group=16, cap=64, gap=8):
    return NeighborConfig(level=level, cap=cap, group=group, window=window,
                          run_cap=run_cap, gap=gap, curve=curve)


# id -> (box kind, NeighborConfig, h, radius_pad, edges)
CASES = {
    "periodic-cube": ("periodic", _nbr(3, 5), 0.04, 0.0, False),
    "periodic-cube-merged": ("periodic", _nbr(3, 5, 256), 0.04, 0.0, False),
    "open-box": ("open", _nbr(4, 5), 0.03, 0.0, False),
    "open-box-merged": ("open", _nbr(4, 5, 256), 0.03, 0.0, False),
    "open-box-cap-clipped": ("open", _nbr(2, 3, 256, cap=24), 0.03, 0.0, False),
    "mixed": ("mixed", _nbr(3, 5), 0.04, 0.0, True),
    "mixed-merged": ("mixed-2", _nbr(3, 5, 128), 0.04, 0.0, True),
    "stretched-411": ("stretched", _nbr(3, 5), 0.04, 0.0, False),
    "stretched-411-merged": ("stretched", _nbr(3, 5, 256), 0.04, 0.0, False),
    "fold-window-eq-ncell": ("periodic", _nbr(2, 4, 256), 0.05, 0.0, False),
    "fold-window-gt-ncell": ("periodic", _nbr(2, 5), 0.05, 0.0, False),
    "fold-mixed-gt-ncell": ("mixed", _nbr(2, 6, 256), 0.05, 0.0, True),
    "open-window-gt-ncell": ("open", _nbr(2, 5), 0.05, 0.0, False),
    "open-window-gt-ncell-merged": ("open", _nbr(2, 6, 256), 0.05, 0.0, False),
    "periodic-base-wraps": ("periodic", _nbr(3, 4, group=8), 0.01, 0.0, True),
    "periodic-base-wraps-merged":
        ("periodic", _nbr(3, 4, 128, group=8), 0.01, 0.0, True),
    "radius-pad": ("periodic", _nbr(3, 6), 0.03, 0.03, False),
    "radius-pad-open-merged": ("open", _nbr(3, 6, 256), 0.03, 0.03, False),
    "morton": ("periodic", _nbr(3, 5, curve="morton"), 0.04, 0.0, False),
    "morton-open-merged": ("open", _nbr(3, 5, 256, "morton"), 0.04, 0.0, True),
    "window-9-level-4": ("periodic", _nbr(4, 9, 256, group=64), 0.05, 0.0, False),
}


def _check(ranges, cells, ref):
    want, c0, c1, _ = ref
    for name, value in want.items():
        np.testing.assert_array_equal(
            np.asarray(getattr(ranges, name)), value, err_msg=name)
    np.testing.assert_array_equal(np.asarray(cells[0]), c0, err_msg="c0")
    np.testing.assert_array_equal(np.asarray(cells[1]), c1, err_msg="c1")


@pytest.mark.parametrize("case", sorted(CASES))
def test_block_lookup_equals_plain_reference(case):
    kind, nbr, hval, radius_pad, edges = CASES[case]
    box = _box(kind)
    x, y, z, table = _cloud(box, nbr.level, nbr.curve, edges)
    h = jnp.full(N, hval, F)
    ref = _reference(x, y, z, h, table, box, nbr, radius_pad)

    ranges, cells = jax.jit(lambda *f: pp.group_cell_ranges(
        *f, None, box, nbr, table=table, radius_pad=radius_pad,
        with_cells=True))(x, y, z, h)
    _check(ranges, cells, ref)
    # the table built inside from the keys, and no cells asked for
    keys = compute_sfc_keys(x, y, z, box, curve=nbr.curve)
    plain = jax.jit(lambda *f: pp.group_cell_ranges(
        *f, box, nbr, radius_pad=radius_pad))(x, y, z, h, keys)
    for a, b in zip(plain, ranges):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the regime each case is here for
    ncell, bases = 1 << nbr.level, ref[3]
    assert (np.asarray(ranges.lens) > 0).sum() > 200
    assert int(ranges.occupancy) <= N  # the window guard holds
    if case.startswith("periodic-base-wraps"):
        assert (bases[:, 0] < 0).any() and (bases[:, 0] == ncell - 1).any()
    if "gt-ncell" in case:
        assert nbr.window > ncell
    if "fold" in case:
        assert pp.engine_fold(box, nbr)
    if "cap-clipped" in case:
        assert int(ranges.occupancy) > nbr.cap
    if nbr.run_cap:
        assert (ref[2] > ref[1]).any(), "no merged run spans two cells"


def test_block_lookup_global_table_under_shard_map():
    """Slab-local groups against the GLOBAL table, as the mesh's halo
    stage calls it: the padded grid is derived from the (replicated)
    table, the block corners from the (varying) slab."""
    from sphexa_tpu.parallel import make_mesh

    P = 2
    box, nbr = _box("mixed"), _nbr(3, 5, 256)
    x, y, z, table = _cloud(box, nbr.level, nbr.curve, True)
    h = jnp.full(N, 0.04, F)
    S = N // P

    def stage(table, x, y, z, h):
        ranges, (c0, c1) = pp.group_cell_ranges(
            x, y, z, h, None, box, nbr, table=table, with_cells=True)
        lift = lambda a: jnp.asarray(a)[None]
        return ranges._replace(occupancy=lift(ranges.occupancy),
                               boxl=lift(ranges.boxl)), (c0, c1)

    Pp, Pr = PartitionSpec("p"), PartitionSpec()
    ranges, cells = jax.jit(jax.shard_map(
        stage, mesh=make_mesh(P), in_specs=(Pr, Pp, Pp, Pp, Pp),
        out_specs=Pp))(table, x, y, z, h)
    ng = -(-S // nbr.group)
    for k in range(P):
        sl = slice(k * S, (k + 1) * S)
        ref = _reference(x[sl], y[sl], z[sl], h[sl], table, box, nbr)
        gs = slice(k * ng, (k + 1) * ng)
        slab = pp.GroupRanges(*(
            np.asarray(a)[k] if name in ("occupancy", "boxl")
            else np.asarray(a)[gs]
            for name, a in zip(pp.GroupRanges._fields, ranges)))
        _check(slab, tuple(np.asarray(c)[gs] for c in cells), ref)


# ---------------------------------------------------------------------------
# lowering guard: the CPU cannot see speed, the jaxpr can see indices
# ---------------------------------------------------------------------------


def _gather_indices(jaxpr):
    """Index vectors of every gather in the program, counted together."""
    return sum(
        int(np.prod(e.invars[1].aval.shape[:-1], dtype=np.int64))
        for e in walk_eqns(jaxpr) if e.primitive.name == "gather"
    )


def _wide_key_ops(jaxpr, shape):
    """Equations that produce a key-typed array of the (NG, W3) slot shape:
    what a per-slot curve encode is made of."""
    return [
        e.primitive.name for e in walk_eqns(jaxpr)
        for v in e.outvars
        if getattr(v.aval, "dtype", None) == KEY_DTYPE
        and tuple(v.aval.shape) == shape
    ]


@pytest.mark.parametrize("with_cells", [False, True], ids=["plain", "cells"])
@pytest.mark.parametrize("periodic", [False, True], ids=["open", "periodic"])
def test_table_path_indexes_blocks_not_slots(periodic, with_cells):
    """Two gathers of NG x W^3 indices each (187 of the 2086 ms mesh
    gravity step, 78 of a 275 ms list rebuild before PR 37) cannot come
    back unnoticed: the table path indexes the grid once (ncell^3) and the
    windows by block (at most one z-row, W^2, a group), and encodes no key
    per window slot."""
    level, window = 3, 5
    nbr = _nbr(level, window, 256)
    box = _box("periodic" if periodic else "open")
    x, y, z, table = _cloud(box, level, "hilbert", False)
    h = jnp.full(N, 0.04, F)
    ng, ncell = -(-N // nbr.group), 1 << level
    jaxpr = jax.make_jaxpr(lambda *f: pp.group_cell_ranges(
        *f, None, box, nbr, table=table, with_cells=with_cells))(x, y, z, h).jaxpr
    count = _gather_indices(jaxpr)
    assert 0 < count <= ncell ** 3 + ng * window ** 2, count
    assert 2 * ng * window ** 3 > ncell ** 3 + ng * window ** 2  # the bound bites
    assert not _wide_key_ops(jaxpr, (ng, window ** 3))
    # the guard sees the per-slot encode where it is: the deep-grid
    # fallback (no table) still searches by key
    deep = _nbr(6, window, 256)
    keys = compute_sfc_keys(x, y, z, box)
    old = jax.make_jaxpr(lambda *f: pp.group_cell_ranges(
        *f, box, deep))(x, y, z, h, keys).jaxpr
    assert _wide_key_ops(old, (ng, window ** 3))
