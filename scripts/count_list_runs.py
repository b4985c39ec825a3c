"""Count, on the CPU, what a pass over the persistent pair lists fetches
and visits: runs, kept chunks, the runs' length histogram and the rows a
group's copies bring in, for the shipped run tile and any others.

    python3 scripts/count_list_runs.py [--init sedov|noh|wind-shock|evrard]
        [--side 160] [--run-rows 13,8,6,4,3]

The configuration is the program's own for a run with lists
(``make_propagator_config(..., backend="pallas", use_lists=True)``: level,
window, group, run_cap, the skin 0.2 x 2 h_max) on the IC; the candidate
runs are ``group_cell_ranges``'; a chunk's marked lanes are counted in
plain jnp with the mark kernels' test (``pair_lists._stream_marks``: lanes
of the run inside the group's bbox inflated by 2 max h + skin), a block of
groups at a time, because the interpreted Mosaic count pass does not end at
64,000 groups; the runs are ``pair_lists._prune_empty_chunks``' at each
``--run-rows`` (13 = the un-cut runs of run_cap 1536). ``run_fill`` = kept
chunks / (runs x run_rows) is what the ``rebuild_lists`` event's
``chunks_live / (runs_live x run_rows)`` and the benchmark's
``list_run_fill`` read. ISSUE 40 was sized on these counts. Counts, never
times: ~2 min at --side 160.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: groups counted at a time (a block's lanes are (BLOCK, slot_cap, 128))
BLOCK = 2048


def marked_lanes(ranges, i_fields, xyz_rows, skin, slot_cap: int):
    """(groups, slot_cap) marked lanes of every candidate chunk: the mark
    kernels' test in plain jnp."""
    import jax.numpy as jnp

    from sphexa_tpu.sph.pair_lists import _run_chunks

    starts, lens = ranges.starts, ranges.lens
    nch_w = _run_chunks(starts, lens)
    cum_w = jnp.cumsum(nch_w, axis=1) - nch_w
    s3 = jnp.arange(slot_cap, dtype=jnp.int32)[None, :, None]
    in_run = (cum_w[:, None, :] <= s3) & (s3 < (cum_w + nch_w)[:, None, :])
    take = lambda a: jnp.sum(
        jnp.where(in_run, a[:, None, :], jnp.zeros((), a.dtype)), axis=2)
    s_w, ln_w = take(starts), take(lens)
    row = s_w // 128 + (s3[:, :, 0] - take(cum_w))
    live = jnp.any(in_run, axis=2)
    row = jnp.where(live, row, 0)
    cand = row[:, :, None] * 128 + jnp.arange(128, dtype=jnp.int32)
    mask = live[:, :, None] & (cand >= s_w[:, :, None]) & (
        cand < (s_w + ln_w)[:, :, None])
    xi, yi, zi, hi = i_fields
    r = (2.0 * jnp.max(hi, axis=1) + skin)[:, None, None]
    for rows, ti, sh in zip(xyz_rows, (xi, yi, zi),
                            (ranges.shift_x, ranges.shift_y,
                             ranges.shift_z)):
        j = rows[row] + take(sh)[:, :, None]
        mask = mask & (j >= jnp.min(ti, axis=1)[:, None, None] - r) & (
            j <= jnp.max(ti, axis=1)[:, None, None] + r)
    return jnp.sum(mask, axis=2).astype(jnp.int32)


def count(init: str, side: int, run_rows, skin_rel: float = 0.2):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.propagator import _sort_by_keys
    from sphexa_tpu.simulation import make_propagator_config
    from sphexa_tpu.sph import pallas_pairs as pp
    from sphexa_tpu.sph.pair_lists import (
        _prune_empty_chunks, _run_chunks, estimate_list_caps)

    state, box, const = make_initializer(init)(side)
    cfg = make_propagator_config(state, box, const, backend="pallas",
                                 use_lists=True, list_skin_rel=skin_rel)
    nbr = cfg.nbr
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    x, y, z, h = ss.x, ss.y, ss.z, ss.h
    skin = jnp.float32(skin_rel * 2.0 * float(jnp.max(h)))
    slot_cap, slots_cap = estimate_list_caps(x, y, z, h, keys, box, nbr,
                                             float(skin))
    ranges = pp.group_cell_ranges(x, y, z, h, keys, box, nbr,
                                  radius_pad=skin)
    i_fields = pp._prep_i(x, y, z, h, (), nbr.group)
    n = x.shape[0]
    uncut = pp._dma_rows(nbr.dma_cap)  # rows a run streams at most
    nrow = -(-n // 128) + uncut        # pack_j_fields' rows, tail pad too
    xyz_rows = [jnp.zeros(nrow * 128, jnp.float32).at[:n].set(a)
                .reshape(nrow, 128) for a in (x, y, z)]
    ng = ranges.num_groups
    run_rows = [min(r, uncut) for r in run_rows]

    @jax.jit
    def block(rng, i_f):
        cnt = marked_lanes(rng, i_f, xyz_rows, skin, slot_cap)
        out = {}
        for rr in run_rows:
            new, _ = _prune_empty_chunks(rng, cnt, slot_cap, rr)
            nch = _run_chunks(new.starts, new.lens)
            out[rr] = (jnp.sum(new.ncells),
                       jnp.bincount(nch.reshape(-1), length=uncut + 1))
        return (jnp.sum(cnt > 0), jnp.sum(cnt),
                jnp.sum(_run_chunks(rng.starts, rng.lens)), out)

    kept = lanes = cand = 0
    runs = {rr: 0 for rr in run_rows}
    hist = {rr: np.zeros(uncut + 1, np.int64) for rr in run_rows}
    for g0 in range(0, ng, BLOCK):
        g1 = min(g0 + BLOCK, ng)
        pad = BLOCK - (g1 - g0)  # one compiled shape: pad with empty groups
        cut = lambda a: jnp.pad(a[g0:g1], ((0, pad),) + ((0, 0),) * (
            a.ndim - 1))
        rng = ranges._replace(
            starts=cut(ranges.starts), lens=cut(ranges.lens),
            shift_x=cut(ranges.shift_x), shift_y=cut(ranges.shift_y),
            shift_z=cut(ranges.shift_z), ncells=cut(ranges.ncells))
        k, l, c, out = block(rng, [cut(a) for a in i_fields])
        kept, lanes, cand = kept + int(k), lanes + int(l), cand + int(c)
        for rr, (nr, hh) in out.items():
            runs[rr] += int(nr)
            hist[rr] += np.asarray(hh)
    res = {
        "init": init, "side": side, "particles": int(n), "groups": int(ng),
        "level": nbr.level, "window": nbr.window, "group": nbr.group,
        "run_cap": nbr.run_cap, "slot_cap": slot_cap,
        "slots_cap": slots_cap,
        "candidate_chunks_group": round(cand / ng, 2),
        "kept_chunks_group": round(kept / ng, 2),
        "kept_lanes_group": round(lanes / ng, 1),
        "staging_chunks_group": round(lanes / 128 / ng, 2),
        "run_rows": {},
    }
    for rr in run_rows:
        res["run_rows"][str(rr)] = {
            "runs_group": round(runs[rr] / ng, 2),
            "chunks_run": round(kept / max(runs[rr], 1), 2),
            "rows_fetched_group": round(runs[rr] * rr / ng, 1),
            "run_fill": round(kept / max(runs[rr] * rr, 1), 4),
            "runs_of_1..n_chunks": [int(v) for v in hist[rr][1:rr + 1]],
        }
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--init", default="sedov",
                    choices=["sedov", "noh", "wind-shock", "evrard"])
    ap.add_argument("--side", type=int, default=160)
    ap.add_argument("--run-rows", default=None,
                    help="comma-separated run tiles to count (default: the "
                    "un-cut width, 8, 6, 4, 3 and the shipped one)")
    args = ap.parse_args(argv)
    from sphexa_tpu.sph.pallas_pairs import LIST_RUN_ROWS

    rows = ([int(v) for v in args.run_rows.split(",")] if args.run_rows
            else sorted({13, 8, 6, 4, 3, LIST_RUN_ROWS}, reverse=True))
    print(json.dumps(count(args.init, args.side, rows), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
