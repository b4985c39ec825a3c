"""The sparse halo stage takes each candidate run's first and last grid
cell from the prologue that built the run (group_cell_ranges
``with_cells``) instead of searching the cell table for them again.

Pinned here: the carried brackets equal the searched ones bit for bit
(every active run, merged or not, open or periodic, with empty cells and
with cells clipped at ``cap``, split at 2, 4 and 8 slabs), the stage
returns the same localized ranges either way on the virtual CPU mesh, and
neither the per-run search nor the payloads can come back unnoticed.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sphexa_tpu.devtools.primitives import CALL_PRIMS, walk_eqns
from sphexa_tpu.dtypes import KEY_BITS, KEY_DTYPE
from sphexa_tpu.neighbors.cell_list import NeighborConfig
from sphexa_tpu.parallel import exchange as ex
from sphexa_tpu.sfc.box import BoundaryType, Box
from sphexa_tpu.sfc.keys import compute_sfc_keys
from sphexa_tpu.sph import pallas_pairs as pp

N = 4096

# table kind -> (level, cap, gap): a deep grid under a clustered cloud
# leaves most cells empty; a shallow one under a small cap clips the
# blob's cells, and merged runs bridge the rows the clip left out
TABLES = {"empty-cells": (4, 64, 16), "cap-clipped": (3, 24, 32)}


def _cloud(periodic: bool, level: int, seed: int = 3):
    """SFC-sorted clustered cloud in the unit box + its cell-starts
    table: half the particles in a blob (dense cells, long merged runs),
    half uniform (sparse cells, empties in between)."""
    rng = np.random.default_rng(seed)
    blob = np.clip(rng.normal(0.35, 0.07, (N // 2, 3)), 0.0, 0.999)
    pos = np.concatenate([blob, rng.random((N - N // 2, 3)) * 0.999])
    x, y, z = (jnp.asarray(pos[:, d], jnp.float32) for d in range(3))
    box = Box.create(0.0, 1.0, boundary=(BoundaryType.periodic if periodic
                                         else BoundaryType.open))
    keys = compute_sfc_keys(x, y, z, box)
    order = jnp.argsort(keys)
    x, y, z, keys = x[order], y[order], z[order], keys[order]
    h = jnp.full(N, 0.03, jnp.float32)
    ncells = (1 << level) ** 3
    cid = (keys >> KEY_DTYPE(3 * (KEY_BITS - level))).astype(jnp.int32)
    table = jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        jnp.cumsum(jnp.zeros(ncells, jnp.int32).at[cid].add(1)),
    ]).astype(jnp.int32)
    return (x, y, z, h), box, table


def _eq(a, b, where=None):
    a, b = np.asarray(a), np.asarray(b)
    if where is not None:
        a, b = a[np.asarray(where)], b[np.asarray(where)]
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kind", sorted(TABLES))
@pytest.mark.parametrize("P", [2, 4, 8])
@pytest.mark.parametrize("periodic", [False, True], ids=["open", "periodic"])
@pytest.mark.parametrize("run_cap", [0, 256], ids=["unmerged", "merged"])
def test_carried_cells_equal_searched(run_cap, periodic, P, kind):
    level, cap, gap = TABLES[kind]
    fields, box, table = _cloud(periodic, level)
    nbr = NeighborConfig(level=level, cap=cap, group=64, window=5,
                         run_cap=run_cap, gap=gap)
    S = N // P

    @jax.jit
    def both(x, y, z, h):
        # slab-local targets against the GLOBAL table, as under shard_map
        ranges, (c0, c1) = pp.group_cell_ranges(
            x, y, z, h, None, box, nbr, table=table, with_cells=True)
        plain = pp.group_cell_ranges(x, y, z, h, None, box, nbr, table=table)
        return (ranges, plain, (c0, c1),
                ex._cells_of_runs(ranges.starts, ranges.lens, table),
                ex._split_runs_cells(ranges, table, S, P, c0=c0),
                ex._split_runs_cells(ranges, table, S, P))

    ranges, plain, carried, searched, split_c, split_s = jax.vmap(both)(
        *(f.reshape(P, S) for f in fields))

    # asking for the cells changes nothing else
    for a, b in zip(ranges, plain):
        _eq(a, b)
    active = np.asarray(ranges.lens) > 0
    assert active.sum() > 300
    _eq(carried[0], searched[0], active)
    _eq(carried[1], searched[1], active)
    # the regime each table kind is here for
    c0, c1 = np.asarray(carried[0]), np.asarray(carried[1])
    if run_cap:
        assert (c1 > c0)[active].any(), "no merged run spans two cells"
    else:
        _eq(c0, c1)
    cell_len = np.diff(np.asarray(table))
    if kind == "empty-cells":
        assert (cell_len == 0).mean() > 0.3
        if run_cap:  # a merged run bridges the empty cells inside it
            inside = np.concatenate([[0], np.cumsum(cell_len == 0)])
            assert (inside[c1 + 1] - inside[c0])[active].any()
    else:
        assert (cell_len[c0[active]] > cap).any(), "no run clipped at cap"

    # the pieces: same split, and each active piece's first cell
    for a, b in zip(split_c[:5], split_s[:5]):
        jax.tree.map(_eq, a, b)
    pieces = np.asarray(split_c[1]) > 0
    if run_cap:  # a single cell clipped at a small cap rarely crosses
        assert pieces.sum() > active.sum(), "no run crossed a slab boundary"
    assert not np.asarray(split_c[4]).any(), "split slots overflowed"
    _eq(split_c[5], split_s[5], pieces)


def test_unsplit_coverage_differs_only_on_empty_boundary_cells():
    """The one place the unsplit marking is not the split one: an EMPTY
    cell lying exactly on the slab boundary that cuts a run. It holds no
    rows, so every layout built from the bitmap is the same."""
    table = jnp.asarray([0, 3, 4, 4, 6, 8], jnp.int32)  # cell 2 is empty
    S, P = 4, 2
    zf = jnp.zeros((1, 1), jnp.float32)
    run = pp.GroupRanges(
        starts=jnp.asarray([[3]], jnp.int32), lens=jnp.asarray([[3]], jnp.int32),
        shift_x=zf, shift_y=zf, shift_z=zf, ncells=jnp.ones(1, jnp.int32),
        occupancy=jnp.int32(0), boxl=jnp.full((3,), 1e30, jnp.float32),
    )  # rows 3..5 = cells 1..3, cut at row 4
    c0, c1 = ex._cells_of_runs(run.starts, run.lens, table)
    assert (int(c0[0, 0]), int(c1[0, 0])) == (1, 3)
    whole = ex.coverage_from_runs(run.starts, run.lens, table, (c0, c1))
    starts, lens, _, _, ovf, pc0 = ex._split_runs_cells(run, table, S, P, c0=c0)
    cut = ex.coverage_from_runs(starts, lens, table)
    assert np.asarray(starts)[0, :2].tolist() == [3, 4]
    assert np.asarray(pc0)[0, :2].tolist() == [1, 3] and not bool(ovf)
    assert np.asarray(whole).tolist() == [False, True, True, True, False]
    assert np.asarray(cut).tolist() == [False, True, False, True, False]
    for a, b in zip(ex._sparse_layout(whole, table, S, P),
                    ex._sparse_layout(cut, table, S, P)):
        _eq(a, b)


# ---------------------------------------------------------------------------
# the stage on the virtual CPU mesh: carried cells vs cells=None
# ---------------------------------------------------------------------------


SIDES = {"sedov": 16, "evrard": 20}


def _sedov_slabs(P: int):
    """``_slabs`` of the Sedov box, without the run slots."""
    return _slabs(P)[:4]


@functools.lru_cache(maxsize=None)
def _slabs(P: int, case: str = "sedov"):
    """Sedov 16^3 (or the Evrard sphere of the 20^3 lattice, 4,201, the
    smallest whose grid takes a window of 4; trimmed to the mesh as main()
    trims it) globally SFC-sorted (what the sharded
    step hands the stage), the clamped NeighborConfig, the sized
    per-distance caps and the sized run slots."""
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.parallel.sizing import device_sparse_halo
    from sphexa_tpu.sfc.box import make_global_box
    from sphexa_tpu.simulation import make_propagator_config

    state, box, const = make_initializer(case)(SIDES[case])
    if state.n % P:
        n_full, keep = state.n, state.n // P * P
        state = jax.tree.map(
            lambda a: a[:keep] if getattr(a, "ndim", 0) >= 1
            and a.shape[0] == n_full else a, state)
    cfg = make_propagator_config(state, box, const, block=512,
                                 backend="pallas")
    gbox = make_global_box(state.x, state.y, state.z, box)
    keys = compute_sfc_keys(state.x, state.y, state.z, gbox)
    order = jnp.argsort(keys)
    x, y, z, h = (f[order] for f in (state.x, state.y, state.z, state.h))
    S = state.n // P
    nbr = cfg.nbr
    if nbr.run_cap > S:  # same clamp as the sharded force stages
        nbr = dataclasses.replace(nbr, run_cap=S)
    sized, run_slots = device_sparse_halo(
        state.x, state.y, state.z, state.h, keys, gbox, nbr, P=P)
    return (gbox, keys[order], x, y, z, h), nbr, S, sized, run_slots


def _stage_fn(P: int, nbr, S: int, hmax, carried: bool, run_slots: int = 0):
    """shard_map'd sparse halo prologue returning everything the serves
    and the engines read (+ the live-run high-water, last)."""
    from jax.sharding import PartitionSpec

    from sphexa_tpu.parallel import make_mesh
    from sphexa_tpu.propagator import shard_map

    def stage(box, keys, x, y, z, h):
        k = jax.lax.axis_index("p")
        table = ex.global_cell_table(keys, nbr.level, "p")
        granges, cells = pp.group_cell_ranges(
            x, y, z, h, None, box, nbr, table=table, with_cells=True)
        ranges, covered_all, escaped, covered = ex.localize_ranges_sparse(
            granges, table, S, P, hmax, k, "p",
            cells=cells if carried else None, run_slots=run_slots)
        rows = ex.exchange_metrics_sparse(covered, table, S, hmax, P, k)
        lift = lambda a: jnp.asarray(a)[None]
        return (tuple(lift(a) for a in ranges), lift(covered_all),
                lift(escaped), lift(covered), lift(rows["halo_rows"]), table,
                lift(ex.live_runs_max(granges)))

    Pp, Pr = PartitionSpec("p"), PartitionSpec()
    return shard_map(
        stage, mesh=make_mesh(P), in_specs=(Pr, Pp, Pp, Pp, Pp, Pp),
        out_specs=((Pp,) * 8, Pp, Pp, Pp, Pp, Pr, Pp), check_vma=False,
    )


@pytest.mark.parametrize("caps", ["sized", "undersized"])
@pytest.mark.parametrize("P", [4, 8])
def test_stage_same_with_carried_and_searched_cells(P, caps):
    args, nbr, S, sized = _sedov_slabs(P)
    hmax = sized if caps == "sized" else (64,) * (P - 1)
    # two programs: one collective order each (exchange.chain_after)
    a = jax.jit(_stage_fn(P, nbr, S, hmax, carried=True))(*args)
    b = jax.jit(_stage_fn(P, nbr, S, hmax, carried=False))(*args)
    ra, cov_all_a, esc_a, cov_a, rows_a, table, _ = a
    rb, cov_all_b, esc_b, cov_b, rows_b, _, _ = b
    for fa, fb in zip(ra, rb):  # starts, lens, shifts, ncells, occ, boxl
        _eq(fa, fb)
    _eq(esc_a, esc_b)
    _eq(rows_a, rows_b)
    assert np.asarray(esc_a).all() == (caps == "undersized")
    assert (np.asarray(ra[1]) > 0).any()
    if caps == "sized":  # the sizing's matrix is the in-step need
        assert 0 < int(np.asarray(rows_a).max()) <= sum(sized)
    # bitmaps: equal wherever a cell holds rows (see the docstring of
    # localize_ranges_sparse for the one exception, which holds none)
    filled = np.diff(np.asarray(table)) > 0
    _eq(np.asarray(cov_a) & filled, np.asarray(cov_b) & filled)
    _eq(np.asarray(cov_all_a) & filled, np.asarray(cov_all_b) & filled)
    assert not (np.asarray(cov_a) ^ np.asarray(cov_b))[:, filled].any()


# ---------------------------------------------------------------------------
# the run-slot axis cut to the sized high-water of live runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["sedov", "evrard"])
@pytest.mark.parametrize("P", [4, 8])
def test_narrowed_stage_equals_full_width(P, case):
    """The stage over ``run_slots`` slots a group is the stage over the
    window's W3: the same coverage bitmaps, the same escapes, the same
    rows needed, and the same localized runs in the same slots (the live
    runs sit at the front; the slots the cut drops are dead ones)."""
    args, nbr, S, hmax, run_slots = _slabs(P, case)
    w3 = nbr.window ** 3
    assert 0 < run_slots < w3
    full = jax.jit(_stage_fn(P, nbr, S, hmax, carried=True))(*args)
    cut = jax.jit(_stage_fn(P, nbr, S, hmax, True, run_slots))(*args)
    rf, cov_all_f, esc_f, cov_f, rows_f, _, live_f = full
    rc, cov_all_c, esc_c, cov_c, rows_c, _, live_c = cut
    extra = max(8, P - 1)
    assert rf[0].shape[-1] == w3 + extra
    assert rc[0].shape[-1] == run_slots + extra
    # the sized slots hold the fullest group's runs of an undrifted state
    _eq(live_f, live_c)
    assert 0 < int(np.asarray(live_f).max()) <= run_slots
    for a, b in ((cov_all_f, cov_all_c), (cov_f, cov_c), (esc_f, esc_c),
                 (rows_f, rows_c)):
        _eq(a, b)
    assert not np.asarray(esc_c).any()
    width = rc[0].shape[-1]
    for a, b in zip(rf[:5], rc[:5]):  # starts, lens, the three shifts
        _eq(np.asarray(a)[..., :width], b)
        assert not np.asarray(a)[..., width:].any()  # dead beyond the cut
    for a, b in zip(rf[5:], rc[5:]):  # ncells, occupancy, boxl
        _eq(a, b)
    assert (np.asarray(rc[1]) > 0).any()


@pytest.mark.parametrize("case", ["sedov", "evrard"])
def test_run_slots_under_the_high_water_trip_the_sentinel(case):
    """One slot under the fullest group's live runs: ``escaped`` on the
    shards that hold such a group, and on no other; at the high-water
    itself, on none. No run is dropped without the flag."""
    P = 4
    args, nbr, S, hmax, run_slots = _slabs(P, case)
    live = np.asarray(jax.jit(_stage_fn(P, nbr, S, hmax, True))(*args)[6])
    hw = int(live.max())
    at = jax.jit(_stage_fn(P, nbr, S, hmax, True, hw))(*args)
    under = jax.jit(_stage_fn(P, nbr, S, hmax, True, hw - 1))(*args)
    assert not np.asarray(at[2]).any()
    _eq(np.asarray(under[2]).ravel(), live.ravel() > hw - 1)
    # a cut that drops live runs drops their cells from the bitmap too:
    # the flag is what keeps such a step from being used
    assert np.asarray(under[3]).sum() <= np.asarray(at[3]).sum()


@pytest.mark.parametrize("P", [2, 4])
def test_windowed_stage_narrowed_equals_full_width(P):
    """``localize_ranges`` (the windowed fallback) shares the split with
    the sparse stage and takes the same cut."""
    from jax.sharding import PartitionSpec

    from sphexa_tpu.parallel import make_mesh
    from sphexa_tpu.propagator import shard_map

    args, nbr, S, _, run_slots = _slabs(P)

    def stage(slots):
        def fn(box, keys, x, y, z, h):
            ranges, serve, jbuf, escaped, metrics = ex.shard_halo_stage(
                x, y, z, h, keys, box, nbr, P, S, "p", run_slots=slots)
            lift = lambda a: jnp.asarray(a)[None]
            return (ranges.starts, ranges.lens, ranges.ncells,
                    lift(escaped), lift(metrics["halo_runs"]),
                    jbuf((x,), serve((x,)))[0])

        Pp, Pr = PartitionSpec("p"), PartitionSpec()
        return jax.jit(shard_map(
            fn, mesh=make_mesh(P), in_specs=(Pr, Pp, Pp, Pp, Pp, Pp),
            out_specs=(Pp,) * 6, check_vma=False))

    full, cut = stage(0)(*args), stage(run_slots)(*args)
    width = cut[0].shape[1]
    assert width == run_slots + max(8, P - 1) < full[0].shape[1]
    for a, b in zip(full[:2], cut[:2]):
        _eq(np.asarray(a)[:, :width], b)
        assert not np.asarray(a)[:, width:].any()
    for a, b in zip(full[2:], cut[2:]):
        _eq(a, b)
    assert not np.asarray(cut[3]).any()
    assert np.asarray(stage(int(np.asarray(full[4]).max()) - 1)(
        *args)[3]).any()


# ---------------------------------------------------------------------------
# lowering guards
# ---------------------------------------------------------------------------


def _search_queries(jaxpr):
    """Query counts of every jnp.searchsorted in the program."""
    return [
        int(np.prod(eqn.invars[1].aval.shape, dtype=np.int64))
        for eqn in walk_eqns(jaxpr)
        if eqn.primitive.name in CALL_PRIMS
        and eqn.params.get("name") == "searchsorted"
    ]


def _sort_operands(jaxpr):
    return [len(e.invars) for e in walk_eqns(jaxpr) if e.primitive.name == "sort"]


@pytest.mark.parametrize("P", [4, 8])
def test_std_stage_searches_at_most_P_rows(P):
    """The 2M-query binary search (two per-run searches of the cell
    table, 477 of 1468 ms of the four-chip Sedov step before PR 24)
    cannot come back unnoticed: the std stage searches for the P - 1
    slab-boundary cells and for nothing else."""
    from jax.sharding import PartitionSpec

    from sphexa_tpu.parallel import make_mesh
    from sphexa_tpu.propagator import shard_map

    args, nbr, S, hmax = _sedov_slabs(P)

    def stage(box, keys, x, y, z, h):
        ranges, serve, jbuf, escaped, metrics = ex.shard_halo_stage_sparse(
            x, y, z, h, keys, box, nbr, P, hmax, "p")
        return jbuf((x,), serve((x,)))[0], ranges.starts

    Pp, Pr = PartitionSpec("p"), PartitionSpec()
    fn = shard_map(stage, mesh=make_mesh(P),
                   in_specs=(Pr, Pp, Pp, Pp, Pp, Pp), out_specs=(Pp, Pp),
                   check_vma=False)
    queries = _search_queries(jax.make_jaxpr(fn)(*args).jaxpr)
    assert queries and max(queries) <= P, queries
    # the guard sees the search when it is there
    old = jax.make_jaxpr(_stage_fn(P, nbr, S, hmax, carried=False))(*args)
    assert max(_search_queries(old.jaxpr)) > 100


@pytest.mark.parametrize("run_cap,plain,cells", [(0, [7], [8]),
                                                (256, [7, 7], [8, 9])],
                         ids=["unmerged", "merged"])
def test_one_chip_prologue_sorts_no_cell_payload(run_cap, plain, cells):
    """Callers that read no cell index (every one-chip program, the
    windowed stage, the dump) sort what they sorted before: the cell
    payloads exist only under ``with_cells``."""
    fields, box, table = _cloud(True, 3)
    nbr = NeighborConfig(level=3, cap=16, group=64, window=4,
                         run_cap=run_cap, gap=16)
    trace = lambda **kw: jax.make_jaxpr(
        lambda *f: pp.group_cell_ranges(*f, None, box, nbr, table=table, **kw)
    )(*fields).jaxpr
    assert _sort_operands(trace()) == plain
    assert _sort_operands(trace(with_cells=True)) == cells


# ---------------------------------------------------------------------------
# the driver: a cut under the live runs is a sentinel trip, re-sized
# ---------------------------------------------------------------------------

TRIP_RUNNER = """
    import dataclasses
    import json
    import numpy as np
    import sphexa_tpu.parallel.sizing as sz
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.sinks import MemorySink

    # the first sizing hands out one slot less than the fullest group's
    # live runs (no margin can: the floor is additive); later ones are
    # the program's own
    real, seen = sz.pad_run_slots, []

    def under(runs, margin, quantum=8):
        seen.append(int(runs))
        if len(seen) == 1:
            return int(runs) - 1
        return real(runs, margin, quantum)

    sz.pad_run_slots = under
    state, box, const = make_initializer({case!r})({side})
    const = dataclasses.replace(const, g=0.0)  # the SPH halo alone
    n4 = state.n // 4 * 4
    state = jax.tree.map(
        lambda a: a[:n4] if getattr(a, "ndim", 0) >= 1 else a, state)
    sink = MemorySink()
    # the STREAMED mesh step's sentinel (a mesh that walks lists ships over
    # a frozen layout: nothing escapes inside a step there)
    sim = Simulation(state, box, const, prop="std", backend="pallas",
                     num_devices=4, check_every=1, use_lists=False,
                     telemetry=Telemetry(sinks=[sink]))
    started = sim._halo_info["run_slots"]
    margin0 = sim._halo_margin
    for _ in range(2):
        sim.step()
    ex = [e for e in sink.events if e["kind"] == "exchange"]
    print("RUN-SLOTS-RESULT " + json.dumps(dict(
        seen=seen, started=started, ended=sim._halo_info["run_slots"],
        stepper=sim._stepper.cfg.halo_runs,
        trips=int(sim.telemetry.counters.get("halo_trips", 0)),
        margin=[margin0, sim._halo_margin],
        reasons=[e["reason"] for e in sink.events
                 if e["kind"] == "reconfigure"],
        events=[[e["run_slots"], e["live_runs_max"], e["trips"]]
                for e in ex],
        finite=bool(np.isfinite(np.asarray(sim.state.x)).all()),
        iteration=int(sim.iteration))))
"""


@pytest.mark.parametrize("case", ["sedov", "evrard"])
def test_driver_resizes_run_slots_after_a_trip(case):
    """``Simulation`` on a mesh whose run slots were sized one under the
    fullest group's live runs: the first step trips the halo sentinel
    (occupancy == cap + 1), is discarded, counts a ``halo_trips`` and
    grows the halo margin; the re-sized run holds every run and passes."""
    from conftest import run_mesh_subprocess

    out = run_mesh_subprocess(TRIP_RUNNER.format(case=case,
                                                 side=SIDES[case]))
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("RUN-SLOTS-RESULT ")]
    assert lines, out.stderr[-3000:]
    r = json.loads(lines[-1].split(" ", 1)[1])
    hw = r["seen"][0]
    assert hw >= 2 and r["started"] == hw - 1
    assert r["trips"] == 1 and r["reasons"][:2] == ["initial", "overflow"]
    assert r["margin"][1] == pytest.approx(1.5 * r["margin"][0])
    assert r["ended"] == r["stepper"] >= hw + 4
    assert r["iteration"] == 2 and r["finite"]
    # one event a verified step: the slots the step ran with, the fullest
    # group's live runs under them, the trip counted once
    assert len(r["events"]) == 2
    for slots, live, trips in r["events"]:
        assert slots == r["ended"] and 0 < live <= slots and trips == 1
