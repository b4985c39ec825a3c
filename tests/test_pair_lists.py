"""Persistent-list engine (sph/pair_lists.py + the list-walk engine in
sph/pallas_pairs.py): the ``noh`` case of tests/pair_list_cases.py (which
see; ``sedov`` is tests/test_pair_lists_sedov.py), and what no case
parametrises: the driven Simulation in list mode, the table of which
kernel runs which op, the flat lane table's segments and a skewed group."""

import numpy as np
import pytest
import jax.numpy as jnp

from sphexa_tpu.init import init_noh
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.simulation import make_propagator_config
from sphexa_tpu.sph import pallas_pairs as pp
from sphexa_tpu.sph.pair_lists import build_pair_lists

CASE = "noh"

from pair_list_cases import *  # noqa: E402,F401,F403  (the case's tests)
from pair_list_cases import _assert_flat_is_dense, _setup  # noqa: E402


def _run_sim(use_lists: bool, steps: int, check_every: int = 1):
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_noh(14)
    sim = Simulation(state, box, const, prop="std", block=4096,
                     backend="pallas", use_lists=use_lists,
                     check_every=check_every)
    diags = [sim.step() for _ in range(steps)]
    sim.flush()
    return sim, diags


def test_simulation_list_mode_matches_streaming():
    """Full Simulation in list mode vs per-step streaming: identical
    physics trajectory (physical quantities match after re-ordering; the
    list mode freezes the sort order between rebuilds)."""
    sim0, _ = _run_sim(False, 4)
    sim1, d1 = _run_sim(True, 4)
    assert sim1._use_lists and sim1._lists is not None
    assert any("list_slack" in d for d in d1)
    s0, s1 = sim0.state, sim1.state
    np.testing.assert_allclose(float(s0.ttot), float(s1.ttot), rtol=1e-6)
    # order-insensitive per-particle comparison: sort both by position
    for a, b, tol in ((s0.x, s1.x, 2e-6), (s0.temp, s1.temp, 1e-4),
                      (s0.vx, s1.vx, 1e-4)):
        np.testing.assert_allclose(np.sort(np.asarray(a)),
                                   np.sort(np.asarray(b)), rtol=tol,
                                   atol=1e-7)


def test_simulation_list_rebuild_on_expiry():
    """Drive enough steps that drift eats the skin: the driver must
    rebuild (proactively or by discard) and keep stepping correctly."""
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_noh(14)
    # a tiny skin forces frequent expiry, exercising both the proactive
    # and the discard-and-replay recovery paths
    sim = Simulation(state, box, const, prop="std", block=4096,
                     backend="pallas", use_lists=True, check_every=3,
                     list_skin_rel=0.05)
    rebuilds = 0
    orig = sim._rebuild_lists

    def counting(*args, **kw):
        nonlocal rebuilds
        rebuilds += 1
        orig(*args, **kw)

    sim._rebuild_lists = counting
    diags = [sim.step() for _ in range(12)]
    sim.flush()
    assert sim._lists is not None
    slacks = [d.get("list_slack") for d in diags if "list_slack" in d]
    assert slacks, "no list diagnostics surfaced"
    # noh piston flow drifts ~0.2 h_min/step: a 0.05*2h skin cannot
    # survive 12 steps — the rebuild machinery must actually have fired
    # beyond the initial build
    assert rebuilds >= 2, f"expected expiry rebuilds, got {rebuilds}"
    # and the run stayed physical
    assert np.isfinite(float(sim.state.ttot))
    assert float(sim.state.ttot) > 0


#: today's rows of pallas_pairs.PAIR_OP_ENGINE: (kernel on lists, AABB cull
#: when streamed). A PR that moves an op edits its row there and here.
ENGINE_ROWS = [("density", "walk", False), ("iad", "walk", False),
               ("gradh", "walk", False),
               ("momentum-energy-std", "walk", True),
               ("divv-curlv", "walk", True), ("av-switches", "walk", True),
               ("momentum-energy-ve", "walk", True)]


def _call_op(op, ss, box, const, nbr, **kw):
    """One pair op by its PAIR_OP_ENGINE name, every field from ``ss``
    (the values do not matter: no kernel runs)."""
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    v, six = (ss.vx, ss.vy, ss.vz), (m,) * 6
    tail = (None, box, const, nbr)
    return {
        "density": lambda: pp.pallas_density(x, y, z, h, m, *tail, **kw),
        "iad": lambda: pp.pallas_iad(x, y, z, h, m, *tail, **kw),
        "gradh": lambda: pp.pallas_ve_def_gradh(x, y, z, h, m, m, *tail,
                                                **kw),
        "momentum-energy-std": lambda: pp.pallas_momentum_energy_std(
            x, y, z, *v, h, m, m, m, m, *six, *tail, **kw),
        "divv-curlv": lambda: pp.pallas_iad_divv_curlv(
            x, y, z, *v, h, m, m, *tail, **kw),
        "av-switches": lambda: pp.pallas_av_switches(
            x, y, z, *v, h, m, m, m, m, m, *six, None, box, 1e-3, const, nbr,
            **kw),
        "momentum-energy-ve": lambda: pp.pallas_momentum_energy_ve(
            x, y, z, *v, h, m, m, m, m, m, m, *six, *tail, **kw),
    }[op]()


@pytest.fixture(scope="module")
def tiny():
    return _setup(init_noh, 8)


@pytest.mark.parametrize("op,on_lists,cull", ENGINE_ROWS,
                         ids=[r[0] for r in ENGINE_ROWS])
def test_the_table_names_the_kernel_each_op_builds(tiny, op, on_lists, cull,
                                                   monkeypatch):
    """pallas_pairs.PAIR_OP_ENGINE is the one place the choice lives: with
    ``lists`` an op builds the kernel its row names (the walk, the one
    list kernel there is, packs ``num_j + 1`` rows: the staged index),
    without them the streamed engine with the cull its row names; no
    ``skip_slots`` / ``skip`` keyword (the mark-bit form PR 43 deleted)
    reaches either builder; and a patched row moves the op. No kernel
    runs: both builders are spied on."""
    from types import SimpleNamespace

    ss, keys, box, const, nbr = tiny
    assert pp.PAIR_OP_ENGINE[op] == (on_lists, cull)
    assert set(pp.PAIR_OP_ENGINE) == {r[0] for r in ENGINE_ROWS}
    built, packed = [], []

    def spy(name):
        def builder(pair_body, finalize, **kw):
            def call(ranges, i_fields, j_packed, i_offset=0, **ckw):
                built.append((name, kw, ckw))
                G = nbr.group
                outs = finalize(
                    [jnp.ones((G, 1))] * kw["num_i"],
                    tuple(jnp.ones((G, 1)) for _ in range(kw["num_acc"])),
                    jnp.ones((G, 1), jnp.int32))
                shape = (i_fields[0].shape[0], 1, G)
                return [jnp.ones(shape)] * len(outs) + [
                    jnp.ones(shape, jnp.int32)]
            return call
        return builder

    pack = pp.pack_j_fields
    monkeypatch.setattr(pp, "group_pair_engine", spy("streamed"))
    monkeypatch.setattr(pp, "group_pair_engine_lists", spy("walk"))
    monkeypatch.setattr(pp, "pack_j_fields", lambda fields, cap, nf_min=0: (
        packed.append((len(fields), nf_min)), pack(fields, cap, nf_min))[1])
    ranges = pp.group_cell_ranges(ss.x, ss.y, ss.z, ss.h, keys, box, nbr)
    lists = SimpleNamespace(ranges=ranges, slot_cap=24)

    def run(**kw):
        del built[:], packed[:]
        _call_op(op, ss, box, const, nbr, **kw)
        (name, bkw, ckw), (num_j, nf_min) = built[0], packed[0]
        assert len(built) == len(packed) == 1 and bkw["num_j"] == num_j
        return name, bkw, ckw, num_j, nf_min

    name, bkw, ckw, num_j, nf_min = run(lists=lists)
    assert on_lists == name == "walk" and nf_min == num_j + 1
    assert "skip_slots" not in bkw and "skip" not in ckw
    name, bkw, ckw, num_j, nf_min = run(ranges=ranges)
    assert name == "streamed" and nf_min == 0
    assert "skip_slots" not in bkw and "skip" not in ckw
    # fold mode and the cull's default are the engine's own, from the box
    assert bkw["fold"] is False
    assert bkw["chunk_skip"] is (None if cull else False)
    assert (ckw["aabb"] is not None) == cull
    # the row is what decides: the other cull streamed, and on lists a
    # kernel that is not there is refused, not replaced by the walk
    monkeypatch.setitem(pp.PAIR_OP_ENGINE, op, ("skip", not cull))
    assert (run(ranges=ranges)[2]["aabb"] is not None) == (not cull)
    with pytest.raises(ValueError, match="no list kernel 'skip'"):
        run(lists=lists)


def test_table_segments_zero_kept_and_the_tables_end():
    from sphexa_tpu.sph.pair_lists import _table_segments

    # kept chunks 3, 0, 8, 9: tiles 1, 0, 1, 2; a group that keeps
    # nothing takes no row and shares its offset with its successor
    cnt = jnp.asarray([[5, 1, 7] + [0] * 9, [0] * 12, [1] * 8 + [0] * 4,
                       [2] * 9 + [0] * 3], jnp.int32)
    seg, ntile, live = _table_segments(cnt, 32)
    assert (list(map(int, seg)), list(map(int, ntile)), int(live)) == (
        [0, 1, 1, 2], [1, 0, 1, 2], 32)   # the last segment ends the table
    # one tile short: the last group writes what fits, nothing past the
    # budget, and the rows needed say so (the caller's sentinel)
    seg, ntile, live = _table_segments(cnt, 24)
    assert (list(map(int, seg)), list(map(int, ntile)), int(live)) == (
        [0, 1, 1, 2], [1, 0, 1, 1], 32)
    seg, ntile, live = _table_segments(cnt, 8)
    assert (list(map(int, seg)), list(map(int, ntile)), int(live)) == (
        [0, 1, 1, 1], [1, 0, 0, 0], 32)


def _skewed_noh():
    """Noh 12^3 with groups of 4 and a quarter of h: most groups keep two
    chunks, the fullest six, as the SFC-straddler groups of the 1.1M
    sphere keep 3-5 x the median."""
    import dataclasses

    state, box, const = init_noh(12)
    state = dataclasses.replace(state, h=state.h * 0.25)
    cfg = make_propagator_config(state, box, const, block=4096,
                                 backend="pallas", group=4, use_lists=True,
                                 list_skin_rel=0.2)
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    return ss, keys, box, const, cfg


def test_skewed_group_builds_at_the_estimated_caps(monkeypatch):
    """One group keeps >= 3 x the median: the table holds the SUM, so
    neither cap is raised for it; with the post-pass tile at a row tile
    the table is exactly as long as its rows, so the last group's
    segment ends it and its fetch reads the window pad."""
    import sphexa_tpu.sph.pair_lists as pair_lists

    ss, keys, box, const, cfg = _skewed_noh()
    skin = 0.2 * 2.0 * float(jnp.max(ss.h))
    args = (ss.x, ss.y, ss.z, ss.h, keys, box, cfg.nbr, skin)
    lists = build_pair_lists(*args, cfg.list_slot_cap, cfg.list_slots_cap,
                             interpret=True)
    assert int(lists.overflow) == 0
    kept = _assert_flat_is_dense(ss, cfg.nbr, lists, skin)
    assert kept.max() >= 3 * np.median(kept)
    assert int(lists.slot_need) <= cfg.list_slot_cap

    monkeypatch.setattr(pair_lists, "LIST_TABLE_TILE", 8)
    live = int(lists.slots_live)
    tight = build_pair_lists(*args, cfg.list_slot_cap, live, interpret=True)
    assert int(tight.overflow) == 0 and tight.slots_cap == live
    assert 8 * int(tight.seg[-1]) + 8 * ((kept[-1] + 7) // 8) == live
    np.testing.assert_array_equal(np.asarray(tight.gidx)[:live],
                                  np.asarray(lists.gidx)[:live])
    short = build_pair_lists(*args, cfg.list_slot_cap, live - 8,
                             interpret=True)
    assert int(short.overflow) == 1 and int(short.slots_live) == live
    assert 8 * int(short.seg.max()) <= short.slots_cap

    # the walk engine on the skewed and on the tight table vs streaming
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    nbr = cfg.nbr
    rho, nc0, _ = pp.pallas_density(x, y, z, h, m, keys, box, const, nbr,
                                    interpret=True)
    from sphexa_tpu.sph.hydro_std import compute_eos_std

    p, c = compute_eos_std(ss.temp, rho, const)
    cs, _ = pp.pallas_iad(x, y, z, h, m / rho, keys, box, const, nbr,
                          interpret=True)
    margs = (x, y, z, ss.vx, ss.vy, ss.vz, h, m, rho, p, c, *cs)
    ref = pp.pallas_momentum_energy_std(*margs, keys, box, const, nbr,
                                        interpret=True)
    outs = [pp.pallas_momentum_energy_std(*margs, None, box, const, nbr,
                                          interpret=True, lists=ls)
            for ls in (lists, tight)]
    scale = float(jnp.max(jnp.abs(ref[0])))
    for out in outs:
        for a, b in zip(out[:3], ref[:3]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5 * scale)
    for a, b in zip(*outs):  # same rows, same order: bit for bit
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
