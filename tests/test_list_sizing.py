"""What a persistent list is sized for, and what a recovery launches.

The list engine's caps are static shapes, so a re-size is a recompile: these
tests pin the rules that keep one out of a run's steady stretch.

- the window covers what the build inflates a group's bbox by (2h + skin on
  each side), with the sizing's 10 % slack left for h;
- ``kernels.h_fixed_point`` reads the fixed point of ``update_h`` back from
  one application, and the driver re-sizes once, after the first verified
  step, where the IC's h is further from it than the slack (Noh's rim has
  half its neighbours), and not where it is not (Sedov's lattice);
- a rolled-back window's replay launches the window's own donated program
  over a pinned copy, so a recovery brings no first-use compile;
- a list build that raises is retried under its own reason.

Pallas kernels run in interpret mode here; nothing in this file is a speed.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from sphexa_tpu.init import init_noh, init_sedov
from sphexa_tpu.observables import make_observable_spec
from sphexa_tpu.propagator import _sort_by_keys
from sphexa_tpu.simulation import Simulation, make_propagator_config
from sphexa_tpu.sph import pallas_pairs as pp
from sphexa_tpu.sph.kernels import h_fixed_point, update_h
from sphexa_tpu.telemetry import Telemetry
from sphexa_tpu.telemetry.sinks import MemorySink

from pair_list_cases import CASES  # noh 16^3, sedov 30^3: one size a case


@pytest.mark.parametrize("nc", [40, 50, 80, 100, 123])
def test_h_fixed_point_inverts_update_h(nc):
    ng0, h = 100, 0.0125
    after = float(update_h(ng0, jnp.float32(nc), jnp.float32(h)))
    # nc scales with h^3, so the update rests where h^3 * nc / ng0 = h^3
    assert h_fixed_point(h, after) == pytest.approx(
        h * (ng0 / nc) ** (1.0 / 3.0), rel=2e-5)


def test_h_fixed_point_of_a_resting_h_is_itself():
    assert h_fixed_point(0.0125, 0.0125) == pytest.approx(0.0125, rel=1e-12)


@pytest.mark.parametrize("init,side", list(CASES.values()), ids=list(CASES))
@pytest.mark.parametrize("grow", [1.0, 1.08], ids=["as-sized", "h+8pc"])
def test_list_window_covers_what_the_build_inflates(init, side, grow):
    """The build searches bbox -/+ (2 h_max + skin): inside the sizing's
    10 % radius slack the window guard must not trip."""
    state, box, const = init(side)
    cfg = make_propagator_config(state, box, const, block=4096,
                                 backend="pallas", use_lists=True)
    assert cfg.list_slot_cap > 0, "lists did not engage"
    ss, keys, _ = _sort_by_keys(state, box, "hilbert")
    h = ss.h * jnp.float32(grow)
    skin = jnp.float32(cfg.list_skin_rel * 2.0) * jnp.max(h)
    ranges = pp.group_cell_ranges(ss.x, ss.y, ss.z, h, keys, box, cfg.nbr,
                                  radius_pad=skin)
    # cap + 1 is the window guard's sentinel
    assert int(ranges.occupancy) <= cfg.nbr.cap


def test_lists_are_sized_for_the_relaxed_h():
    """h_relax widens what the lists are sized for, and nothing else."""
    # 65k particles on a 16^3 grid: the smallest sphere whose window and
    # slot budget are not simply the whole grid (sizing only, no kernel)
    state, box, const = init_noh(50)
    kw = dict(block=4096, backend="pallas", use_lists=True,
              tuned={"cell_target": 16})
    base = make_propagator_config(state, box, const, **kw)
    wide = make_propagator_config(state, box, const, h_relax=1.3, **kw)
    assert wide.list_slot_cap > base.list_slot_cap
    assert wide.nbr.window > base.nbr.window
    assert (wide.nbr.level, wide.nbr.cap) == (base.nbr.level, base.nbr.cap)
    off = make_propagator_config(state, box, const, h_relax=1.3,
                                 **{**kw, "use_lists": False})
    assert off == make_propagator_config(state, box, const,
                                         **{**kw, "use_lists": False})


def _drive(init, side, steps, flushed=3, **kw):
    """The benchmark's traffic in small: ``flushed`` single flushed steps
    (its warm-up), then deferred windows."""
    sink = MemorySink()
    state, box, const = init(side)
    sim = Simulation(state, box, const, prop="std", backend="pallas",
                     use_lists=True, check_every=4, science_rows=True,
                     obs_spec=make_observable_spec(
                         "noh" if init is init_noh else "sedov"),
                     telemetry=Telemetry(sinks=[sink]), **kw)
    for i in range(steps):
        sim.step()
        if i < flushed:
            sim.flush()
    sim.flush()
    return sim, sink, sim.drain_science()


@pytest.fixture(scope="module")
def noh_run():
    return _drive(init_noh, 16, 16)


def test_first_sizing_covers_the_relaxed_h(noh_run):
    sim, sink, _ = noh_run
    reasons = [(e["it"], e["reason"]) for e in sink.of_kind("reconfigure")]
    # the rim's nc is about half of ng0: h is heading 26-35 % up, past the
    # 10 % slack, and since PR 44 the first configure knows it from a host
    # count of the hull's neighbours (simulation.hull_h_relax): no re-size
    # (a second set of step and rebuild programs) in the start-up
    assert reasons == [(0, "initial")], reasons
    assert 1.1 < sim._h_sized / float(np.asarray(init_noh(16)[0].h).max())
    # and the sizing held: no list-slot, overflow or stale-grid re-size
    # while the rim relaxed
    builds = sink.of_kind("rebuild_lists")
    assert builds[0]["reason"] == "first"
    assert all(e["attempts"] == 1 for e in builds)


def test_first_verified_step_resizes_where_the_count_missed(monkeypatch):
    """The net under the count: with the hull unseen (an estimate of 1),
    the driver knows after ONE verified step and re-sizes once."""
    import sphexa_tpu.simulation as simulation

    monkeypatch.setattr(simulation, "hull_h_relax", lambda *a, **k: 1.0)
    sim, sink, _ = _drive(init_noh, 16, 4)
    reasons = [(e["it"], e["reason"]) for e in sink.of_kind("reconfigure")]
    assert reasons == [(0, "initial"), (1, "h-relax")], reasons
    assert 1.1 < sim._h_sized / float(np.asarray(init_noh(16)[0].h).max())
    builds = sink.of_kind("rebuild_lists")
    assert [e["reason"] for e in builds[:2]] == ["first", "reconfigure"]
    assert all(e["attempts"] == 1 for e in builds)


def test_relaxation_estimate_tracks_the_run(noh_run):
    sim, _, rows = noh_run
    # the constructor's count is replaced by what the first verified
    # step shows, and that is what a later configure would size for: h
    # still has 5-30 % to go at iteration 1
    assert 1.0 <= sim._h_relax < 1.3, sim._h_relax
    assert sim._h_configured is None  # checked once per configure
    assert [r["it"] for r in rows] == list(range(1, 17))


def test_lattice_ic_is_not_resized():
    """Sedov's lattice sits at its fixed point to within the slack: the
    first verified step must NOT re-size (the four-million-particle
    cells' programs stay what they were)."""
    sim, sink, _ = _drive(init_sedov, 30, 2, flushed=2)
    assert sim._use_lists
    assert [e["reason"] for e in sink.of_kind("reconfigure")] == ["initial"]
    assert sim._h_relax < 1.1


def _rows_equal(a, b):
    assert [r["it"] for r in a] == [r["it"] for r in b]
    for ra, rb in zip(a, b):
        for k in ("dt", "etot", "ecin", "eint"):
            assert ra[k] == rb[k], (ra["it"], k, ra[k], rb[k])


def test_replay_launches_the_windows_own_donated_program():
    """A thin skin forces list-expiry rollbacks. With donation on, the
    replay must not bring a second executable (no ``retrace`` that is
    not the run's first launch or the one after a reconfigure), must pin
    a copy per replayed step, and must give the undonated run's bits."""
    kw = dict(list_skin_rel=0.05)
    plain, sink0, rows0 = _drive(init_noh, 14, 12, flushed=0, donate=False,
                                 **kw)
    donated, sink1, rows1 = _drive(init_noh, 14, 12, flushed=0, donate=True,
                                   **kw)
    assert donated._donate_active and not plain._donate_active
    rollbacks = sink1.of_kind("rollback")
    assert rollbacks and len(rollbacks) == len(sink0.of_kind("rollback"))
    _rows_equal(rows0, rows1)
    # every retrace of the donated run follows a configure (the run's
    # first launch follows the initial one): the replays brought none
    explained = False
    for e in sink1.events:
        if e["kind"] == "reconfigure":
            explained = True
        elif e["kind"] == "retrace":
            assert explained, e
            explained = False
    replayed = sum(e["steps"] for e in sink1.of_kind("replay"))
    pins = [e for e in sink1.of_kind("span") if e["name"] == "sphexa:pin"]
    windows = len(sink1.of_kind("window")) + len(rollbacks)
    assert len(pins) >= windows + replayed
    assert all(e["copied"] for e in pins)


def test_failed_build_is_retried_under_its_own_reason(monkeypatch):
    """``_rebuild_lists`` lets go of the outgoing list before it builds;
    a build that raises leaves none, and the next launch's build must say
    why the FAILED one was made, not ``reconfigure``."""
    import sphexa_tpu.propagator as propagator

    sim, sink, _ = _drive(init_noh, 14, 2, flushed=2)
    real = propagator.rebuild_pair_lists
    calls = []

    def failing(*args, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise ValueError("RESOURCE_EXHAUSTED: test")
        return real(*args, **kw)

    monkeypatch.setattr(propagator, "rebuild_pair_lists", failing)
    with pytest.raises(ValueError):
        sim._rebuild_lists("proactive", slack=0.2)
    assert sim.pair_lists is None
    sim.step()
    sim.flush()
    last = sink.of_kind("rebuild_lists")[-1]
    assert last["reason"] == "proactive" and last["attempts"] == 1
    assert sim.pair_lists is not None


def test_flat_table_overflow_resizes_and_retries_once(monkeypatch):
    """The flat lane table's budget (rows for the SUM of kept chunks) is a
    static cap like the per-group one: a build that needs more rows than
    it has raises the same sentinel, the driver re-sizes under
    ``list-slot`` and the second attempt fits."""
    import sphexa_tpu.sph.pair_lists as pair_lists

    # (the budget is taken up to a post-pass tile: at a row tile here,
    # so a table can be short at this size at all)
    monkeypatch.setattr(pair_lists, "LIST_TABLE_TILE", 8)
    real, sized = pair_lists.estimate_list_caps, []

    def short_table_first(*args, **kw):
        slot_cap, slots_cap = real(*args, **kw)
        sized.append(slots_cap)
        return slot_cap, (64 if len(sized) == 1 else slots_cap)

    monkeypatch.setattr(pair_lists, "estimate_list_caps", short_table_first)
    sim, sink, _ = _drive(init_noh, 14, 2, flushed=2)
    first = sink.of_kind("rebuild_lists")[0]
    assert first["reason"] == "first" and first["attempts"] == 2
    assert 64 < first["slots_live"] <= first["slots_cap"]
    assert first["slot_need"] <= first["slot_cap"]
    resized = [e for e in sink.of_kind("reconfigure")
               if e["reason"] == "list-slot"]
    assert len(resized) == 1
    assert sim.pair_lists is not None
    assert sim.pair_lists.slots_cap == sim.active_cfg.list_slots_cap
