"""The list engine through its whole lifecycle, against the plain reference.

One driven run per (init, side), shared by the tests of this module: Noh with
persistent pair lists under deferred 4-step check windows, with a thin skin
and a fine cell grid (constructor arguments only), so that within a few tens
of steps the driver has made a ``list-expiry`` rollback with its replay (the
run's first list, which the planner has no trend for yet), planned proactive
rebuilds and a ``stale-grid`` reconfigure. A second, kicked run shifts every
particle between two windows, which no trend can foresee: the deferred
driver rolls back and replays, the checked driver rebuilds in the step
(``expiry``). Then:

- the live state's ``rho`` and ``(ax, ay, az, du)``, evaluated by the
  program's force stage on the LIVE lists, against
  benchmarks/reference_sph_std.py's all-pairs float32 sums at seeded targets
  (the comparison benchmarks/check_forces_std.py makes on the chip at 1.1M);
- the trajectory (``dt``, ``etot``, ``ecin``, ``eint`` per verified step)
  against a run without lists, checked every step;
- the ``rebuild_lists`` event's schema-v10 payload and the planner's v11 fields.

Pallas kernels run in interpret mode here; nothing in this file is a speed.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check_forces_std  # noqa: E402  (benchmarks/)
import reference  # noqa: E402
import reference_sph_std  # noqa: E402
from sphexa_tpu.init import make_initializer  # noqa: E402
from sphexa_tpu.observables import make_observable_spec  # noqa: E402
from sphexa_tpu.simulation import (  # noqa: E402
    _LIST_COVER_MARGIN,
    Simulation,
)
from sphexa_tpu.telemetry import Telemetry  # noqa: E402
from sphexa_tpu.telemetry.registry import (  # noqa: E402
    EVENT_KINDS,
    KIND_SINCE,
    SCHEMA_VERSION,
    validate_event,
)
from sphexa_tpu.telemetry.sinks import MemorySink  # noqa: E402

CASES = [("noh", 14), ("noh", 12)]
STEPS = 24
SEED = 2400000025
TARGETS = 48

#: Limits of the system-vs-reference comparison (reference_sph_std.errors)
#: in this CPU tier. (The chip at 1,098,340 after the cell's traffic reads
#: rho 5.8e-7, acceleration rms 2.1e-6 / max 2.6e-5, du 4.5e-6: PERF.md,
#: PR 25, with the bound proposed for a ``correct`` check.) Both are float32
#: sums of ~100 terms per target in different orders, and the program
#: evaluates a polynomial fit of the kernel (3e-7 floor): on the CPU the
#: comparison reads rho 3.3-4.1e-7, acceleration rms 2.7-3.0e-7 / max
#: 1.4-1.6e-6, du 1.2e-7-1.1e-6 (of the sample's rms). The limits are 12
#: to 19 times the largest of those. A bf16-rounded kernel product reads
#: rho 1.0e-3, acceleration rms 6.0e-4 / max 3.1e-3, du 6.3e-4 (120 to
#: 200 times over), one dropped neighbour rho 0.10, acceleration max
#: 1.7e-2, du 4.7e-3 (the tests below hold both to "refused").
LIMITS = {"rho_rel_max": 5e-6, "acc_rel_rms": 5e-6, "acc_rel_max": 2e-5,
          "du_rel_max": 2e-5}
#: Lists (frozen order, rebuilt lists, replayed windows) against per-step
#: streaming: the same pairs summed in another order. Measured: dt equal
#: to the bit (the 1.1x ramp), etot 2.4e-7, ecin 1.8e-7, eint 1.5e-7 over
#: STEPS steps; the limits are ten times that.
TRAJECTORY_RTOL = {"dt": 1e-6, "etot": 2.5e-6, "ecin": 2e-6, "eint": 2e-6}


def _simulate(init, side, steps=STEPS, kick_at=None, **kw):
    """``kick_at``: after that many steps, at a verified boundary, every
    particle is shifted by 0.75 of the live list's skin. The flow is the
    same (open box, translation invariant); the list is not: its next
    step finds slack -0.5, which no trend of the steps before predicts."""
    sink = MemorySink()
    state, box, const = make_initializer(init)(side)
    sim = Simulation(state, box, const, prop="std", backend="pallas",
                     tuned={"cell_target": 16}, science_rows=True,
                     obs_spec=make_observable_spec(init),
                     telemetry=Telemetry(sinks=[sink]), **kw)
    for i in range(steps):
        if i == kick_at:
            sim.flush()
            shift = 0.75 * float(sim.pair_lists.skin)
            sim.state = dataclasses.replace(sim.state,
                                            x=sim.state.x + shift)
            sink.kick_mark = len(sink.events)
        sim.step()
    sim.flush()
    return sim, const, sink, sim.drain_science()


@pytest.fixture(scope="module", params=CASES, ids=lambda c: f"{c[0]}-{c[1]}")
def driven(request):
    """(sim, const, sink, science rows) after STEPS steps on lists."""
    init, side = request.param
    return _simulate(init, side, use_lists=True, check_every=4,
                     list_skin_rel=0.1) + (request.param,)


@pytest.fixture(scope="module")
def streamed(driven):
    """The same case without lists, every step checked."""
    init, side = driven[-1]
    return _simulate(init, side, use_lists=False, check_every=1)


def _exceeded(errs):
    return [k for k, limit in LIMITS.items() if not errs[k] < limit]


def test_driven_through_every_recovery(driven):
    sim, _, sink, rows, _ = driven
    assert sim._use_lists and sim._lists is not None
    reasons = [e["reason"] for e in sink.of_kind("rebuild_lists")]
    assert reasons[0] == "first"
    for reason in ("proactive", "rollback", "reconfigure"):
        assert reason in reasons, reasons
    rollbacks = sink.of_kind("rollback")
    assert any(e["reason"] == "list-expiry" for e in rollbacks)
    assert len(sink.of_kind("replay")) == len(rollbacks)
    assert any(e["reason"] == "stale-grid"
               for e in sink.of_kind("reconfigure"))
    # every step verified once, whatever was rolled back and replayed
    assert [r["it"] for r in rows] == list(range(1, STEPS + 1))


#: Lists the fixed rule (rebuild under ``list_slack`` 0.25 at a boundary,
#: always whole windows) built over the same 24 steps, counted on the CPU
#: at the parent commit of PR 26 (side 14: ages 1 1 2 4 7 16; side 12:
#: 1 1 1 3 6 16); in both it rolled two windows back on ``list-expiry``
#: after the first list's.
FIXED_RULE_BUILDS = {("noh", 14): 7, ("noh", 12): 7}


def test_planned_windows_do_not_roll_back(driven):
    """Once the first list has given the planner a trend, no window runs a
    list out: the rebuild lands at a boundary, where the plan put it. And
    the plan does not buy that with shorter lives: no more lists are built
    over the same steps than the fixed rule built (the trajectory of this
    same run is held to the streamed one below)."""
    _, _, sink, _, case = driven
    first_retired = next(i for i, e in enumerate(sink.events)
                         if e["kind"] == "rebuild_lists"
                         and e["reason"] != "first")
    later = [e for e in sink.events[first_retired:]
             if e["kind"] == "rollback" and e["reason"] == "list-expiry"]
    assert later == []
    builds = sink.of_kind("rebuild_lists")
    assert len(builds) <= FIXED_RULE_BUILDS[case], builds
    # the plan engaged: windows were cut to the list's cover, never over
    # what was asked for, and whole ones stayed whole
    windows = sink.of_kind("window")
    assert all(1 <= w["steps"] <= w["planned_steps"] <= 4 for w in windows)
    assert any(w["planned_steps"] < 4 for w in windows)
    assert any(w["steps"] == 4 for w in windows)
    # a list retired by the plan had served what it was planned for
    for e in builds:
        if e["reason"] == "proactive" and e["cover_steps"] is not None:
            assert e["age_steps"] >= min(e["cover_steps"], 4), e


def test_rebuild_event_says_why(driven):
    from sphexa_tpu.sph.pallas_pairs import list_run_rows

    sim, _, sink, _, _ = driven
    events = sink.of_kind("rebuild_lists")
    for e in events:
        assert e["v"] == SCHEMA_VERSION and validate_event(e) == []
        assert e["reason"] in ("first", "proactive", "expiry", "rollback",
                               "reconfigure")
        assert 0 < e["slot_need"] <= e["slot_cap"] and e["attempts"] >= 1
        # v12: the flat lane table's occupancy, whole 8-row tiles
        assert 0 < e["slots_live"] <= e["slots_cap"]
        assert e["slots_live"] % 8 == 0
        # v18: kept chunks, the runs they lie in, the rows a run fetches
        assert e["run_rows"] == list_run_rows(sim._cfg.nbr)
        assert 0 < e["runs_live"] <= e["chunks_live"] <= e["slots_live"]
        assert e["chunks_live"] <= e["runs_live"] * e["run_rows"]
        triggered = e["reason"] in ("proactive", "expiry", "rollback")
        assert (e["slack"] is not None) == triggered
        if e["reason"] == "proactive":
            # the computed threshold: the list had skin left, and the
            # step to come was predicted to find less than the margin
            assert e["slack"] >= 0.0 and e["rate"] > 0.0
            assert e["slack"] - e["rate"] < _LIST_COVER_MARGIN
        elif triggered:
            assert e["slack"] < 0.0
    assert events[0]["rate"] is None and events[0]["cover_steps"] is None
    assert events[0]["age_steps"] == 0
    # a list's age is the verified steps it served: the builds partition
    # the run, so no age exceeds the steps made
    assert all(0 <= e["age_steps"] <= STEPS for e in events)
    assert any(e["age_steps"] >= 4 for e in events)


def test_forces_match_reference(driven):
    sim, const, _, _, _ = driven
    errs = check_forces_std.compare(sim, const, SEED, TARGETS)
    assert errs["list_ok"] and errs["finite"], errs
    assert not _exceeded(errs), errs


def test_lower_precision_is_refused(driven):
    """The reference with its kernel products rounded to bfloat16 (the
    nearest precision below the float32 the configuration states) has to
    come out as not correct."""
    import jax.numpy as jnp

    sim, const, _, _, _ = driven
    errs = check_forces_std.compare(sim, const, SEED, TARGETS,
                                    product_dtype=jnp.bfloat16)
    assert set(_exceeded(errs)) == set(LIMITS), errs


def test_dropped_neighbour_is_refused(driven):
    """One neighbour of one target missing from the sums (what a stale
    list does) has to come out as not correct."""
    sim, const, _, _, _ = driven
    state, fields, _ = check_forces_std.system_forces(sim)
    targets = reference.seeded_targets(SEED, state.n, TARGETS)
    x, y, z = (np.asarray(a) for a in (state.x, state.y, state.z))
    t = targets[0]
    d2 = (x - x[t]) ** 2 + (y - y[t]) ** 2 + (z - z[t]) ** 2
    d2[targets] = np.inf  # the dropped one is not itself compared
    lost = int(np.argmin(d2))
    assert d2[lost] < (2.0 * float(state.h[t])) ** 2
    ref = reference_sph_std.std_forces(
        targets, np.where(np.arange(state.n) == lost, x + 100.0, x), y, z,
        state.vx, state.vy, state.vz, state.h, state.m, state.temp,
        gamma=const.gamma, cv=const.cv, sinc_index=const.sinc_index)
    got = {k: np.asarray(v)[targets] for k, v in fields.items()}
    errs = reference_sph_std.errors(got, ref)
    assert {"rho_rel_max", "acc_rel_max", "du_rel_max"} <= set(
        _exceeded(errs)), errs


@pytest.mark.parametrize("key", sorted(TRAJECTORY_RTOL))
def test_trajectory_matches_streaming(driven, streamed, key):
    rows, ref_rows = driven[3], streamed[3]
    assert [r["it"] for r in rows] == [r["it"] for r in ref_rows]
    np.testing.assert_allclose([r[key] for r in rows],
                               [r[key] for r in ref_rows],
                               rtol=TRAJECTORY_RTOL[key])


@pytest.fixture(scope="module", params=[4, 1],
                ids=lambda n: f"check-every-{n}")
def kicked(request):
    """12 steps of Noh on lists, every particle shifted after the eighth
    (a trend is known by then), with and without deferred checks."""
    return _simulate("noh", 12, steps=12, kick_at=8, use_lists=True,
                     check_every=request.param,
                     list_skin_rel=0.1) + (request.param,)


@pytest.fixture(scope="module")
def streamed_12():
    return _simulate("noh", 12, steps=12, use_lists=False,
                     check_every=1)[3]


def test_kick_is_recovered_by_the_net(kicked):
    """What no plan foresees still goes through the recovery that was
    there: a rollback and replay under deferred checks, a discarded step
    and an in-step rebuild where every step is checked."""
    sim, _, sink, rows, check_every = kicked
    after = sink.events[sink.kick_mark:]
    reasons = [e["reason"] for e in after if e["kind"] == "rebuild_lists"]
    rollbacks = [e for e in after if e["kind"] == "rollback"]
    if check_every > 1:
        assert [e["reason"] for e in rollbacks] == ["list-expiry"]
        assert rollbacks[0]["bad_index"] == 0 and "rollback" in reasons
        (replay,) = [e for e in after if e["kind"] == "replay"]
        assert replay["steps"] == rollbacks[0]["steps"]
    else:
        assert rollbacks == [] and reasons[0] == "expiry"
    kicked_build = next(e for e in after if e["kind"] == "rebuild_lists")
    assert kicked_build["slack"] < -0.4 and kicked_build["rate"] > 0.0
    assert [r["it"] for r in rows] == list(range(1, 13))
    assert sim._use_lists and sim.pair_lists is not None
    assert all(validate_event(e) == [] for e in sink.events)


@pytest.mark.parametrize("key", sorted(TRAJECTORY_RTOL))
def test_kicked_trajectory_matches_streaming(kicked, streamed_12, key):
    np.testing.assert_allclose([r[key] for r in kicked[3]],
                               [r[key] for r in streamed_12],
                               rtol=TRAJECTORY_RTOL[key])


class TestSchemaV10:
    def test_v10_and_v11_add_no_kind_and_no_required_field(self):
        # (nor does v12: ``rebuild_lists.slots_live`` / ``slots_cap``)
        assert SCHEMA_VERSION >= 14
        assert not {10, 11, 12, 13, 14} & set(KIND_SINCE.values())
        assert EVENT_KINDS["rebuild_lists"] == ("it",)
        assert EVENT_KINDS["window"] == ("it", "steps", "wall_s",
                                         "per_step_s")

    @pytest.mark.parametrize("version", range(1, 13))
    def test_bare_rebuild_event_validates_at_every_version(self, version):
        # a v1-v9 writer's event carries ``it`` alone; later readers take it
        e = {"v": version, "seq": 0, "t": 1.0, "kind": "rebuild_lists",
             "it": 3}
        assert validate_event(e) == []

    @pytest.mark.parametrize("version", range(1, 13))
    def test_window_without_planned_steps_validates(self, version):
        # a v1-v10 writer's window has no ``planned_steps``
        e = {"v": version, "seq": 0, "t": 1.0, "kind": "window", "it": 8,
             "steps": 4, "wall_s": 1.2, "per_step_s": 0.3}
        assert validate_event(e) == []

    def test_v11_payload_validates(self):
        e = {"v": 11, "seq": 0, "t": 1.0, "kind": "rebuild_lists", "it": 10,
             "reason": "proactive", "age_steps": 7, "slack": 0.1,
             "slot_need": 12, "slot_cap": 16, "attempts": 1,
             "rate": 0.15, "cover_steps": 7}
        assert validate_event(e) == []
        w = {"v": 11, "seq": 1, "t": 1.0, "kind": "window", "it": 10,
             "steps": 3, "wall_s": 0.9, "per_step_s": 0.3,
             "planned_steps": 3}
        assert validate_event(w) == []

    def test_v12_payload_validates(self):
        e = {"v": 12, "seq": 0, "t": 1.0, "kind": "rebuild_lists", "it": 10,
             "reason": "proactive", "age_steps": 7, "slack": 0.1,
             "slot_need": 12, "slot_cap": 16, "slots_live": 4096,
             "slots_cap": 8192, "attempts": 1, "rate": 0.15,
             "cover_steps": 7}
        assert validate_event(e) == []

    def test_v10_payload_validates(self):
        e = {"v": 10, "seq": 0, "t": 1.0, "kind": "rebuild_lists", "it": 8,
             "reason": "rollback", "age_steps": 7, "slack": -0.027,
             "slot_need": 12, "slot_cap": 16, "attempts": 1}
        assert validate_event(e) == []
