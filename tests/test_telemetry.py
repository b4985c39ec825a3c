"""Telemetry subsystem: registry/sink semantics, the zero-sync
deferred-window guard (the runtime JXA104 analog: no device->host
transfer may ride the happy path), rollback/retrace/replay events as
first-class telemetry, and the sphexa-telemetry CLI contracts
(summary schema validation, diff thresholds + exit codes)."""

import dataclasses
import json

import numpy as np
import pytest

import jax

from sphexa_tpu.init import init_sedov
from sphexa_tpu.propagator import STEP_DIAG_KEYS
from sphexa_tpu.simulation import Simulation
from sphexa_tpu.telemetry import (
    ConsoleSink,
    JsonlSink,
    MemorySink,
    SCHEMA_VERSION,
    Telemetry,
    write_manifest,
)
from sphexa_tpu.telemetry.cli import main as cli_main
from sphexa_tpu.telemetry.registry import validate_event


# ---------------------------------------------------------------------------
# registry + sinks
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counters_spans(self):
        t = Telemetry()
        t.count("x")
        t.count("x", 2)
        with t.span("sphexa:p"):
            pass
        with t.span("sphexa:p"):
            pass
        assert t.counters["x"] == 3
        assert t.counters["events.span"] == 2

    def test_event_envelope_and_seq(self):
        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        t.event("note", msg="a")
        t.event("note", msg="b")
        a, b = sink.events
        assert a["v"] == SCHEMA_VERSION and a["kind"] == "note"
        assert (a["seq"], b["seq"]) == (0, 1)
        assert a["msg"] == "a"
        # counted even without reading the sink
        assert t.counters["events.note"] == 2

    def test_sinkless_event_is_counter_only(self):
        t = Telemetry()
        t.event("step", it=1, wall_s=0.1)  # must not raise, must count
        assert t.counters["events.step"] == 1
        assert t._seq == 0  # no envelope built

    def test_numpy_payloads_json_safe(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        t = Telemetry(sinks=[JsonlSink(path)])
        t.event("note", a=np.float32(1.5), b=np.int64(3))
        t.close()
        (e,) = [json.loads(l) for l in open(path)]
        assert e["a"] == 1.5 and e["b"] == 3

    def test_validate_event(self):
        ok = {"v": SCHEMA_VERSION, "seq": 0, "t": 1.0, "kind": "step",
              "it": 1, "wall_s": 0.1}
        assert validate_event(ok) == []
        assert validate_event({**ok, "v": 99})
        # an unknown kind is NOT a schema problem: it is the
        # forward-compat dimension the summary counts separately
        # (unknown_kinds + strict exit code) — flagging it here too
        # would double-report every future-schema event
        assert validate_event({**ok, "kind": "bogus"}) == []
        # ...but a v2-only kind claiming v1 is writer confusion
        assert validate_event({"v": 1, "seq": 0, "t": 1.0,
                               "kind": "exchange", "it": 1,
                               "shipped_rows": 1, "rows": [1]})
        bad = dict(ok)
        del bad["wall_s"]
        assert any("wall_s" in p for p in validate_event(bad))

    @pytest.mark.parametrize("v", [12, 17, SCHEMA_VERSION])
    def test_rebuild_lists_run_tile_fields(self, v):
        """Schema v18: ``chunks_live`` / ``runs_live`` / ``run_rows`` on
        ``rebuild_lists`` are optional payload: an event with them is
        clean, and so are a v12 and a v17 writer's without them."""
        from sphexa_tpu.telemetry.registry import (
            EVENT_KINDS, KIND_SINCE, SUPPORTED_VERSIONS)

        assert SCHEMA_VERSION == 21 == SUPPORTED_VERSIONS[-1]
        assert EVENT_KINDS["rebuild_lists"] == ("it",)
        assert 18 not in KIND_SINCE.values()
        e = {"v": v, "seq": 0, "t": 1.0, "kind": "rebuild_lists", "it": 10,
             "reason": "proactive", "age_steps": 7, "slack": 0.1,
             "slot_need": 231, "slot_cap": 296, "slots_live": 600216,
             "slots_cap": 2375680, "attempts": 1, "rate": 0.15,
             "cover_steps": 7}
        if v >= 18:
            e.update(chunks_live=545000, runs_live=210000, run_rows=4)
        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        t.event("rebuild_lists", **{k: val for k, val in e.items()
                                    if k not in ("v", "seq", "t", "kind")})
        (sent,) = sink.events
        assert validate_event(e) == [] == validate_event(sent)
        assert sent["v"] == SCHEMA_VERSION

    @pytest.mark.parametrize("v", [14, 18, SCHEMA_VERSION])
    def test_exchange_sort_stage_fields(self, v):
        """Schema v19: ``exchange`` of stage ``sort`` (the mesh's global
        sort of a step's aux state) carries ``rows`` as the number of rows
        sorted and the optional ``migrant_rows``; no kind and no required
        field came, so a v14 and a v18 writer's exchange events stay
        clean."""
        from sphexa_tpu.telemetry.registry import (
            EVENT_KINDS, KIND_SINCE, SUPPORTED_VERSIONS)

        assert SUPPORTED_VERSIONS == tuple(range(1, SCHEMA_VERSION + 1))
        assert EVENT_KINDS["exchange"] == ("it", "shipped_rows", "rows")
        assert 19 not in KIND_SINCE.values()
        e = {"v": v, "seq": 0, "t": 1.0, "kind": "exchange", "it": 8,
             "steps": 4, "mode": "sparse", "shipped_rows": 417280,
             "rows": [90210, 117004, 117311, 90077], "stage": "sph",
             "run_slots": 48, "live_runs_max": 34}
        if v >= 19:
            e.update(mode="gspmd", stage="sort", shipped_rows=3141807,
                     rows=4189076, migrant_rows=212)
            for k in ("run_slots", "live_runs_max"):
                del e[k]
        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        t.event("exchange", **{k: val for k, val in e.items()
                               if k not in ("v", "seq", "t", "kind")})
        (sent,) = sink.events
        assert validate_event(e) == [] == validate_event(sent)
        assert sent["v"] == SCHEMA_VERSION

    @pytest.mark.parametrize("v", [14, 19, SCHEMA_VERSION])
    def test_exchange_layout_age_field(self, v):
        """Schema v20: ``exchange`` of stage ``sph`` carries the optional
        ``layout_age_steps`` (the steps the shipped send layout had served:
        a mesh list step's is frozen at its rebuild); no kind and no
        required field came, so a v14 and a v19 writer's stay clean."""
        from sphexa_tpu.telemetry.registry import EVENT_KINDS, KIND_SINCE

        assert EVENT_KINDS["exchange"] == ("it", "shipped_rows", "rows")
        assert 20 not in KIND_SINCE.values()
        e = {"v": v, "seq": 0, "t": 1.0, "kind": "exchange", "it": 8,
             "steps": 4, "mode": "sparse", "shipped_rows": 712704,
             "rows": [590210, 617004, 617311, 590077], "stage": "sph",
             "run_slots": 32, "live_runs_max": 21}
        if v >= 20:
            e.update(layout_age_steps=7)
        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        t.event("exchange", **{k: val for k, val in e.items()
                               if k not in ("v", "seq", "t", "kind")})
        (sent,) = sink.events
        assert validate_event(e) == [] == validate_event(sent)
        assert sent["v"] == SCHEMA_VERSION

    def test_console_sink_and_printer_routing(self):
        lines = []
        sink = ConsoleSink(printer=lines.append)
        t = Telemetry(sinks=[sink])
        t.event("rollback", it=4, steps=3, reason="overflow")
        t.event("launch", it=1)  # not notable: no console line
        assert len(lines) == 1 and "rollback" in lines[0]
        t.console_printer()("raw line")
        assert lines[-1] == "raw line"  # routed through the sink
        assert Telemetry().console_printer(print) is print

    def test_jsonl_round_trip(self, tmp_path):
        from sphexa_tpu.telemetry.cli import load_events

        run = tmp_path / "run"
        t = Telemetry(sinks=[JsonlSink(str(run / "events.jsonl"))])
        t.event("step", it=1, wall_s=0.25, dt=0.1, reconfigured=False)
        t.event("retrace", it=1, delta=2)
        t.close()
        events, problems = load_events(str(run))
        assert problems == []
        assert [e["kind"] for e in events] == ["step", "retrace"]
        assert events[0]["wall_s"] == 0.25 and events[1]["delta"] == 2


# ---------------------------------------------------------------------------
# Simulation wiring
# ---------------------------------------------------------------------------


def _sedov_sim(side=8, telemetry=None, **kw):
    state, box, const = init_sedov(side)
    return Simulation(state, box, const, prop="std", block=4096,
                      telemetry=telemetry, **kw)


class TestSimulationTelemetry:
    def test_step_diag_contract(self):
        sim = _sedov_sim()
        d = sim.step()
        assert set(STEP_DIAG_KEYS) <= set(d)

    def test_sync_steps_emit_step_events(self):
        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]))
        sim.step()
        sim.step()
        steps = sink.of_kind("step")
        assert [e["it"] for e in steps] == [1, 2]
        assert all(e["wall_s"] > 0 and e["dt"] > 0 for e in steps)
        recfg = sink.of_kind("reconfigure")
        assert recfg and recfg[0]["reason"] == "initial"

    def test_deferred_happy_path_is_sync_free(self, tmp_path, monkeypatch):
        """The JXA104-analog runtime guard: with telemetry fully enabled
        (JSONL sink + registry) AND the in-graph observables on (a case
        extra + science rows), deferred-window steps must not issue ANY
        device->host transfer — jax.device_get / block_until_ready are
        poisoned for the whole happy-path window and only restored for
        the flush, which is where the one batched fetch belongs."""
        from sphexa_tpu.observables import ObservableSpec

        sink = JsonlSink(str(tmp_path / "events.jsonl"))
        tel = Telemetry(sinks=[sink])
        sim = _sedov_sim(side=12, telemetry=tel, check_every=4,
                         obs_spec=ObservableSpec(extra="mach"),
                         science_rows=True, drift_budget=1e3)
        # settle compiles + config on a first full window
        for _ in range(4):
            sim.step()

        real_get = jax.device_get

        def boom(*a, **k):
            raise AssertionError(
                "device->host transfer on the deferred happy path"
            )

        monkeypatch.setattr(jax, "device_get", boom)
        monkeypatch.setattr(jax, "block_until_ready", boom)
        for _ in range(3):
            d = sim.step()
            assert d.get("deferred") == 1.0
        monkeypatch.setattr(jax, "device_get", real_get)
        monkeypatch.undo()
        d = sim.flush()
        assert "deferred" not in d or d.get("deferred") != 1.0
        tel.close()

        events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
        kinds = [e["kind"] for e in events]
        # 7 launches (both windows), 2 window flushes, no rollbacks
        assert kinds.count("launch") == 7
        # the spans were on all through the poisoned stretch: the guard
        # holds with them, because a span only stamps the host's clock
        names = [e["name"] for e in events if e["kind"] == "span"]
        assert names.count("sphexa:launch") == 7
        assert names.count("sphexa:pin") == names.count("sphexa:flush") == 2
        windows = [e for e in events if e["kind"] == "window"]
        assert len(windows) == 2
        assert windows[-1]["steps"] == 3
        assert windows[-1]["per_step_s"] > 0
        assert "rollback" not in kinds
        # the science ledger rode the same fetch: one physics + one
        # numerics event per window, every step's row preserved
        phys = [e for e in events if e["kind"] == "physics"]
        assert [e["steps"] for e in phys] == [4, 3]
        assert phys[-1]["its"] == [5, 6, 7]
        assert all(np.isfinite(v) for e in phys for v in e["etot"])
        assert all(len(e["extra"]) == e["steps"] for e in phys)  # machRMS
        nums = [e for e in events if e["kind"] == "numerics"]
        assert len(nums) == 2 and sum(nums[-1]["limiter"].values()) == 3
        assert "drift" not in kinds and "field_health" not in kinds
        rows = sim.drain_science()
        assert [r["it"] for r in rows] == list(range(1, 8))
        assert all(np.isfinite(r["etot"]) and "extra" in r for r in rows)
        assert sim.drain_science() == []  # drained
        assert sim.energy_drift is not None and sim.energy_drift < 1e-3

    def test_rollback_retrace_replay_events(self):
        """A deferred-detected overflow must surface as first-class
        rollback/replay telemetry (it used to be visible only as
        ``reconfigured`` on one diagnostics dict), and the forced
        reconfigure must trip the retrace watchdog.

        side 12 DELIBERATELY collides with test_simulation_async's
        doctored sedov(12)/block-4096/cap-8 config: under alphabetical
        suite order the global jit caches arrive pre-warmed, the cache
        delta is zero, and the old cache-size-only watchdog reported
        nothing (the order-dependent failure this pins). The watchdog
        now baselines executable signatures PER Simulation
        (_launch_signature), so this run's launches under a config it
        never used count as retraces — warm cache or not."""
        state, box, const = init_sedov(12)
        sink = MemorySink()
        from sphexa_tpu.observables import ObservableSpec

        sim = Simulation(state, box, const, prop="std", block=4096,
                         check_every=3, science_rows=True,
                         obs_spec=ObservableSpec(),
                         telemetry=Telemetry(sinks=[sink]))
        sim._cfg = dataclasses.replace(
            sim._cfg, nbr=dataclasses.replace(sim._cfg.nbr, cap=8)
        )
        for _ in range(3):
            sim.step()
        d = sim.flush() if sim._pending else sim._last_diag
        assert d["reconfigured"] == 1.0
        (rb,) = sink.of_kind("rollback")
        assert rb["reason"] == "overflow"
        assert rb["steps"] == 3 and rb["to_it"] == 0 and rb["bad_index"] == 0
        (rp,) = sink.of_kind("replay")
        assert rp["steps"] == 3
        # the replayed window runs through the checked path: 3 step events
        assert len(sink.of_kind("step")) == 3
        assert any(e["reason"] == "overflow"
                   for e in sink.of_kind("reconfigure"))
        assert sim.telemetry.counters["rollbacks"] == 1
        assert sim.telemetry.counters["retraces"] >= 1
        assert sink.of_kind("retrace")
        # science rows: the rolled-back window wrote NONE of its rows —
        # only the replay's verified steps did, so the constants.txt
        # series stays monotone and complete
        rows = sim.drain_science()
        assert [r["it"] for r in rows] == [1, 2, 3]
        assert len(sink.of_kind("physics")) == 3  # one per replayed step

    def test_run_line_survives_missing_diag_keys(self):
        """Simulation.run's report uses .get() + nan for propagator-
        specific scalars and routes through the console sink."""
        lines = []
        sim = _sedov_sim(
            telemetry=Telemetry(sinks=[ConsoleSink(printer=lines.append)])
        )
        sim.step = lambda: {"reconfigured": 0.0}  # diagnostics-poor step
        sim.run(1, log_every=1, printer=None)  # printer unused: sink wins
        (line,) = [l for l in lines if l.startswith("it ")]
        assert "nan" in line and "rho_max=nan" in line

    def test_run_printer_fallback_without_sink(self):
        lines = []
        sim = _sedov_sim(side=8)
        sim.run(1, log_every=1, printer=lines.append)
        assert len(lines) == 1 and "rho_max=" in lines[0]


# ---------------------------------------------------------------------------
# host spans (schema v9): the one recorder, and the spans the driver and
# the dump open at their own boundaries
# ---------------------------------------------------------------------------


def _spans(sink, since=0):
    return [e for e in sink.events[since:] if e["kind"] == "span"]


def _tree(spans):
    """Names of the outermost spans in opening order (ids rise with
    opening), each with the names of its children, nested the same way."""
    spans = sorted(spans, key=lambda e: e["id"])
    known = {e["id"] for e in spans}

    def walk(e):
        below = [walk(c) for c in spans if c["parent"] == e["id"]]
        return (e["name"], below) if below else e["name"]

    return [walk(e) for e in spans if e["parent"] not in known]


class TestSpans:
    def test_span_event_fields_and_clock(self):
        import time

        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        t.iteration = 12
        before = time.perf_counter_ns()
        with t.span("sphexa:x", reason="r") as sp:
            sp["bytes"] = 3
        after = time.perf_counter_ns()
        (e,) = sink.events
        assert e["kind"] == "span" and e["v"] == SCHEMA_VERSION
        assert validate_event(e) == []
        assert (e["name"], e["parent"], e["it"]) == ("sphexa:x", None, 12)
        assert (e["reason"], e["bytes"]) == ("r", 3)
        # perf_counter_ns: subtracts directly from a harness's own spans
        assert before <= e["t0_ns"] <= e["t0_ns"] + e["dur_ns"] <= after

    @pytest.mark.parametrize("version", range(1, 9))
    def test_older_versioned_span_is_flagged(self, version):
        e = {"v": version, "seq": 0, "t": 1.0, "kind": "span",
             "name": "sphexa:x", "id": 1, "parent": None, "it": 0,
             "t0_ns": 1, "dur_ns": 1}
        assert any("v9-only" in p for p in validate_event(e))
        assert validate_event({**e, "v": 9}) == []
        assert validate_event({k: v for k, v in {**e, "v": 9}.items()
                               if k != "dur_ns"})

    def test_nesting_gives_parent_siblings_do_not(self):
        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        with t.span("a") as a:
            with t.span("b") as b:
                with t.span("c") as c:
                    pass
            with t.span("d") as d:
                pass
        with t.span("e") as e:
            pass
        by = {x["name"]: x for x in sink.events}
        assert by["a"]["parent"] is None and by["e"]["parent"] is None
        assert by["b"]["parent"] == a.id and by["d"]["parent"] == a.id
        assert by["c"]["parent"] == b.id
        assert len({a.id, b.id, c.id, d.id, e.id}) == 5
        # emitted at exit: children precede their parent in the stream
        assert [x["name"] for x in sink.events] == ["c", "b", "d", "a", "e"]

    def test_span_survives_an_exception_and_other_threads(self):
        import threading

        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        with pytest.raises(ValueError):
            with t.span("outer"):
                with t.span("raises"):
                    raise ValueError("x")
        seen = {}

        def worker():
            with t.span("elsewhere") as sp:
                seen["parent"] = sp.parent

        with t.span("here"):
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=10)
        assert not th.is_alive()
        # the failed spans were recorded and closed: nothing leaked onto
        # the stack, and a span of another thread has no parent here
        assert [e["name"] for e in sink.events] == [
            "raises", "outer", "elsewhere", "here"]
        assert seen["parent"] is None
        assert sink.events[-1]["parent"] is None

    def test_sinkless_span_is_one_counter_bump(self):
        t = Telemetry()
        with t.span("sphexa:x"):
            pass
        assert dict(t.counters) == {"events.span": 1}
        assert t._seq == 0  # no envelope built

    def test_process_current_recorder(self):
        from sphexa_tpu.telemetry import registry

        sink = MemorySink()
        t = Telemetry(sinks=[sink])
        registry.set_current(t)
        with registry.span("sphexa:x"):
            pass
        assert [e["name"] for e in sink.of_kind("span")] == ["sphexa:x"]
        # constructing a Simulation names its registry; closing un-names
        sim = _sedov_sim()
        with registry.span("sphexa:y"):
            pass
        assert sim.telemetry.counters["events.span"] >= 1
        assert len(sink.of_kind("span")) == 1
        sim.telemetry.close()
        assert registry._CURRENT is None

    def test_one_window_yields_its_spans(self):
        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]), check_every=4)
        for _ in range(4):
            sim.step()  # first window: compiles
        mark = len(sink.events)
        for _ in range(4):
            sim.step()
        spans = _spans(sink, mark)
        assert _tree(spans) == [
            "sphexa:pin", "sphexa:launch", "sphexa:launch", "sphexa:launch",
            "sphexa:launch",
            ("sphexa:flush", ["sphexa:fetch", "sphexa:settle"])]
        dur = {e["name"]: e["dur_ns"] for e in spans}
        assert dur["sphexa:flush"] >= dur["sphexa:fetch"] + dur["sphexa:settle"]
        # the spans of one window share the iteration it opened at; the
        # window before had another
        assert {e["it"] for e in spans} == {4}
        assert {e["it"] for e in _spans(sink)[:3]} == {0}
        launches = [e for e in spans if e["name"] == "sphexa:launch"]
        assert all(e["donated"] is False and e["retrace"] == 0
                   for e in launches)
        # the window event's wall runs from before the pin to after the
        # fetch: the spans inside it cannot exceed it
        (w,) = [e for e in sink.events[mark:] if e["kind"] == "window"]
        inside = (dur["sphexa:pin"] + sum(e["dur_ns"] for e in launches)
                  + dur["sphexa:fetch"])
        assert inside * 1e-9 <= w["wall_s"] + 1e-3

    def test_checked_steps_have_launch_and_fetch_children(self):
        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]))
        sim.step()
        mark = len(sink.events)
        sim.step()
        sim.step()
        step = ("sphexa:step", ["sphexa:launch", "sphexa:fetch"])
        assert _tree(_spans(sink, mark)) == [step, step]
        assert [e["it"] for e in _spans(sink, mark)
                if e["name"] == "sphexa:step"] == [1, 2]

    def test_forced_rollback_yields_its_spans(self):
        state, box, const = init_sedov(12)
        sink = MemorySink()
        sim = Simulation(state, box, const, prop="std", block=4096,
                         check_every=3, telemetry=Telemetry(sinks=[sink]))
        sim._cfg = dataclasses.replace(
            sim._cfg, nbr=dataclasses.replace(sim._cfg.nbr, cap=8))
        mark = len(sink.events)
        for _ in range(3):
            sim.step()
        assert sink.of_kind("rollback")
        spans = _spans(sink, mark)
        (flush,) = [t for t in _tree(spans) if t[0] == "sphexa:flush"]
        fetch, (name, inside) = flush[1]
        assert fetch == "sphexa:fetch" and name == "sphexa:rollback"
        assert inside[0][0] == "sphexa:reconfigure"
        assert "sphexa:size-neighbors" in inside[0][1]
        steps = [t for t in inside[1:]]
        assert len(steps) == 3 and all(t[0] == "sphexa:step" for t in steps)
        # the replay belongs to the window it replays
        assert {e["it"] for e in spans} == {0}
        (rc,) = [e for e in spans if e["name"] == "sphexa:reconfigure"]
        assert rc["reason"] == "overflow"

    @pytest.mark.parametrize("writer", ["h5", "npz", "sharded"])
    def test_dump_yields_program_fetch_and_write(self, tmp_path, writer):
        from sphexa_tpu.analysis import compute_output_fields
        from sphexa_tpu.io import write_snapshot
        from sphexa_tpu.io.snapshot import (
            CONSERVED_FIELDS, write_snapshot_sharded)

        sink = MemorySink()
        state, box, const = init_sedov(8)
        kw = {"num_devices": 2} if writer == "sharded" else {}
        sim = Simulation(state, box, const, prop="std", block=4096,
                         telemetry=Telemetry(sinks=[sink]), **kw)
        sim.step()
        mark = len(sink.events)
        extra = compute_output_fields(sim.state, sim.box, sim.active_cfg)
        path = str(tmp_path / ("dump.npz" if writer == "npz" else "dump.h5"))
        write = write_snapshot_sharded if writer == "sharded" \
            else write_snapshot
        write(path, sim.state, sim.box, const, iteration=1,
              extra_fields=extra)
        spans = _spans(sink, mark)
        names = [e["name"] for e in spans]
        parts = 2 if writer == "sharded" else 1
        assert names[0] == "sphexa:dump-program"
        assert names.count("sphexa:dump-h5") == parts
        n = int(sim.state.n)
        written = (len(CONSERVED_FIELDS) + len(extra)) * n * 4
        fetched = [e for e in spans if e["name"] == "sphexa:dump-fetch"]
        assert sum(e["bytes"] for e in fetched) == written
        assert sum(e["fields"] for e in fetched) \
            == len(extra) + parts * len(CONSERVED_FIELDS)
        h5 = [e for e in spans if e["name"] == "sphexa:dump-h5"]
        assert sum(e["bytes"] for e in h5) == written
        assert {e["format"] for e in h5} == {
            "npz" if writer == "npz" else "h5"}
        # outside main() a dump's spans have no program parent, and they
        # share the iteration of the step they follow
        assert all(e["parent"] is None for e in spans)
        assert {e["it"] for e in spans} == {0}

    def test_capture_holds_the_span_under_its_id(self, tmp_path):
        """Under jax.profiler the same span is in the capture's host
        plane, under the same name, with its id as a stat: the device
        trace and the in-memory event name one stretch of host time."""
        import glob

        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]), check_every=2)
        sim.step()
        sim.step()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            mark = len(sink.events)
            sim.step()
            sim.step()
        finally:
            jax.profiler.stop_trace()
        (pb,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        captured = {}
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("sphexa:"):
                        captured[dict(ev.stats)["id"]] = (
                            ev.name, ev.duration_ns)
        spans = _spans(sink, mark)
        assert {e["name"] for e in spans} >= {
            "sphexa:pin", "sphexa:launch", "sphexa:flush", "sphexa:fetch",
            "sphexa:settle"}
        for e in spans:
            name, dur_ns = captured[e["id"]]
            assert name == e["name"]
            # two clocks read at slightly different instants
            assert abs(dur_ns - e["dur_ns"]) < 2e6 + 0.05 * e["dur_ns"]


# ---------------------------------------------------------------------------
# distributed telemetry (schema v2): sharded no-sync guard, shard events,
# imbalance watchdog, memory snapshots
# ---------------------------------------------------------------------------


class TestDistributedTelemetry:
    def test_sharded_window_sync_free_emits_shard_events(
            self, tmp_path, monkeypatch):
        """Satellite of the JXA104-analog guard, sharded: a 2-virtual-
        device CPU-mesh deferred window with full telemetry must issue
        ZERO device->host transfers on the happy path while still
        producing the schema-v2 ``exchange``/``shard_load`` events at
        the flush. The pre-existing CPU-mesh drain
        (Simulation._drain, a collective-serialization workaround that
        real TPU meshes don't run) is the ONE sanctioned
        block_until_ready — it is re-pointed at the real function so
        everything else stays poisoned."""
        import numpy as np

        from sphexa_tpu.parallel.sizing import device_sparse_halo
        from sphexa_tpu.sfc.box import make_global_box
        from sphexa_tpu.sfc.keys import compute_sfc_keys

        state, box, const = init_sedov(6)  # 216 / 2 devices (audit scale)
        sink = JsonlSink(str(tmp_path / "events.jsonl"))
        tel = Telemetry(sinks=[sink])
        from sphexa_tpu.observables import ObservableSpec

        sim = Simulation(state, box, const, prop="std", block=512,
                         backend="pallas", num_devices=2, check_every=3,
                         obs_spec=ObservableSpec(), telemetry=tel)
        for _ in range(3):  # settle compiles on one full window
            sim.step()

        real_get = jax.device_get
        real_block = jax.block_until_ready

        def boom(*a, **k):
            raise AssertionError(
                "device->host transfer on the sharded deferred happy path"
            )

        # sanction ONLY the drain's block (CPU-mesh artifact guard);
        # any other block/get inside the window is instrumentation debt
        drained = []

        def drain_ok(out):
            drained.append(1)
            real_block([a for a in jax.tree.leaves(out)
                        if hasattr(a, "block_until_ready")])
            return out

        monkeypatch.setattr(jax, "device_get", boom)
        monkeypatch.setattr(jax, "block_until_ready", boom)
        monkeypatch.setattr(sim, "_drain", drain_ok)
        for _ in range(2):
            d = sim.step()
            assert d.get("deferred") == 1.0
        monkeypatch.setattr(jax, "device_get", real_get)
        monkeypatch.setattr(jax, "block_until_ready", real_block)
        monkeypatch.undo()
        sim.flush()
        tel.close()
        assert drained  # the sanctioned drain actually ran

        events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
        by_kind = lambda k: [e for e in events if e["kind"] == k]
        loads = by_kind("shard_load")
        exchanges = by_kind("exchange")
        assert loads and exchanges
        S = state.n // 2
        assert loads[-1]["particles"] == [S, S]
        assert len(exchanges[-1]["rows"]) == 2
        assert exchanges[-1]["shipped_rows"] > 0
        assert exchanges[-1]["mode"] in ("sparse", "windowed")
        # independent size-based check (measure_multichip.py formulas):
        # shipped rows == sum of the sized per-distance caps
        gbox = make_global_box(state.x, state.y, state.z, box)
        keys = compute_sfc_keys(state.x, state.y, state.z, gbox)
        hc, runs = device_sparse_halo(
            state.x, state.y, state.z, state.h, keys, gbox, sim._cfg.nbr,
            P=2, margin=sim._halo_margin)
        assert exchanges[-1]["shipped_rows"] == sum(min(c, S) for c in hc)
        # schema v14: the sized run slots beside the fullest group's runs
        assert all(e["run_slots"] == runs >= e["live_runs_max"] > 0
                   for e in exchanges)
        mems = by_kind("memory")
        assert {e["point"] for e in mems} >= {"post-compile", "flush"}
        # the science ledger rode the same sharded fetch: its sums
        # lowered to the chained collectives, values stayed finite
        phys = by_kind("physics")
        assert [e["steps"] for e in phys] == [3, 2]
        assert all(np.isfinite(v) for e in phys for v in e["etot"])
        assert all(validate_event(e) == [] for e in events)

    def test_snapshot_rides_flush_sync_free(self, tmp_path, monkeypatch):
        """Schema-v8 satellite of the JXA104-analog guard: with in-graph
        snapshots ON over a 2-virtual-device deferred window, the happy
        path must still issue ZERO device->host transfers — the snapshot
        grid rides the SAME batched fetch as the science ledger, and the
        whole window's due frames (.npz ring + ``snapshot`` events) land
        at the flush boundary."""
        from sphexa_tpu.observables import ObservableSpec, SnapshotSpec

        state, box, const = init_sedov(6)  # 216 / 2 devices (audit scale)
        sink = JsonlSink(str(tmp_path / "events.jsonl"))
        tel = Telemetry(sinks=[sink])
        sim = Simulation(state, box, const, prop="std", block=512,
                         backend="pallas", num_devices=2, check_every=3,
                         obs_spec=ObservableSpec(), telemetry=tel,
                         snap_spec=SnapshotSpec(fields=("rho",), grid=8),
                         snap_dir=str(tmp_path / "snapshots"))
        for _ in range(3):  # settle compiles on one full window
            sim.step()
        sim.drain_snapshots()

        real_get = jax.device_get
        real_block = jax.block_until_ready

        def boom(*a, **k):
            raise AssertionError(
                "device->host transfer on the snapshot deferred happy path"
            )

        def drain_ok(out):  # the ONE sanctioned CPU-mesh drain block
            real_block([a for a in jax.tree.leaves(out)
                        if hasattr(a, "block_until_ready")])
            return out

        monkeypatch.setattr(jax, "device_get", boom)
        monkeypatch.setattr(jax, "block_until_ready", boom)
        monkeypatch.setattr(sim, "_drain", drain_ok)
        for _ in range(2):
            d = sim.step()
            assert d.get("deferred") == 1.0
        monkeypatch.setattr(jax, "device_get", real_get)
        monkeypatch.setattr(jax, "block_until_ready", real_block)
        monkeypatch.undo()
        sim.flush()
        tel.close()

        # the deferred window's frames landed WHOLE at the flush
        frames = sim.drain_snapshots()
        assert [it for it, _ in frames] == [4, 5]
        events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
        snaps = [e for e in events if e["kind"] == "snapshot"]
        assert [e["it"] for e in snaps] == [1, 2, 3, 4, 5]
        assert all(e["v"] == SCHEMA_VERSION and validate_event(e) == []
                   for e in snaps)
        for e in snaps:
            z = np.load(e["path"], allow_pickle=False)
            g = np.asarray(z["grid"])
            assert g.shape == (1, 8, 8)
            # the deposit conserves the deposited quantity: cell sums of
            # rho recover the global sum, finite and positive
            assert np.isfinite(g).all() and g.sum() > 0
            assert e["vmax"][0] >= e["vmin"][0] >= 0.0

    def test_imbalance_watchdog_fires_on_skewed_load(self):
        """max/mean of a per-shard metric past the configured ratio is a
        first-class ``imbalance`` event (+ counter), mirroring the
        retrace watchdog — unit-level via a stub mesh so the watchdog
        logic is pinned without a 90-second mesh run."""
        from types import SimpleNamespace

        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]))
        sim._mesh = SimpleNamespace(size=2)
        sim._halo_info = {"mode": "sparse", "shipped_rows": 128,
                          "bytes_per_step": 128 * 18 * 4}
        sim._emit_distributed(
            {"shard_work": np.asarray([300.0, 100.0]),
             "shard_rows": np.asarray([64, 64], np.int32),
             "shard_occ": np.asarray([0.5, 0.5], np.float32),
             "shard_trips": np.asarray([0, 0], np.int32)},
            steps=1,
        )
        (imb,) = sink.of_kind("imbalance")
        assert imb["metric"] == "work"
        assert imb["ratio"] == pytest.approx(1.5)  # 300 / 200
        assert imb["threshold"] == 1.5
        assert sim.telemetry.counters["imbalances"] == 1
        (ex,) = sink.of_kind("exchange")
        assert ex["rows"] == [64, 64] and ex["shipped_rows"] == 128
        (load,) = sink.of_kind("shard_load")
        assert load["work"] == [300.0, 100.0]
        # balanced load below the ratio stays silent
        sim._emit_distributed(
            {"shard_work": np.asarray([100.0, 100.0]),
             "shard_rows": np.asarray([64, 64], np.int32),
             "shard_occ": np.asarray([0.5, 0.5], np.float32),
             "shard_trips": np.asarray([0, 0], np.int32)},
            steps=1,
        )
        assert len(sink.of_kind("imbalance")) == 1

    def test_memory_snapshot_shape_and_event(self):
        from sphexa_tpu.telemetry import (
            device_memory_snapshot,
            emit_memory_event,
        )

        snap = device_memory_snapshot()
        assert len(snap["devices"]) == len(jax.local_devices())
        # CPU has no allocator stats: byte lists empty but PRESENT, so
        # the mesh rehearsal validates the same schema the chip writes
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            assert isinstance(snap[k], list)
        sink = MemorySink()
        tel = Telemetry(sinks=[sink])
        out = emit_memory_event(tel, "manifest")
        assert out is not None
        (e,) = sink.of_kind("memory")
        assert e["point"] == "manifest"
        assert validate_event(e) == []
        # sink-less registry: snapshot skipped entirely (not worth the
        # per-device stat calls for a counter bump)
        assert emit_memory_event(Telemetry(), "manifest") is None


# ---------------------------------------------------------------------------
# physics observability (schema v3): ledger events, drift + field-health
# watchdogs
# ---------------------------------------------------------------------------


class TestScienceTelemetry:
    def test_drift_watchdog_fires_on_energy_leak(self):
        """A seeded energy leak (internal energy doubled mid-run) must
        cross the configured drift budget and surface as a first-class
        ``drift`` event + counter — the conservation contract of long
        unattended runs (Keller et al. 2023)."""
        from sphexa_tpu.observables import ObservableSpec

        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]),
                         drift_budget=0.05, obs_spec=ObservableSpec())
        sim.step()  # establishes etot0
        assert sink.of_kind("drift") == []
        sim.state = dataclasses.replace(sim.state,
                                        temp=sim.state.temp * 2.0)
        sim.step()
        events = sink.of_kind("drift")
        assert events and events[-1]["drift"] > 0.05
        assert events[-1]["budget"] == 0.05
        assert sim.telemetry.counters["drifts"] >= 1
        assert sim.energy_drift > 0.05
        from sphexa_tpu.telemetry.registry import validate_event

        assert all(validate_event(e) == [] for e in sink.events)

    def test_drift_watchdog_fires_on_mid_window_excursion(self):
        """A transient leak that relaxes before the flush must still
        fire: the watchdog gates on the WINDOW MAX drift, matching the
        offline science --budget gate over the full series (unit-level
        via doctored fetched diagnostics, like the imbalance test)."""
        def diag(it, etot):
            return {"obs_ttot": it * 1e-3, "dt": 1e-3, "obs_etot": etot,
                    "obs_ecin": 0.0, "obs_eint": etot, "obs_egrav": 0.0,
                    "obs_linmom": 0.0, "obs_angmom": 0.0}

        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]),
                         drift_budget=0.1)
        # spike at step 2, fully relaxed by the window's last step
        sim._emit_science([diag(1, 1.0), diag(2, 1.5), diag(3, 1.0)],
                          [1, 2, 3])
        (ev,) = sink.of_kind("drift")
        assert ev["it"] == 2 and ev["drift"] == pytest.approx(0.5)
        assert sim.energy_drift == pytest.approx(0.0)  # latest verified

    def test_drift_watchdog_silent_without_budget(self):
        """Default is report-only: no budget, no drift events — but the
        drift itself is still tracked for bench/CLI consumers."""
        from sphexa_tpu.observables import ObservableSpec

        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]),
                         obs_spec=ObservableSpec())
        sim.step()
        sim.state = dataclasses.replace(sim.state,
                                        temp=sim.state.temp * 2.0)
        sim.step()
        assert sink.of_kind("drift") == []
        assert sim.energy_drift > 0.05

    def test_field_health_watchdog_fires_on_seeded_nan(self):
        """A seeded NaN velocity must poison du in the next step and
        surface as a ``field_health`` event naming the bad field —
        with the pointer at --debug-checks for localization."""
        import numpy as np

        from sphexa_tpu.observables import ObservableSpec

        sink = MemorySink()
        sim = _sedov_sim(telemetry=Telemetry(sinks=[sink]),
                         obs_spec=ObservableSpec())
        sim.step()
        assert sink.of_kind("field_health") == []
        vx = np.asarray(sim.state.vx).copy()
        vx[0] = np.nan
        import jax.numpy as jnp

        sim.state = dataclasses.replace(sim.state, vx=jnp.asarray(vx))
        d = sim.step()
        assert int(d["n_bad_du"]) > 0
        (ev,) = sink.of_kind("field_health")
        assert ev["nonfinite"] > 0 and ev["fields"]["du"] > 0
        assert "--debug-checks" in ev["hint"]
        assert sim.telemetry.counters["field_health"] == 1

# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _make_run(tmp_path, name, step_walls, particles=1000):
    d = tmp_path / name
    t = Telemetry(sinks=[JsonlSink(str(d / "events.jsonl"))])
    for i, w in enumerate(step_walls, 1):
        t.event("step", it=i, wall_s=w, dt=0.1, reconfigured=False)
    t.event("retrace", it=1, delta=1)
    t.close()
    write_manifest(str(d), particles=particles, config={"side": 8})
    return str(d)


class TestCli:
    def test_summary_text_and_json(self, tmp_path, capsys):
        run = _make_run(tmp_path, "a", [0.1, 0.2, 0.3])
        assert cli_main(["summary", run]) == 0
        out = capsys.readouterr().out
        assert "step time p50" in out and "retraces" in out
        assert cli_main(["summary", run, "--format", "json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert s["steps"] == 3 and s["retraces"] == 1
        assert s["step_time"]["p50_s"] == pytest.approx(0.2)
        assert s["manifest"]["particles"] == 1000

    def test_summary_strict_flags_schema_drift(self, tmp_path, capsys):
        run = _make_run(tmp_path, "a", [0.1])
        with open(f"{run}/events.jsonl", "a") as f:
            f.write('{"v":1,"seq":9,"t":1.0,"kind":"bogus"}\n')
            f.write("not json\n")
            # truncated step/window events (killed run): flagged but must
            # not crash the aggregation
            f.write('{"v":1,"seq":10,"t":1.0,"kind":"step","it":2}\n')
            f.write('{"v":1,"seq":11,"t":1.0,"kind":"window","it":3,'
                    '"steps":2}\n')
        assert cli_main(["summary", run]) == 0  # lax by default
        out = capsys.readouterr().out
        assert "steps" in out
        assert cli_main(["summary", run, "--strict"]) == 1
        assert "schema:" in capsys.readouterr().out

    def test_jsonl_sink_truncates_per_run(self, tmp_path):
        """One sink = one run: re-running into the same --telemetry-dir
        must not merge two runs' events under one manifest."""
        from sphexa_tpu.telemetry.cli import load_events

        path = str(tmp_path / "events.jsonl")
        for it in (1, 2):
            t = Telemetry(sinks=[JsonlSink(path)])
            t.event("step", it=it, wall_s=0.1)
            t.close()
        events, problems = load_events(str(tmp_path))
        assert problems == []
        assert len(events) == 1 and events[0]["it"] == 2

    def test_summary_excludes_initial_configure(self, tmp_path):
        from sphexa_tpu.telemetry.cli import summarize_run

        sim = _sedov_sim(
            telemetry=Telemetry(
                sinks=[JsonlSink(str(tmp_path / "events.jsonl"))])
        )
        sim.step()
        sim.telemetry.close()
        s = summarize_run(str(tmp_path))
        # the construction-time sizing is not a mid-run reconfigure
        assert s["reconfigures"] == 0
        assert sim.telemetry.counters.get("reconfigures", 0) == 0
        assert sim.telemetry.counters["events.reconfigure"] == 1

    def test_summary_missing_run_is_usage_error(self, tmp_path, capsys):
        assert cli_main(["summary", str(tmp_path / "nope")]) == 2
        assert "events.jsonl" in capsys.readouterr().err

    def test_diff_runs_threshold_exit_codes(self, tmp_path, capsys):
        base = _make_run(tmp_path, "base", [0.1] * 5)
        cand = _make_run(tmp_path, "cand", [0.25] * 5)
        # 150% slower: beyond a 50% threshold, within a 200% one
        assert cli_main(["diff", base, cand, "--threshold", "0.5"]) == 1
        assert "REGRESSED" in capsys.readouterr().out
        assert cli_main(["diff", base, cand, "--threshold", "2.0"]) == 0
        # faster candidate is never a step-time regression
        assert cli_main(["diff", cand, base, "--threshold", "0.5"]) == 0

    def test_diff_bench_files(self, tmp_path, capsys):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(
            {"metric": "m", "value": 100.0, "unit": "u",
             "extra": {"ve_updates_per_sec": 70.0}}))
        # driver wrapper shape (BENCH_r*.json): bench line buried in tail
        b.write_text(json.dumps(
            {"n": 5, "rc": 0,
             "tail": "warn\n" + json.dumps(
                 {"metric": "m", "value": 50.0, "unit": "u",
                  "extra": {"ve_updates_per_sec": 90.0}})}))
        assert cli_main(["diff", str(a), str(b)]) == 1  # throughput halved
        capsys.readouterr()
        assert cli_main(["diff", str(b), str(a)]) == 0
        out = capsys.readouterr().out
        assert "updates_per_sec" in out

    def test_diff_run_vs_bench(self, tmp_path):
        # run: 1000 particles / 0.1 s p50 = 1e4 ups vs bench 5e3 -> ok
        run = _make_run(tmp_path, "run", [0.1] * 4, particles=1000)
        bench = tmp_path / "bench.json"
        bench.write_text(json.dumps({"metric": "m", "value": 5e3,
                                     "unit": "u"}))
        assert cli_main(["diff", str(bench), run]) == 0
        # and a bench far above the run's throughput regresses
        bench.write_text(json.dumps({"metric": "m", "value": 5e5,
                                     "unit": "u"}))
        assert cli_main(["diff", str(bench), run]) == 1

    def test_strict_reports_unknown_kind_counts(self, tmp_path, capsys):
        """Forward compat: kinds this reader does not know are COUNTED
        and reported (never silently dropped from the aggregation);
        --strict turns them into exit 1 so CI notices version skew."""
        run = _make_run(tmp_path, "a", [0.1])
        with open(f"{run}/events.jsonl", "a") as f:
            f.write(json.dumps({"v": SCHEMA_VERSION, "seq": 8, "t": 1.0,
                                "kind": "from_the_future", "x": 1}) + "\n")
            f.write(json.dumps({"v": SCHEMA_VERSION, "seq": 9, "t": 1.0,
                                "kind": "from_the_future", "x": 2}) + "\n")
        assert cli_main(["summary", run]) == 0  # lax: reported, not fatal
        out = capsys.readouterr().out
        assert "unknown kind: from_the_future x2" in out
        assert cli_main(["summary", run, "--strict"]) == 1
        capsys.readouterr()
        assert cli_main(["summary", run, "--format", "json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert s["unknown_kinds"] == {"from_the_future": 2}

    def test_v1_v2_v3_files_validate_under_v4_reader(self, tmp_path,
                                                     capsys):
        """The version-compat contract: files written by the v1-v3
        schemas (older envelopes, their own kinds) summarize strictly
        clean under this v4 reader; a newer-only kind claiming an older
        version is flagged."""
        d = tmp_path / "v1run"
        d.mkdir()
        with open(d / "events.jsonl", "w") as f:
            f.write('{"v":1,"seq":0,"t":1.0,"kind":"step","it":1,'
                    '"wall_s":0.1}\n')
            f.write('{"v":1,"seq":1,"t":1.0,"kind":"retrace","it":1,'
                    '"delta":1}\n')
            # v2 envelope with a v2 kind: valid under the v4 reader
            f.write('{"v":2,"seq":2,"t":1.0,"kind":"exchange","it":1,'
                    '"shipped_rows":1,"rows":[1]}\n')
            # v3 envelope with a v3 kind: valid too
            f.write('{"v":3,"seq":3,"t":1.0,"kind":"physics","it":1,'
                    '"etot":[1.0]}\n')
            # v4 kinds on a v4 envelope: valid
            f.write('{"v":4,"seq":4,"t":1.0,"kind":"phase_attr",'
                    '"phases":{"density":10.0},"coverage":0.9}\n')
            f.write('{"v":4,"seq":5,"t":1.0,"kind":"crash",'
                    '"reason":"signal SIGTERM"}\n')
        assert cli_main(["summary", str(d), "--strict"]) == 0
        capsys.readouterr()
        with open(d / "events.jsonl", "a") as f:
            f.write('{"v":1,"seq":6,"t":1.0,"kind":"exchange","it":2,'
                    '"shipped_rows":1,"rows":[1]}\n')
        assert cli_main(["summary", str(d), "--strict"]) == 1
        assert "v2-only kind" in capsys.readouterr().out
        with open(d / "events.jsonl", "a") as f:
            f.write('{"v":2,"seq":7,"t":1.0,"kind":"physics","it":3,'
                    '"etot":[1.0]}\n')
        assert cli_main(["summary", str(d), "--strict"]) == 1
        assert "v3-only kind" in capsys.readouterr().out
        # a v4-only kind claiming a v3 envelope is writer confusion
        with open(d / "events.jsonl", "a") as f:
            f.write('{"v":3,"seq":8,"t":1.0,"kind":"crash",'
                    '"reason":"x"}\n')
        assert cli_main(["summary", str(d), "--strict"]) == 1
        assert "v4-only kind" in capsys.readouterr().out

    def _make_shard_run(self, tmp_path):
        d = tmp_path / "mesh"
        t = Telemetry(sinks=[JsonlSink(str(d / "events.jsonl"))])
        for it in (3, 6):
            t.event("shard_load", it=it, steps=3,
                    particles=[256, 256], work=[900.0 + it, 700.0])
            t.event("exchange", it=it, steps=3, mode="sparse",
                    shipped_rows=512, rows=[200 + it, 150],
                    occ=[0.8, 0.6], bytes_per_step=512 * 18 * 4, trips=1)
        t.event("memory", point="flush", it=6, devices=["0", "1"],
                bytes_in_use=[1024, 2048], peak_bytes_in_use=[4096, 8192])
        t.event("imbalance", it=6, metric="work", ratio=1.6,
                threshold=1.5)
        t.close()
        write_manifest(str(d), particles=512, mesh_shape=(2,))
        return str(d)

    def test_shards_view_renders_and_aggregates(self, tmp_path, capsys):
        run = self._make_shard_run(tmp_path)
        assert cli_main(["shards", run]) == 0
        out = capsys.readouterr().out
        assert "halo rows" in out and "occ p95" in out
        assert "sparse" in out and "escape trips" in out
        assert "memory snapshots:" in out
        assert cli_main(["shards", run, "--format", "json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert [sh["shard"] for sh in s["shards"]] == [0, 1]
        assert s["shards"][0]["particles"] == 256
        assert s["shards"][0]["work_share"] > s["shards"][1]["work_share"]
        assert s["shipped_rows"] == 512 and s["mode"] == "sparse"
        assert s["imbalance_events"] == 1 and s["trips"] == 1
        assert s["memory"][0]["peak_bytes_in_use"] == [4096, 8192]

    def test_shards_view_splits_gravity_stage(self, tmp_path, capsys):
        """Schema v7: a run with BOTH staged exchange records renders
        the SPH columns unchanged plus the gravity serve's columns and
        summary block; the stages never mix (the gravity rows must not
        pollute the SPH halo-rows aggregate)."""
        d = tmp_path / "gmesh"
        t = Telemetry(sinks=[JsonlSink(str(d / "events.jsonl"))])
        for it in (3, 6):
            t.event("shard_load", it=it, steps=3, stage="sph",
                    particles=[256, 256], work=[900.0 + it, 700.0])
            t.event("exchange", it=it, steps=3, mode="sparse",
                    shipped_rows=512, rows=[200 + it, 150],
                    occ=[0.8, 0.6], bytes_per_step=512 * 18 * 4,
                    trips=0, stage="sph")
            t.event("exchange", it=it, steps=3, mode="sparse",
                    shipped_rows=2864, rows=[1000 + it, 900],
                    occ=[0.95, 0.7], bytes_per_step=2864 * 5 * 4,
                    trips=1, stage="gravity")
            # schema v19: the sort's record of a step with an aux state
            # (``rows`` an int) belongs to neither stage's aggregate
            t.event("exchange", it=it, steps=3, mode="gspmd",
                    shipped_rows=256, rows=512, migrant_rows=3,
                    stage="sort")
        t.close()
        write_manifest(str(d), particles=512, mesh_shape=(2,))
        assert cli_main(["shards", str(d)]) == 0
        out = capsys.readouterr().out
        assert "grav rows" in out and "grav occ" in out
        assert "gravity rows/serve" in out and "gravity trips" in out
        assert cli_main(["shards", str(d), "--format", "json"]) == 0
        s = json.loads(capsys.readouterr().out)
        # SPH aggregates untouched by the gravity records
        assert s["shipped_rows"] == 512 and s["trips"] == 0
        assert s["shards"][0]["rows_mean"] < 1000
        g = s["gravity"]
        assert g["shipped_rows"] == 2864 and g["trips"] == 1
        assert g["windows"] == 2 and g["mode"] == "sparse"
        assert s["shards"][0]["grav_rows_mean"] > 1000
        assert 0 < s["shards"][1]["grav_occ_p95"] <= 1.0

    def test_shards_exit_1_without_shard_telemetry(self, tmp_path, capsys):
        """The mesh smoke's assertion: a run with no per-shard events
        must FAIL the shards view (exit 1), so check.sh catches a
        silently un-instrumented mesh run."""
        run = _make_run(tmp_path, "plain", [0.1])
        assert cli_main(["shards", run]) == 1
        assert "no per-shard telemetry" in capsys.readouterr().out

    def test_diff_multichip_wrapper(self, tmp_path, capsys):
        """MULTICHIP_r*.json wrapper diffing: the measure_multichip
        --json line buried in a driver-wrapper tail compares with
        threshold exit codes — comm-volume saving is higher-is-better."""
        base = tmp_path / "MULTICHIP_base.json"
        cand = tmp_path / "mc_cand.json"
        line = {"metric": "sparse-halo saving vs replication", "value": 4.0,
                "unit": "x", "extra": {"s16_p8_shipped_frac": 0.5,
                                       "s16_p8_saving": 4.0}}
        base.write_text(json.dumps(
            {"n_devices": 8, "rc": 0, "ok": True,
             "tail": "dryrun OK\n" + json.dumps(line)}))
        cand.write_text(json.dumps(line))  # identical candidate
        assert cli_main(["diff", str(base), str(cand)]) == 0
        capsys.readouterr()
        worse = dict(line, value=3.0,
                     extra={"s16_p8_shipped_frac": 0.7,
                            "s16_p8_saving": 3.0})
        cand.write_text(json.dumps(worse))
        # saving dropped 25%: beyond a 5% threshold -> regression exit 1
        assert cli_main(["diff", str(base), str(cand),
                         "--threshold", "0.05"]) == 1
        assert "REGRESSED" in capsys.readouterr().out

    def _make_science_run(self, tmp_path, name, etots, nan_steps=0,
                          watchdogs=()):
        d = tmp_path / name
        t = Telemetry(sinks=[JsonlSink(str(d / "events.jsonl"))])
        n = len(etots)
        t.event("physics", it=n, steps=n, its=list(range(1, n + 1)),
                t_sim=[0.001 * i for i in range(1, n + 1)],
                dt=[0.001] * n, etot=etots, ecin=[0.0] * n,
                eint=etots, egrav=[0.0] * n, linmom=[0.0] * n,
                angmom=[0.0] * n)
        t.event("numerics", it=n, steps=n,
                limiter={"courant": n - 1, "growth": 1},
                nonfinite={"rho": 0, "h": 0, "du": nan_steps},
                nc_clip=0, h_sat=2, rho_min=0.9, rho_max=1.5,
                h_min=0.1, h_max=0.2, du_max=0.3)
        for kind in watchdogs:
            if kind == "drift":
                t.event("drift", it=n, drift=0.5, budget=0.1,
                        etot0=etots[0], etot=etots[-1])
            else:
                t.event("field_health", it=n, nonfinite=nan_steps,
                        fields={"du": nan_steps}, hint="--debug-checks")
        t.close()
        write_manifest(str(d), particles=512)
        return str(d)

    def test_science_renders_and_exit_codes(self, tmp_path, capsys):
        run = self._make_science_run(tmp_path, "clean", [1.0, 1.0, 1.0])
        assert cli_main(["science", run]) == 0
        out = capsys.readouterr().out
        assert "|drift| max" in out and "timestep limiter" in out
        assert "courant" in out and "extrema timeline" in out
        assert cli_main(["science", run, "--format", "json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert s["steps"] == 3 and s["drift"]["max"] == 0.0
        assert s["limiter"] == {"courant": 2, "growth": 1}
        # budget gate: 10% drift against a 5% budget fails, 20% passes
        leaky = self._make_science_run(tmp_path, "leaky", [1.0, 1.05, 1.1])
        assert cli_main(["science", leaky, "--budget", "0.05"]) == 1
        capsys.readouterr()
        assert cli_main(["science", leaky, "--budget", "0.2"]) == 0
        capsys.readouterr()
        # without a budget, in-run watchdog events decide the exit code
        fired = self._make_science_run(tmp_path, "fired", [1.0, 1.5],
                                       watchdogs=("drift",))
        assert cli_main(["science", fired]) == 1
        capsys.readouterr()
        sick = self._make_science_run(tmp_path, "sick", [1.0, float("nan")],
                                      nan_steps=3,
                                      watchdogs=("field_health",))
        assert cli_main(["science", sick]) == 1
        out = capsys.readouterr().out
        assert "field-health events" in out

    def test_science_partial_run_no_traceback(self, tmp_path, capsys):
        """Satellite regression: a run that crashed before its first
        flush (launch events only, possibly a truncated trailing line)
        must render partial output from BOTH summary and science — exit
        codes, never tracebacks."""
        d = tmp_path / "crashed"
        t = Telemetry(sinks=[JsonlSink(str(d / "events.jsonl"))])
        t.event("reconfigure", it=0, reason="initial")
        for i in (1, 2, 3):
            t.event("launch", it=i)
        t.close()
        write_manifest(str(d), particles=64)
        with open(d / "events.jsonl", "a") as f:
            f.write('{"v":3,"seq":99,"t":1.0,"kind":"phys')  # killed mid-write
        assert cli_main(["summary", str(d)]) == 0
        out = capsys.readouterr().out
        assert "steps" in out and "schema: line 5" in out
        assert cli_main(["science", str(d)]) == 1  # no ledger: must fail
        assert "no physics telemetry" in capsys.readouterr().out
        # strict still flags the truncated line without crashing
        assert cli_main(["summary", str(d), "--strict"]) == 1

    def test_diff_drift_threshold_exit_codes(self, tmp_path, capsys):
        base = self._make_science_run(tmp_path, "dbase",
                                      [1.0, 1.001, 1.002])  # 0.2% drift
        cand = self._make_science_run(tmp_path, "dcand",
                                      [1.0, 1.005, 1.01])   # 1% drift
        # drift x5 vs baseline: regression beyond a 100% threshold
        assert cli_main(["diff", base, cand, "--drift",
                         "--threshold", "1.0"]) == 1
        assert "energy_drift_max" in capsys.readouterr().out
        assert cli_main(["diff", base, cand, "--drift",
                         "--threshold", "10.0"]) == 0
        capsys.readouterr()
        # improving drift never regresses
        assert cli_main(["diff", cand, base, "--drift",
                         "--threshold", "1.0"]) == 0
        capsys.readouterr()
        # without --drift the drift row informs but cannot regress
        assert cli_main(["diff", base, cand, "--threshold", "1.0"]) == 0
        capsys.readouterr()
        # --drift needs physics telemetry on both sides
        plain = _make_run(tmp_path, "noledger", [0.1])
        assert cli_main(["diff", base, plain, "--drift"]) == 2
        assert "--drift" in capsys.readouterr().err

    def test_app_writes_manifest_and_events(self, tmp_path):
        import os

        from sphexa_tpu.app.main import main as app_main
        from sphexa_tpu.telemetry.cli import summarize_run

        tdir = str(tmp_path / "telemetry")
        rc = app_main(["--init", "sedov", "-n", "6", "-s", "2", "--quiet",
                       "-o", str(tmp_path / "out"), "--telemetry-dir", tdir])
        assert rc == 0
        s = summarize_run(tdir)
        assert s["schema_problems"] == []
        assert s["steps"] == 2
        assert s["manifest"]["particles"] == 216
        assert s["manifest"]["config"]["prop"] == "std"
        assert s["phase_mean_s"]  # Timer laps flowed through as phases
        assert cli_main(["summary", tdir, "--strict"]) == 0
        # the in-graph ledger made it into the record: science renders
        assert cli_main(["science", tdir]) == 0
        # clean exit: the flight recorder disarmed, no blackbox written
        assert not os.path.exists(os.path.join(tdir, "blackbox.json"))
        assert s["crash"] is None


# ---------------------------------------------------------------------------
# cross-run history + the regression lock (schema v4 CLI)
# ---------------------------------------------------------------------------


class TestHistoryAndRegress:
    def _bench_file(self, tmp_path, name, value, ve=None, wrapped=False,
                    extra=None):
        line = {"metric": "particle-updates/sec/chip", "value": value,
                "unit": "particles/s", "vs_baseline": value / 2e7,
                "extra": dict(extra or {})}
        if ve is not None:
            line["extra"]["ve_updates_per_sec"] = ve
        p = tmp_path / name
        if wrapped:
            p.write_text(json.dumps(
                {"n": 5, "rc": 0, "tail": "noise\n" + json.dumps(line)}))
        else:
            p.write_text(json.dumps(line))
        return str(p)

    def test_history_renders_rounds_and_trend(self, tmp_path, capsys):
        self._bench_file(tmp_path, "BENCH_r01.json", 1.0e6, wrapped=True)
        self._bench_file(tmp_path, "BENCH_r02.json", 2.0e6, ve=1.5e6)
        # a committed skipped round keeps its row instead of erroring
        (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(
            {"n_devices": 8, "rc": 0, "ok": True, "tail": "dry run"}))
        assert cli_main(["history", "--root", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "r01" in out and "r02" in out
        assert "+100.0%" in out  # 1.0 -> 2.0 M/s between rounds
        assert "dry-run ok" in out
        assert "bench trajectory" in out
        assert cli_main(["history", "--root", str(tmp_path),
                         "--format", "json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert [r["round"] for r in rows] == [1, 2, 1]
        assert rows[1]["change"] == pytest.approx(1.0)
        # empty root: nothing to trend is exit 1, not a fake table
        empty = tmp_path / "empty"
        empty.mkdir()
        assert cli_main(["history", "--root", str(empty)]) == 1
        capsys.readouterr()
        # unreadable input is a usage error
        assert cli_main(["history", str(tmp_path / "nope.json")]) == 2
        # an explicit input that is valid JSON but NOT a bench/wrapper
        # file (a manifest, the lock itself, a typo) must exit 2 too,
        # not fabricate a value-less row
        stray = tmp_path / "manifest.json"
        stray.write_text(json.dumps({"schema": 1, "particles": 64}))
        assert cli_main(["history", str(stray)]) == 2
        # a round-NAMED file with non-dict JSON is corrupt, not a dry
        # run: exit 2, no traceback
        corrupt = tmp_path / "BENCH_r09.json"
        corrupt.write_text("[1, 2]")
        assert cli_main(["history", str(corrupt)]) == 2

    def _lock_file(self, tmp_path, value, source="BENCH_r05.json",
                   field="value", threshold=0.05):
        lock = {"schema": 1, "metrics": [
            {"name": "std_updates_per_sec", "source": source,
             "field": field, "value": value, "threshold": threshold,
             "higher_is_better": True}]}
        p = tmp_path / "LOCK.json"
        p.write_text(json.dumps(lock))
        return str(p)

    def test_regress_exit_codes(self, tmp_path, capsys):
        self._bench_file(tmp_path, "BENCH_r05.json", 3.5e6, wrapped=True)
        # holding: committed value matches the lock
        lock = self._lock_file(tmp_path, 3.5e6)
        assert cli_main(["regress", "--lock", lock]) == 0
        assert "all locked metrics hold" in capsys.readouterr().out
        # a doctored lock claiming a higher chip number fails the gate
        lock = self._lock_file(tmp_path, 4.2e6)
        assert cli_main(["regress", "--lock", lock]) == 1
        out = capsys.readouterr().out
        assert "REGRESSED" in out and "regression vs lock" in out
        # within threshold: 3% below a 5% budget still holds
        lock = self._lock_file(tmp_path, 3.6e6)
        assert cli_main(["regress", "--lock", lock]) == 0
        capsys.readouterr()
        # a missing source/field must FAIL, not silently pass
        lock = self._lock_file(tmp_path, 3.5e6, source="GONE.json")
        assert cli_main(["regress", "--lock", lock]) == 1
        assert "problem:" in capsys.readouterr().out
        lock = self._lock_file(tmp_path, 3.5e6, field="extra.nope")
        assert cli_main(["regress", "--lock", lock]) == 1
        capsys.readouterr()
        # unreadable lock file is a usage error
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert cli_main(["regress", "--lock", str(bad)]) == 2

    def test_regress_candidate_and_write(self, tmp_path, capsys):
        """The harvest-day workflow: gate a FRESH measurement against
        the lock before committing it, then --write to lock it in."""
        self._bench_file(tmp_path, "BENCH_r05.json", 3.5e6, wrapped=True)
        lock = self._lock_file(tmp_path, 3.5e6)
        good = self._bench_file(tmp_path, "fresh.json", 3.8e6)
        worse = self._bench_file(tmp_path, "slow.json", 3.0e6)
        assert cli_main(["regress", "--lock", lock, good]) == 0
        capsys.readouterr()
        assert cli_main(["regress", "--lock", lock, worse]) == 1
        capsys.readouterr()
        # --write + candidate is a usage error: it would silently relock
        # the stale committed values, not the fresh file
        assert cli_main(["regress", "--lock", lock, good, "--write"]) == 2
        capsys.readouterr()
        # --write re-reads the committed source and locks its value
        self._bench_file(tmp_path, "BENCH_r05.json", 3.9e6, wrapped=True)
        assert cli_main(["regress", "--lock", lock, "--write"]) == 0
        capsys.readouterr()
        locked = json.loads(open(lock).read())
        assert locked["metrics"][0]["value"] == pytest.approx(3.9e6)
        assert cli_main(["regress", "--lock", lock]) == 0

    def test_regress_candidate_gates_matching_kind_only(self, tmp_path,
                                                        capsys):
        """A candidate measures ONE kind: its metrics are gated, the
        other kind's locked metrics are skipped (a fresh BENCH says
        nothing about the multichip saving — comparing a throughput
        against a saving ratio was a nonsense verdict either way), and
        a candidate matching NO locked metric fails."""
        self._bench_file(tmp_path, "BENCH_r05.json", 3.5e6, wrapped=True)
        lock = {"schema": 1, "metrics": [
            {"name": "std_updates_per_sec", "source": "BENCH_r05.json",
             "field": "value", "value": 3.5e6, "threshold": 0.05},
            {"name": "multichip_sparse_saving",
             "source": "MULTICHIP_BASELINE.json", "field": "value",
             "value": 1.25, "threshold": 0.05}]}
        lp = tmp_path / "LOCK.json"
        lp.write_text(json.dumps(lock))
        # bench candidate: throughput gated, the saving skipped — worse
        # throughput still fails, a BETTER one passes even though 3.8e6
        # vs the locked 1.25 saving would be nonsense
        good = self._bench_file(tmp_path, "fresh.json", 3.8e6)
        assert cli_main(["regress", "--lock", str(lp), good]) == 0
        out = capsys.readouterr().out
        assert "skipped" in out and "REGRESSED" not in out
        worse = self._bench_file(tmp_path, "slow.json", 3.0e6)
        assert cli_main(["regress", "--lock", str(lp), worse]) == 1
        capsys.readouterr()
        # multichip candidate: only the saving is gated (a fresh saving
        # of 1.3 vs the locked bench 3.5e6 must NOT read as regressed)
        mc = tmp_path / "MULTICHIP_fresh.json"
        mc.write_text(json.dumps(
            {"metric": "sparse saving", "value": 1.3, "unit": "x"}))
        assert cli_main(["regress", "--lock", str(lp), str(mc)]) == 0
        out = capsys.readouterr().out
        assert out.count("skipped") == 1 and "ok" in out
        # a candidate whose kind matches no locked metric gated nothing
        lock["metrics"] = lock["metrics"][:1]  # bench-only lock
        lp.write_text(json.dumps(lock))
        assert cli_main(["regress", "--lock", str(lp), str(mc)]) == 1
        assert "nothing was gated" in capsys.readouterr().out
        # a multichip source NOT named MULTICHIP_* classifies by its
        # CONTENT (saving metric), so a bench candidate skips it
        (tmp_path / "chip_saving.json").write_text(json.dumps(
            {"metric": "sparse-exchange saving", "value": 1.25,
             "unit": "x"}))
        lock["metrics"] = [
            {"name": "saving", "source": "chip_saving.json",
             "field": "value", "value": 1.25, "threshold": 0.05}]
        lp.write_text(json.dumps(lock))
        assert cli_main(["regress", "--lock", str(lp), "--root",
                         str(tmp_path), good]) == 1  # skipped -> nothing gated
        assert "nothing was gated" in capsys.readouterr().out

    def test_committed_lock_holds(self, capsys):
        """The repo's own TELEMETRY_LOCK.json must gate green against
        the committed round files — the check.sh contract."""
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        lock = os.path.join(root, "TELEMETRY_LOCK.json")
        assert cli_main(["regress", "--lock", lock]) == 0
        assert "all locked metrics hold" in capsys.readouterr().out
