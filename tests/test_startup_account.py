"""Start-up from inside (schema v21): the ``compile`` event, the spans
over the initialiser and the constructor, the pending list, and the
benchmark's account of them (``benchmarks/startup_spans.py``). Structural,
not timed: which events exist, what they hang under, what a clean window
must NOT hold."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from sphexa_tpu.init import make_initializer
from sphexa_tpu.simulation import Simulation
from sphexa_tpu.telemetry import ConsoleSink, JsonlSink, MemorySink, Telemetry
from sphexa_tpu.telemetry import registry
from sphexa_tpu.telemetry.cli import main as cli_main
from sphexa_tpu.telemetry.cli import render_summary, summarize_run
from sphexa_tpu.telemetry.registry import EVENT_KINDS, KIND_SINCE, validate_event

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

import startup_spans  # noqa: E402  (benchmarks/)

COMPILE_FIELDS = ("fun", "trace_s", "lower_s", "backend_s", "cache",
                  "retrieval_s", "saved_s", "t1_ns", "it", "parent")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/"


@pytest.fixture
def current():
    """A registry with a memory sink, current for the test; whatever an
    earlier test left pending goes to a registry nobody reads first."""
    registry.set_current(Telemetry())
    sink = MemorySink()
    tel = Telemetry(sinks=[sink])
    registry.set_current(tel)
    yield tel, sink
    registry.set_current(None)


@pytest.fixture(scope="module")
def started():
    """The benchmark's order of things at a tiny size: the initialiser with
    no registry current, then the constructor, two flushed warm-up steps,
    and one clean window."""
    registry.set_current(Telemetry())
    registry.set_current(None)
    state, box, const = make_initializer("sedov")(8)
    sink = MemorySink()
    sim = Simulation(state, box, const, prop="std", block=4096,
                     check_every=4, telemetry=Telemetry(sinks=[sink]))
    for _ in range(2):
        sim.step()
        sim.flush()
    mark = len(sink.events)
    counters = dict(sim.telemetry.counters)
    for _ in range(4):
        sim.step()
    sim.flush()
    jax.block_until_ready(sim.state)
    window = {k: v - counters.get(k, 0)
              for k, v in sim.telemetry.counters.items()}
    yield {"sim": sim, "sink": sink, "setup": sink.events[:mark],
           "window_events": sink.events[mark:], "window_counters": window}
    sim.telemetry.close()


def _fire(name="step", hit=False, nested=("inner",)):
    """One program's monitoring events in jax's order: nested traces, the
    outermost trace, the lowering, the cache's events, the backend."""
    for inner in nested:
        registry._on_duration(TRACE, 0.125, fun_name=inner)
    registry._on_duration(TRACE, 0.5, fun_name=name)
    registry._on_duration(LOWER, 0.25, fun_name=f"jit({name})")
    registry._on_event(CACHE + "compile_requests_use_cache")
    if hit:
        registry._on_event(CACHE + "cache_hits")
        registry._on_duration(CACHE + "compile_time_saved_sec", 40.0)
        registry._on_duration(CACHE + "cache_retrieval_time_sec", 1.5)
    registry._on_duration(BACKEND, 2.0, fun_name=f"jit({name})")


class TestCompileEvent:
    def test_schema_v21_has_the_kind_and_its_fields(self):
        assert registry.SCHEMA_VERSION == 21 == registry.SUPPORTED_VERSIONS[-1]
        assert EVENT_KINDS["compile"] == COMPILE_FIELDS
        assert KIND_SINCE["compile"] == 21
        e = {"v": 21, "seq": 0, "t": 1.0, "kind": "compile",
             "fun": "jit(step)", "trace_s": 0.5, "lower_s": 0.25,
             "backend_s": 2.0, "cache": "hit", "retrieval_s": 1.5,
             "saved_s": 40.0, "t1_ns": 1, "it": 0, "parent": None}
        assert validate_event(e) == []
        # the kind came with v21: an older writer cannot have emitted it
        assert validate_event({**e, "v": 20})
        for field in COMPILE_FIELDS:
            assert any(field in p for p in validate_event(
                {k: v for k, v in e.items() if k != field}))

    def test_fold_takes_the_outermost_trace(self, current):
        tel, sink = current
        with tel.span("sphexa:launch") as sp:
            _fire(hit=True)
        (e,) = sink.of_kind("compile")
        assert validate_event(e) == []
        assert (e["fun"], e["trace_s"], e["lower_s"], e["backend_s"]) == (
            "jit(step)", 0.5, 0.25, 2.0)
        assert (e["cache"], e["retrieval_s"], e["saved_s"]) == (
            "hit", 1.5, 40.0)
        assert e["parent"] == sp.id and e["t1_ns"] > 0
        # the pieces are gone with the event: the next program starts clean
        registry._on_duration(BACKEND, 0.75, fun_name="jit(other)")
        other = sink.of_kind("compile")[-1]
        assert (other["trace_s"], other["lower_s"], other["retrieval_s"],
                other["parent"]) == (0.0, 0.0, 0.0, None)
        assert other["cache"] != "hit"

    def test_nested_jits_give_one_event_a_program(self, current):
        tel, sink = current

        @jax.jit
        def start_inner(x):
            return x * 2.0

        @jax.jit
        def start_outer(x):
            return start_inner(x) + start_inner(x + 1.0)

        x = jnp.ones(7)
        jax.block_until_ready(x)
        mark = len(sink.events)
        start_outer(x)
        funs = [e["fun"] for e in sink.events[mark:]
                if e["kind"] == "compile"]
        assert funs == ["jit(start_outer)"]
        e = sink.events[-1]
        assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
        assert e["cache"] in ("hit", "miss", "off")
        # the jit's own cache serves the next call: no listener is reached
        calls = tel.counters["compile_callbacks"]
        assert calls >= 3
        start_outer(x)
        assert tel.counters["compile_callbacks"] == calls
        assert len(sink.events) == mark + 1

    def test_cache_reads_off_without_a_directory(self, current,
                                                 monkeypatch):
        tel, sink = current
        monkeypatch.setattr(registry, "_cache_dir", lambda: None)
        _fire()
        monkeypatch.setattr(registry, "_cache_dir", lambda: "/some/where")
        _fire()
        assert [e["cache"] for e in sink.of_kind("compile")] == [
            "off", "miss"]

    def test_console_prints_slow_misses_only(self):
        lines = []
        tel = Telemetry(sinks=[ConsoleSink(printer=lines.append)])
        base = dict(fun="jit(step)", trace_s=0.5, lower_s=0.25,
                    retrieval_s=0.0, saved_s=0.0, t1_ns=1, it=0, parent=None)
        tel.event("compile", backend_s=0.2, cache="miss", **base)
        tel.event("compile", backend_s=30.0, cache="hit", **base)
        tel.event("compile", backend_s=30.0, cache="off", **base)
        assert lines == []
        tel.event("compile", backend_s=30.0, cache="miss", **base)
        (line,) = lines
        assert "compile" in line and "jit(step)" in line

    def test_summary_gains_one_line(self, tmp_path, capsys):
        tel = Telemetry(sinks=[JsonlSink(str(tmp_path / "events.jsonl"))])
        registry.set_current(Telemetry())
        registry.set_current(tel)
        _fire(hit=True)
        _fire(name="rebuild")
        tel.close()
        s = summarize_run(str(tmp_path))
        assert s["schema_problems"] == [] and not s["unknown_kinds"]
        assert s["compiles"] == {
            "programs": 2, "hits": 1,
            "misses": s["compiles"]["misses"], "trace_lower_s": 1.5,
            "retrieval_s": 1.5, "backend_compile_s": 2.0}
        text = render_summary(s)
        assert "2 programs, 1 cache hits" in text
        assert cli_main(["summary", str(tmp_path), "--strict"]) == 0
        assert "compiles" in capsys.readouterr().out
        # a run from before v21 has no such line
        assert "compiles" not in render_summary({**s, "compiles": {
            **s["compiles"], "programs": 0}})

    def test_cli_imports_without_jax(self):
        code = ("import sys; sys.modules['jax'] = None; "
                "import sphexa_tpu.telemetry.cli as cli; "
                "from sphexa_tpu.telemetry import registry; "
                "registry.set_current(registry.Telemetry()); "
                "registry.span('sphexa:x').__enter__(); "
                "assert registry._LISTENING is False; "
                "assert not any(m == 'jax' or m.startswith('jax.') "
                "for m, v in sys.modules.items() if v is not None)")
        done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                              capture_output=True, text=True)
        assert done.returncode == 0, done.stderr


class TestPending:
    def test_a_span_closed_with_none_current_reaches_the_next(self):
        registry.set_current(Telemetry())
        registry.set_current(None)
        assert registry.current() is None
        with registry.span("sphexa:init-case", case="x") as sp:
            sp["rows"] = 3
            _fire(name="iota", nested=())
        sink = MemorySink()
        tel = Telemetry(sinks=[sink])
        registry.set_current(tel)
        assert registry.current() is tel
        compile_, span_ = sink.events
        assert (compile_["kind"], compile_["fun"]) == ("compile", "jit(iota)")
        assert compile_["parent"] == span_["id"] == sp.id
        assert (span_["name"], span_["case"], span_["rows"]) == (
            "sphexa:init-case", "x", 3)
        assert span_["t0_ns"] == sp._t0 and span_["dur_ns"] > 0
        assert span_["t0_ns"] < compile_["t1_ns"] <= (
            span_["t0_ns"] + span_["dur_ns"])
        assert all(validate_event(e) == [] for e in sink.events)
        # handed over once
        registry.set_current(Telemetry(sinks=[sink]))
        assert len(sink.events) == 2
        registry.set_current(None)

    def test_the_list_keeps_the_newest_256(self):
        registry.set_current(Telemetry())
        registry.set_current(None)
        assert registry.PENDING_MAX == 256
        for i in range(300):
            with registry.span("sphexa:x", i=i):
                pass
        assert len(registry._PENDING) == 256
        sink = MemorySink()
        registry.set_current(Telemetry(sinks=[sink]))
        assert [e["i"] for e in sink.events] == list(range(44, 300))
        assert not registry._PENDING
        registry.set_current(None)

    def test_a_handle_less_span_reports_where_it_closes(self, current):
        tel, sink = current
        tel.iteration = 12
        with registry.span("sphexa:dump-fetch"):
            pass
        (e,) = sink.of_kind("span")
        assert (e["name"], e["it"], e["parent"]) == (
            "sphexa:dump-fetch", 12, None)

    def test_the_initialiser_is_wrapped_where_it_is_made(self, current):
        tel, sink = current
        init = make_initializer("sedov+list-lifecycle")
        assert init.__name__ == "init_sedov"
        state, box, const = init(4)
        (e,) = [e for e in sink.of_kind("span")
                if e["name"] == "sphexa:init-case"]
        assert e["case"] == "sedov" and state.n == 64


class TestStartedSimulation:
    def test_construct_holds_reconfigure_holds_sizing(self, started):
        spans = {e["id"]: e for e in started["setup"] if e["kind"] == "span"}
        by_name = {}
        for e in spans.values():
            by_name.setdefault(e["name"], []).append(e)
        (ic,), (construct,) = (by_name["sphexa:init-case"],
                               by_name["sphexa:construct"])
        (reconfigure,) = by_name["sphexa:reconfigure"]
        (sizing,) = by_name["sphexa:size-neighbors"]
        assert ic["parent"] is None and construct["parent"] is None
        assert reconfigure["parent"] == construct["id"]
        assert sizing["parent"] == reconfigure["id"]
        assert reconfigure["reason"] == "initial"
        # the initialiser ran before the constructor and reached its sink
        assert ic["t0_ns"] + ic["dur_ns"] <= construct["t0_ns"]
        for inner, outer in ((sizing, reconfigure),
                             (reconfigure, construct)):
            assert outer["t0_ns"] <= inner["t0_ns"]
            assert (inner["t0_ns"] + inner["dur_ns"]
                    <= outer["t0_ns"] + outer["dur_ns"])

    def test_every_compile_hangs_under_an_emitted_span_or_none(self, started):
        events = started["setup"]
        ids = {e["id"] for e in events if e["kind"] == "span"}
        compiles = [e for e in events if e["kind"] == "compile"]
        assert compiles and all(validate_event(e) == [] for e in compiles)
        assert all(e["parent"] is None or e["parent"] in ids
                   for e in compiles)
        names = {e["id"]: e["name"] for e in events if e["kind"] == "span"}
        step = next(e for e in compiles if "_step_" in e["fun"])
        assert names[step["parent"]] == "sphexa:launch"
        # one retrace says THAT the first launch traced; this says the cost
        assert step["trace_s"] > 0 and step["backend_s"] > 0

    def test_the_account_stays_inside_the_wall(self, started):
        events = started["setup"]
        leaves = startup_spans.account(events)
        assert set(leaves) == set(startup_spans.TIMES) | {
            "cache_misses", "programs"}
        assert all(v >= 0 for v in leaves.values())
        assert leaves["programs"] == len(
            [e for e in events if e["kind"] == "compile"])
        spans = [e for e in events if e["kind"] == "span"]
        t0 = min(e["t0_ns"] for e in spans)
        t1 = max(e["t0_ns"] + e["dur_ns"] for e in spans)
        named = sum(leaves[k] for k in startup_spans.TIMES)
        assert 0 < named <= (t1 - t0) * 1e-9
        # a span's self time and what is inside it make its duration
        own = startup_spans.self_seconds(events)
        for s in spans:
            inside = sum(c["dur_ns"] * 1e-9 for c in spans
                         if c["parent"] == s["id"])
            inside += sum(startup_spans.cost(c) for c in events
                          if c["kind"] == "compile"
                          and c["parent"] == s["id"])
            assert own[s["id"]] + inside == pytest.approx(
                s["dur_ns"] * 1e-9)

    def test_a_clean_window_reaches_no_listener(self, started):
        """The hot-path guard, beside the no-sync guard of
        tests/test_telemetry.py: a window of a warmed step compiles
        nothing, so jax calls neither listener."""
        kinds = [e["kind"] for e in started["window_events"]]
        assert kinds.count("launch") == 4 and kinds.count("window") == 1
        assert "compile" not in kinds and "retrace" not in kinds
        counters = started["window_counters"]
        assert counters.get("compile_callbacks", 0) == 0
        assert counters.get("events.compile", 0) == 0
        assert started["sim"].telemetry.counters["compile_callbacks"] > 0

    def test_the_readers_reach_the_sink_through_the_registry(self, started):
        run = {"events": started["window_events"],
               "setup_spans": [{"name": "init-construct", "t0": 0.0,
                                "t1": 60.0},
                               {"name": "warm", "t0": 60.0, "t1": 100.0}]}
        assert registry.current() is started["sim"].telemetry
        assert startup_spans.setup_events(run) == started["setup"]
        import run as harness

        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        ours = [m for m in bench["per_layer"]
                if m["name"].startswith("setup_")]
        assert len(ours) == 10 and bench["per_layer"][-10:] == ours
        for m in ours:
            assert "workloads" not in m and m["moves"] == "setup_s"
            assert m["layer"] == "host runtime and initialisers"
            value = harness.load_reader("layers", m["name"])(run)
            assert value is not None and value >= 0, m["name"]
        share = harness.load_reader("layers", "setup_accounted_share")(run)
        leaves = startup_spans.account(started["setup"])
        assert share == pytest.approx(
            sum(leaves[k] for k in startup_spans.TIMES))
        # a program from before this PR: nothing current, nothing read
        registry.set_current(None)
        try:
            for m in ours:
                assert harness.load_reader("layers", m["name"])(run) is None
        finally:
            registry.set_current(started["sim"].telemetry)
