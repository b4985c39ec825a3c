"""Chip-harvest observability: the in-graph phase-attribution contract.

Three pins, one per leg of the time-and-history stack (schema v4):

- named-scope presence: every propagator's lowered step IR must carry
  the expected ``sphexa/<phase>`` scope paths in its op locations, so a
  refactor cannot silently strip the attribution a chip capture relies
  on (the HLO pin the traceview renderer points at);
- traceview parsing: the committed miniature capture fixture
  (tests/trace_fixture: one xplane.pb + one perfetto dump from a tiny
  3-scope program) must attribute through the generic protobuf walk —
  scope maps, computation inheritance, base-name fallback, coverage
  gate exit codes;
- crash flight recorder: blackbox.json + the first-class ``crash``
  event on abnormal exit, including a genuinely killed child process.
"""

import io
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from sphexa_tpu.util.phases import (PHASES, STAGE_SEP, STAGES, named_phase,
                                    named_stage, phase_scope, stage_scope)

FIXTURE = os.path.join(os.path.dirname(__file__), "trace_fixture")


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------


class TestTaxonomy:
    def test_phases_unique_and_wellformed(self):
        assert len(PHASES) == len(set(PHASES))
        from sphexa_tpu.telemetry.traceview import PHASE_RE

        for p in PHASES:
            m = PHASE_RE.search(f"jit(step)/jit(main)/sphexa/{p}/op")
            assert m and m.group(1) == p  # the renderer can key on it

    def test_unknown_phase_rejected(self):
        with pytest.raises(AssertionError):
            phase_scope("not-a-phase")
        with pytest.raises(AssertionError):
            named_phase("bogus")


# ---------------------------------------------------------------------------
# named-scope presence in lowered step IR (one per propagator)
# ---------------------------------------------------------------------------

#: phases every SPH step must stamp
_COMMON = ("sort", "neighbors", "eos", "iad", "momentum-energy",
           "timestep", "integrate", "ledger")
_EXPECT = {
    "std": _COMMON + ("density",),
    "ve": _COMMON + ("xmass", "gradh", "divv-curlv", "av-switches"),
    "turb-ve": _COMMON + ("xmass", "gradh", "divv-curlv", "av-switches",
                          "turbulence"),
    "std-cooling": _COMMON + ("density", "cooling"),
    "nbody": ("sort", "gravity-upsweep", "gravity-mac", "gravity-m2p",
              "gravity-p2p", "timestep", "integrate", "ledger"),
}


def _lowered_ir(prop, backend="auto"):
    """Debug-info StableHLO text of one lowered (NOT compiled) step of
    ``prop`` at audit scale (side 6), built through the real Simulation
    machinery so the lowered program IS the production one."""
    import dataclasses as dc

    from sphexa_tpu.init import init_sedov
    from sphexa_tpu.observables import ObservableSpec
    from sphexa_tpu.simulation import _PROPAGATORS, Simulation

    state, box, const = init_sedov(6)
    if prop == "nbody":
        const = dc.replace(const, g=1.0)
    sim = Simulation(state, box, const, prop=prop, block=512,
                     obs_spec=ObservableSpec(), backend=backend)
    fn = _PROPAGATORS[prop]
    if prop == "turb-ve":
        aux = (sim.turb_state, sim.turb_cfg)
    elif prop == "std-cooling":
        aux = (sim.chem, sim.cooling_cfg)
    else:
        aux = ()
    lowered = fn.lower(sim.state, sim.box, sim._cfg, sim._gtree, *aux)
    buf = io.StringIO()
    lowered.compiler_ir(dialect="stablehlo").operation.print(
        file=buf, enable_debug_info=True)
    return buf.getvalue()


class TestNamedScopePins:
    @pytest.mark.parametrize("prop", sorted(_EXPECT))
    def test_step_ir_carries_phase_scopes(self, prop):
        """A refactor that drops a stage's named scope strips the chip
        capture's attribution without failing any numeric test — THIS
        is the test that fails instead."""
        ir = _lowered_ir(prop)
        missing = [p for p in _EXPECT[prop] if f"sphexa/{p}" not in ir]
        assert not missing, (
            f"{prop} step lost named scopes for {missing} "
            f"(util/phases.py taxonomy; wrap the stage again)")
        # and nothing outside the taxonomy leaked in
        import re

        seen = set(re.findall(r"sphexa/([A-Za-z0-9_.:+-]+?)[/\"]", ir))
        assert seen <= set(PHASES), f"unknown phases stamped: " \
                                    f"{seen - set(PHASES)}"

    @pytest.mark.parametrize("prop", ["ve", "turb-ve"])
    def test_engine_ve_step_opens_five_pair_scopes(self, prop):
        """On the pair engine a VE step is FIVE neighbour passes: the IAD
        moments ride the divv/curlv pass (pallas_iad_divv_curlv), so no
        ``sphexa/iad`` scope is opened; std keeps its own IAD pass."""
        ir = _lowered_ir(prop, backend="pallas")
        pair = ("xmass", "gradh", "divv-curlv", "av-switches",
                "momentum-energy")
        assert not [p for p in pair if f"sphexa/{p}" not in ir]
        assert "sphexa/iad" not in ir
        assert "sphexa/iad" in _lowered_ir("std", backend="pallas")



# ---------------------------------------------------------------------------
# stages: a second name inside a phase (sphexa/<phase>~<stage>)
# ---------------------------------------------------------------------------

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
_COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "all_to_all",
                "ppermute")
#: the per-step sort of a streamed step; ``aux`` only where an aux pytree
#: rides it (the chemistry of a std-cooling step)
_SORT = ("keys", "order", "permute")
_GRAVITY_LOOP = {
    "sort": _SORT,
    "neighbors": ("windows", "cell-ranges"),
    "gravity-mac": ("geometry", "prepass", "classify", "compact"),
    "gravity-p2p": ("leaf-ranges", "merge-runs", "kernel"),
}
_EXCHANGE = ("table", "cover", "localize", "pack", "wire", "jbuf")
#: the stages each lowered program must open
_STAGED = {
    # (a) the one-chip gravity step, on both sides of the 500k switch
    "chip-bitmask": _GRAVITY_LOOP,
    "chip-sort": _GRAVITY_LOOP,
    # (b) + (c): the mesh step holds the sparse SPH halo stage and the
    # sharded gravity stage (bitmask compaction over the LET list)
    "mesh-sparse": dict(_GRAVITY_LOOP, **{
        "gravity-mac": ("geometry", "let", "prepass", "classify", "compact"),
        "gravity-exchange": ("psum", "jbuf"), "halo-exchange": _EXCHANGE}),
    # the windowed serves (the retry ceiling) under the sort compaction,
    # no superblocks: blocks classify against the LET list
    "mesh-windowed": dict(_GRAVITY_LOOP, **{
        "gravity-mac": ("geometry", "let", "classify", "compact"),
        "gravity-exchange": ("psum", "jbuf"), "halo-exchange": _EXCHANGE}),
    # the std-cooling step: the limiter's pass and the subcycled network
    "chip-cooling": {"sort": _SORT + ("aux",),
                     "neighbors": ("windows", "cell-ranges"),
                     "cooling": ("limiter", "network")},
}


def _phase_re():
    """The pattern the benchmark's readers take an op's phase with."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import trace_reduce

    return trace_reduce.PHASE_RE


def _tokens(path):
    """Every ``<phase>`` / ``<phase>~<stage>`` of a path, as the stage
    reader (benchmarks/stage_times.py) finds them."""
    _phase_re()
    import stage_times

    return [a + b for a, b in stage_times.TOKEN_RE.findall(path)]


def _ir_text(lowered):
    buf = io.StringIO()
    lowered.compiler_ir(dialect="stablehlo").operation.print(
        file=buf, enable_debug_info=True)
    return buf.getvalue()


@pytest.fixture(scope="module")
def staged_paths():
    """{program: scope paths of every op} of four lowered (NOT compiled)
    Evrard VE steps and one Sedov std-cooling step at audit scale, built
    through the real Simulation."""
    import dataclasses as dc
    import re

    import jax

    from sphexa_tpu.init import init_evrard, init_sedov
    from sphexa_tpu.observables import ObservableSpec
    from sphexa_tpu.parallel.mesh import make_sharded_step
    from sphexa_tpu.propagator import step_hydro_ve
    from sphexa_tpu.simulation import _PROPAGATORS, Simulation

    state, box, const = init_evrard(10)
    n4 = (state.n // 4) * 4
    state = jax.tree.map(
        lambda a: a[:n4] if getattr(a, "ndim", 0) == 1 else a, state)
    kw = dict(prop="ve", block=512, backend="pallas",
              obs_spec=ObservableSpec())

    def with_gravity(sim, **changes):
        nodes = sim._cfg.grav_meta.num_nodes
        return dc.replace(sim._cfg, gravity=dc.replace(
            sim._cfg.gravity, super_cap=nodes, **changes))

    one = Simulation(state, box, const, **kw)
    lowered = {
        name: _PROPAGATORS["ve"].lower(
            one.state, one.box,
            with_gravity(one, compaction=mode, super_factor=2), one._gtree)
        for name, mode in (("chip-bitmask", "bitmask"), ("chip-sort", "sort"))}

    mesh = Simulation(state, box, const, num_devices=4, **kw)
    ss = mesh.sim_state
    nodes = mesh._cfg.grav_meta.num_nodes
    sparse = make_sharded_step(
        mesh._mesh,
        with_gravity(mesh, compaction="bitmask", super_factor=2,
                     let_cap=nodes),
        step_hydro_ve, halo_cells=mesh._halo_info["caps"],
        grav_cells=mesh._grav_cells)
    windowed = make_sharded_step(
        mesh._mesh, with_gravity(mesh, compaction="sort", let_cap=nodes),
        step_hydro_ve, halo_window=n4 // 4)
    for name, step in (("mesh-sparse", sparse), ("mesh-windowed", windowed)):
        lowered[name] = step._jitted.lower(ss.particles, ss.box,
                                           mesh._gtree, None)
    cool = Simulation(*init_sedov(6), **dict(kw, prop="std-cooling"))
    lowered["chip-cooling"] = _PROPAGATORS["std-cooling"].lower(
        cool.state, cool.box, cool._cfg, cool._gtree, cool.chem,
        cool.cooling_cfg)
    return {name: sorted(p for p in set(re.findall(
        r'loc\("([^"]*)"', _ir_text(low))) if "sphexa/" in p)
        for name, low in lowered.items()}


class TestStages:
    def test_stages_wellformed(self):
        """Every reader the repo has reads a staged scope as its phase,
        and the stage comes back from the path alone."""
        import re

        from sphexa_tpu.telemetry.traceview import PHASE_RE as traceview_re

        assert set(STAGES) <= set(PHASES)
        for phase, stages in STAGES.items():
            assert len(set(stages)) == len(stages)
            for stage in stages:
                assert re.fullmatch(r"[A-Za-z0-9_.:+-]+", stage)
                path = (f"jit(step)/sphexa/{phase}/while/body/"
                        f"sphexa/{phase}{STAGE_SEP}{stage}/gather")
                for pattern in (_phase_re(), traceview_re):
                    assert pattern.search(path).group(1) == phase
                    assert pattern.search(
                        path.split("/", 3)[3]).group(1) == phase
                assert _tokens(path)[-1] == f"{phase}{STAGE_SEP}{stage}"

    def test_unknown_stage_rejected(self):
        with pytest.raises(AssertionError):
            stage_scope("gravity-mac", "not-a-stage")
        with pytest.raises(AssertionError):
            named_stage("density", "wire")  # a phase without stages
        with pytest.raises(AssertionError):
            stage_scope("not-a-phase", "wire")

    @pytest.mark.parametrize("program", sorted(_STAGED))
    def test_program_opens_its_stages(self, staged_paths, program):
        seen = {t for p in staged_paths[program]
                for t in _tokens(p) if STAGE_SEP in t}
        want = {f"{ph}{STAGE_SEP}{st}"
                for ph, sts in _STAGED[program].items() for st in sts}
        assert seen == want, (sorted(want - seen), sorted(seen - want))

    def test_every_stage_is_opened_somewhere(self):
        opened = {(ph, st) for prog in _STAGED.values()
                  for ph, sts in prog.items() for st in sts}
        assert opened == {(ph, st) for ph, sts in STAGES.items()
                          for st in sts}

    @pytest.mark.parametrize("program", sorted(_STAGED))
    def test_first_phase_same_with_stages_stripped(self, staged_paths,
                                                   program):
        """The guard that no metric the benchmark had can move: the first
        ``sphexa/<phase>`` of every op's path is what it is with the stage
        scopes taken out of the path."""
        import re

        phase_re = _phase_re()
        first = lambda p: getattr(phase_re.search(p), "group",
                                  lambda _: None)(1)
        staged = 0
        for path in staged_paths[program]:
            stripped = re.sub(
                r"sphexa/[A-Za-z0-9_.:+-]+~[A-Za-z0-9_.:+-]+/?", "", path)
            staged += stripped != path
            assert first(path) == first(stripped), path
            assert first(path) in PHASES
        assert staged > 20

    @pytest.mark.parametrize("program", ["mesh-sparse", "mesh-windowed"])
    def test_exchange_collectives_carry_wire_or_psum(self, staged_paths,
                                                     program):
        """Every collective of the two exchanges reads under ``~wire`` or
        ``~psum``, and those two stages hold nothing else (``add`` is a
        psum's own reduction)."""
        phase_re = _phase_re()
        collectives = 0
        for path in staged_paths[program]:
            prim = path.rsplit("/", 1)[-1]
            stage = _tokens(path)[-1].partition(STAGE_SEP)[2]
            if phase_re.search(path).group(1) in ("halo-exchange",
                                                  "gravity-exchange") \
                    and prim in _COLLECTIVES:
                collectives += 1
                assert stage in ("wire", "psum"), path
            if stage in ("wire", "psum"):
                assert prim in _COLLECTIVES + ("add",), path
        assert collectives >= 5


# ---------------------------------------------------------------------------
# traceview over the committed fixture
# ---------------------------------------------------------------------------


class TestTraceview:
    def test_fixture_attributes_phases(self):
        from sphexa_tpu.telemetry.traceview import summarize_trace

        s = summarize_trace(FIXTURE)
        assert s["device_op_events"] > 0
        assert s["total_device_us"] > 0
        phases = {p["phase"] for p in s["phases"]}
        assert {"density", "momentum-energy", "neighbors"} <= phases
        # the fixture's cumsum lowers to a metadata-less reduce-window:
        # computation inheritance must still attribute the neighbors bulk
        nb = next(p for p in s["phases"] if p["phase"] == "neighbors")
        assert nb["us"] > 0
        assert s["coverage"] > 0.5
        assert abs(sum(p["share"] for p in s["phases"])
                   - s["coverage"]) < 1e-9

    def test_cli_exit_codes(self, tmp_path, capsys):
        from sphexa_tpu.telemetry.cli import main as cli_main

        assert cli_main(["trace", FIXTURE]) == 0
        out = capsys.readouterr().out
        assert "density" in out and "attributed:" in out
        # the chip-harvest gate: coverage below the floor fails
        assert cli_main(["trace", FIXTURE, "--min-coverage", "0.999"]) == 1
        capsys.readouterr()
        assert cli_main(["trace", FIXTURE, "--format", "json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert s["coverage"] > 0.5
        # no capture at all is a usage error, not a silent pass
        assert cli_main(["trace", str(tmp_path / "nope")]) == 2

    def test_json_fallback_without_xplane(self, tmp_path, capsys):
        """A dir holding only the perfetto dump parses through the json
        fallback: device ops are found, but without the xplane's HLO
        metadata nothing attributes — and the CLI must FAIL (exit 1)
        instead of blessing an unattributable capture."""
        import shutil

        from sphexa_tpu.telemetry.cli import main as cli_main
        from sphexa_tpu.telemetry.traceview import summarize_trace

        d = tmp_path / "jsononly"
        d.mkdir()
        shutil.copy(os.path.join(FIXTURE, "vm.trace.json.gz"), d)
        s = summarize_trace(str(d))
        assert s["device_op_events"] > 0
        assert s["phases"] == []
        assert cli_main(["trace", str(d)]) == 1
        assert "no sphexa/ phases" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# crash flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_dump_writes_blackbox_and_crash_event(self, tmp_path):
        from sphexa_tpu.telemetry import (
            FlightRecorder,
            JsonlSink,
            Telemetry,
            read_blackbox,
        )
        from sphexa_tpu.telemetry.registry import validate_event

        run = str(tmp_path)
        rec = FlightRecorder(run, capacity=3, telemetry=None)
        tel = Telemetry(sinks=[JsonlSink(os.path.join(run, "events.jsonl")),
                               rec.sink])
        rec.telemetry = tel
        for i in range(5):
            tel.event("launch", it=i)
        path = rec.dump(reason="unit-test crash", tb="Traceback: boom")
        assert path and os.path.exists(path)
        box = read_blackbox(run)
        assert box["reason"] == "unit-test crash"
        assert len(box["events"]) == 3  # ring capacity, newest kept
        assert box["events"][-1]["it"] == 4
        assert box["watchdogs"]["events_total"] == 5
        # first cause wins: a later cascade must not overwrite it
        assert rec.dump(reason="second") is None
        assert read_blackbox(run)["reason"] == "unit-test crash"
        # the crash landed as a first-class v4 event in the stream
        events = [json.loads(l)
                  for l in open(os.path.join(run, "events.jsonl"))]
        crash = [e for e in events if e["kind"] == "crash"]
        assert len(crash) == 1
        assert crash[0]["reason"] == "unit-test crash"
        assert validate_event(crash[0]) == []
        # the crash event continues the run's REAL seq (monotone-per-run
        # envelope contract), not the ring-buffer length
        assert crash[0]["seq"] == events[-2]["seq"] + 1 == 5

    def test_summary_and_science_explain_the_crash(self, tmp_path, capsys):
        from sphexa_tpu.telemetry import (
            FlightRecorder,
            JsonlSink,
            Telemetry,
        )
        from sphexa_tpu.telemetry.cli import main as cli_main
        from sphexa_tpu.telemetry.manifest import write_manifest

        run = str(tmp_path)
        rec = FlightRecorder(run, telemetry=None)
        tel = Telemetry(sinks=[JsonlSink(os.path.join(run, "events.jsonl")),
                               rec.sink])
        rec.telemetry = tel
        tel.event("step", it=1, wall_s=0.1)
        tel.count("rollbacks", 2)
        rec.dump(reason="signal SIGTERM (15)", tb="fake stack")
        write_manifest(run, particles=64)
        assert cli_main(["summary", run]) == 0
        out = capsys.readouterr().out
        assert "CRASH: signal SIGTERM (15)" in out
        assert "rollbacks=2" in out
        assert cli_main(["science", run]) == 1  # still no physics events
        assert "CRASH:" in capsys.readouterr().out
        # --strict: the appended crash event is schema-valid v4
        assert cli_main(["summary", run, "--strict"]) == 0

    def test_close_disarms_cleanly(self, tmp_path):
        from sphexa_tpu.telemetry import FlightRecorder

        rec = FlightRecorder(str(tmp_path))
        rec.install()
        assert rec._installed
        rec.close()
        assert not rec._installed
        rec._on_atexit()  # even a stray atexit call stays silent now
        assert not os.path.exists(tmp_path / "blackbox.json")
        # nothing faulted: the empty fault.log is tidied away too
        assert not os.path.exists(tmp_path / "fault.log")

    def test_ignored_signal_stays_ignored(self, tmp_path):
        """A deliberately-ignored signal (nohup's SIGHUP) must not be
        hooked: it would fabricate a crash record in a run that then
        survives; and install/close must round-trip the original
        disposition for hooked signals."""
        import signal as _signal

        from sphexa_tpu.telemetry import FlightRecorder

        prev_hup = _signal.signal(_signal.SIGHUP, _signal.SIG_IGN)
        try:
            rec = FlightRecorder(str(tmp_path))
            rec.install()
            assert _signal.getsignal(_signal.SIGHUP) is _signal.SIG_IGN
            assert _signal.SIGHUP not in rec._prev_signals
            assert _signal.getsignal(_signal.SIGTERM) == rec._on_signal
            rec.close()
            assert not os.path.exists(tmp_path / "blackbox.json")
        finally:
            _signal.signal(_signal.SIGHUP, prev_hup)

    def test_killed_child_leaves_blackbox(self, tmp_path):
        """The real contract: a child process running a flight-recorded
        event loop is SIGTERMed mid-run and must leave blackbox.json +
        the crash event, with the buffered tail intact. jax-free child
        (the telemetry package contract), so the spawn is cheap."""
        run = str(tmp_path / "run")
        script = textwrap.dedent(f"""
            import os, sys, time
            from sphexa_tpu.telemetry import (FlightRecorder, JsonlSink,
                                              Telemetry)
            run = {run!r}
            rec = FlightRecorder(run, capacity=50, telemetry=None)
            tel = Telemetry(sinks=[
                JsonlSink(os.path.join(run, "events.jsonl")), rec.sink])
            rec.telemetry = tel
            rec.install()
            tel.event("launch", it=0)
            print("READY", flush=True)
            for i in range(1, 10**9):
                tel.event("launch", it=i)
                time.sleep(0.01)
        """)
        env = dict(os.environ, PYTHONPATH=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        proc = subprocess.Popen([sys.executable, "-c", script],
                                stdout=subprocess.PIPE, env=env, text=True)
        try:
            line = proc.stdout.readline()
            assert "READY" in line
            time.sleep(0.3)  # let a few events buffer
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert rc != 0  # died by signal, conventional nonzero status
        from sphexa_tpu.telemetry import read_blackbox

        box = read_blackbox(run)
        assert box is not None
        assert "SIGTERM" in box["reason"]
        assert box["events"] and box["events"][-1]["kind"] == "launch"
        events = [json.loads(l)
                  for l in open(os.path.join(run, "events.jsonl"))]
        assert events[-1]["kind"] == "crash"
        assert "SIGTERM" in events[-1]["reason"]
