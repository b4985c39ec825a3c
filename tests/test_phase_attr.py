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

from sphexa_tpu.util.phases import PHASES, named_phase, phase_scope

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
