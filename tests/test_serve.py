"""Live science surface (schema v8): the in-graph snapshot event
round-trip, the sharded==single grid-equality pin, the snap=None
lowering-neutrality pin, and the jax-free fleet dashboard
(``sphexa-telemetry serve`` / ``fleet``) contracts — discovery, exit
codes, self-contained HTML, and the committed 2-run mini-fixture with
one blackboxed member."""

import hashlib
import json
import os

import numpy as np
import pytest

import jax

from sphexa_tpu.init import init_sedov
from sphexa_tpu.observables import SnapshotSpec, snapshot_diagnostics
from sphexa_tpu.simulation import Simulation, make_propagator_config
from sphexa_tpu.telemetry import JsonlSink, MemorySink, Telemetry
from sphexa_tpu.telemetry.cli import main as cli_main
from sphexa_tpu.telemetry.registry import (
    KIND_SINCE,
    SCHEMA_VERSION,
    validate_event,
)

FIXTURE = os.path.join(os.path.dirname(__file__), "serve_fixture")


# ---------------------------------------------------------------------------
# schema v8: the snapshot event
# ---------------------------------------------------------------------------


class TestSnapshotSchema:
    def test_v8_snapshot_event_round_trip(self, tmp_path):
        """A Simulation with a SnapshotSpec emits strict-clean schema-v8
        ``snapshot`` events whose .npz sidecars carry the grid + meta."""
        sink = MemorySink()
        state, box, const = init_sedov(6)
        sim = Simulation(state, box, const, prop="std", block=512,
                         telemetry=Telemetry(sinks=[sink]),
                         snap_spec=SnapshotSpec(fields=("rho", "temp"),
                                                grid=8, stride=7),
                         snap_dir=str(tmp_path / "snapshots"))
        sim.step()
        sim.step()
        snaps = sink.of_kind("snapshot")
        assert [e["it"] for e in snaps] == [1, 2]
        for e in snaps:
            assert e["v"] == SCHEMA_VERSION
            assert validate_event(e) == []
            assert e["fields"] == ["rho", "temp"] and e["grid"] == 8
            z = np.load(e["path"], allow_pickle=False)
            assert np.asarray(z["grid"]).shape == (2, 8, 8)
            assert list(z["fields"]) == ["rho", "temp"]
            pts = np.asarray(z["pts"])  # xyz + one row per field
            assert pts.shape[0] == 5 and pts.shape[1] > 0
        # frames drain in iteration order, once
        assert [it for it, _ in sim.drain_snapshots()] == [1, 2]
        assert sim.drain_snapshots() == []

    def test_snapshot_is_v8_only_and_old_versions_validate(self):
        """v8 only ADDS the snapshot kind: every pre-v8 kind keeps its
        introduction version, so v1..v7 files stay strictly clean under
        the v8 reader (the fixture runs below re-check this end to
        end)."""
        assert KIND_SINCE["snapshot"] == 8
        assert all(v < 8 for k, v in KIND_SINCE.items()
                   if k not in ("snapshot", "span", "compile"))
        # a v7 writer never emitted snapshots; its events validate as-is
        old = {"v": 7, "seq": 1, "t": 0.0, "kind": "step", "it": 1,
               "wall_s": 0.1, "dt": 1e-3, "reconfigured": False}
        assert validate_event(old) == []
        # a snapshot stamped pre-v8 is the anachronism the gate catches
        bad = {"v": 7, "seq": 2, "t": 0.0, "kind": "snapshot", "it": 1,
               "fields": ["rho"], "grid": 8}
        assert validate_event(bad) != []


# ---------------------------------------------------------------------------
# the deposit itself: sharded equivalence + lowering neutrality
# ---------------------------------------------------------------------------


class TestSnapshotDeposit:
    def test_sharded_equals_single_device_grid(self):
        """The stacked scatter-add deposit must be partition-invariant:
        the same particles on a 2-device mesh produce the same grid (one
        psum over per-shard partial grids) as single-device, up to
        float-sum rounding."""
        from jax.sharding import NamedSharding, PartitionSpec

        from sphexa_tpu.parallel import make_mesh, shard_state

        state, box, const = init_sedov(6)
        spec = SnapshotSpec(fields=("rho", "m"), grid=8)
        rho = jax.numpy.ones_like(state.m)
        single = jax.jit(
            lambda s, r, b: snapshot_diagnostics(s, r, b, spec)
        )(state, rho, box)
        mesh = make_mesh(2)
        sstate = shard_state(state, mesh)
        srho = jax.device_put(rho, NamedSharding(mesh, PartitionSpec("p")))
        sharded = jax.jit(
            lambda s, r, b: snapshot_diagnostics(s, r, b, spec)
        )(sstate, srho, box)
        g0 = np.asarray(single["snap_grid"])
        g1 = np.asarray(sharded["snap_grid"])
        assert g0.shape == g1.shape == (2, 8, 8)
        np.testing.assert_allclose(g1, g0, rtol=1e-6, atol=1e-12)
        # total deposited mass is conserved through the deposit
        np.testing.assert_allclose(g1[1].sum(), np.asarray(state.m).sum(),
                                   rtol=1e-6)

    def test_snap_none_lowering_has_no_snapshot_scope(self):
        """The conditionality pin (the dt_bins pattern): a step built
        with ``snap=None`` must contain NO sphexa/snapshot phase and no
        snap_ output — the committed LOWERING_LOCK digests rely on unset
        snapshots being byte-invisible."""
        import dataclasses

        from sphexa_tpu import propagator as prop
        from sphexa_tpu.devtools.audit.lowerdiff import fingerprint_callable

        state, box, const = init_sedov(6)
        cfg = make_propagator_config(state, box, const, block=512)
        assert cfg.snap is None
        fp = fingerprint_callable(
            lambda s, b: prop.step_hydro_std(s, b, cfg, None), state, box)
        assert not any("snapshot" in ph for ph in fp.phases)
        # and turning the spec ON surfaces the scope (the same program
        # otherwise — this is what rides the production step when set)
        cfg_on = dataclasses.replace(
            cfg, snap=SnapshotSpec(fields=("rho",), grid=8))
        fp_on = fingerprint_callable(
            lambda s, b: prop.step_hydro_std(s, b, cfg_on, None), state, box)
        assert any("snapshot" in ph for ph in fp_on.phases)


# ---------------------------------------------------------------------------
# render_grid golden
# ---------------------------------------------------------------------------


class TestRenderGrid:
    # sha256 of the rendered (32, 32, 3) uint8 pixel array for the
    # arange ramp below — pins the log/clip/colormap/upsample treatment
    # (pixel content, not PNG bytes: zlib output may vary by version)
    GOLDEN = "a1e34d4640f0f2f376c0de578b8366a3d5aba243f5f3e827fcd5b16fd255a08b"

    def test_pixel_golden_and_png_container(self):
        from sphexa_tpu.viz import _png_bytes, render_grid

        img = render_grid(np.arange(64, dtype=np.float64).reshape(8, 8),
                          upsample=4)
        assert img.shape == (32, 32, 3) and img.dtype == np.uint8
        assert hashlib.sha256(img.tobytes()).hexdigest() == self.GOLDEN
        png = _png_bytes(img)
        assert png[:8] == b"\x89PNG\r\n\x1a\n" and b"IEND" in png


# ---------------------------------------------------------------------------
# serve / fleet over the committed mini-fixture
# ---------------------------------------------------------------------------


class TestServeFixture:
    def test_fixture_validates_strict_under_v8(self):
        """The committed runs (one clean, one blackboxed) are strict-
        clean under the current reader — the forward-compat contract."""
        for name in ("run_clean", "run_crashed"):
            path = os.path.join(FIXTURE, name, "events.jsonl")
            events = [json.loads(l) for l in open(path)]
            assert events, name
            for e in events:
                assert validate_event(e) == [], (name, e["kind"])
            assert any(e["kind"] == "snapshot" for e in events)

    def test_serve_once_renders_fleet_html(self, tmp_path, capsys):
        out = str(tmp_path / "dash.html")
        rc = cli_main(["serve", os.path.join(FIXTURE, "run_*"),
                       "--once", "--out", out])
        assert rc == 0
        html = open(out).read()
        # self-contained: both members, an inline PNG frame (no external
        # fetches), the crashed member's red CRASH block
        assert "run_clean" in html and "run_crashed" in html
        assert "data:image/png;base64," in html
        assert "CRASHED" in html and "doctored fixture crash" in html
        assert "http://" not in html.split("<body>")[-1]  # no remote refs
        # --once with no --refresh loop: no meta-refresh tag
        assert 'http-equiv="refresh"' not in html

    def test_fleet_table_and_json(self, capsys):
        rc = cli_main(["fleet", os.path.join(FIXTURE, "run_*")])
        assert rc == 0
        text = capsys.readouterr().out
        assert "run_clean" in text and "run_crashed" in text
        assert "CRASHED" in text
        rc = cli_main(["fleet", os.path.join(FIXTURE, "run_*"),
                       "--format", "json"])
        assert rc == 0
        rows = json.loads(capsys.readouterr().out)
        by_run = {r["name"]: r for r in rows}
        assert by_run["run_crashed"]["status"] == "CRASHED"
        assert by_run["run_crashed"]["error"] is None  # readable, not corrupt
        assert by_run["run_clean"]["status"] in ("ok", "watchdog")
        assert by_run["run_clean"]["snapshots"] >= 1

    def test_exit_codes(self, tmp_path, capsys):
        # 1: nothing matched
        assert cli_main(["serve", str(tmp_path / "nope_*"), "--once"]) == 1
        # 2: every matched run unreadable (corrupt events.jsonl)
        bad = tmp_path / "bad_run"
        bad.mkdir()
        (bad / "events.jsonl").write_text("{not json\n")
        out = str(tmp_path / "dash.html")
        assert cli_main(["serve", str(bad), "--once", "--out", out]) == 2
        # 0 with a partial fleet: the corrupt member renders UNREADABLE
        # next to the committed clean one instead of taking serve down
        both = tmp_path / "mix"
        both.mkdir()
        os.symlink(os.path.join(FIXTURE, "run_clean"), both / "run_clean")
        os.symlink(str(bad), both / "bad_run")
        assert cli_main(["serve", str(both), "--once", "--out", out]) == 0
        html = open(out).read()
        assert "UNREADABLE" in html and "run_clean" in html

    def test_frame_fallback_uses_fixture_relative_paths(self):
        """Event-recorded absolute paths from the generating machine are
        stale in a committed fixture; the frame lookup must fall back to
        ``<run>/snapshots/<basename>`` so the dashboard still renders."""
        from sphexa_tpu.telemetry.serve import build_run_card

        card = build_run_card(os.path.join(FIXTURE, "run_clean"))
        assert card.get("error") is None
        assert card["frame"] is not None
        assert card["frame"]["png"][:8] == b"\x89PNG\r\n\x1a\n"
        assert card["frame"]["path"].startswith(FIXTURE)  # local fallback
        assert card["snapshots"] >= 1
