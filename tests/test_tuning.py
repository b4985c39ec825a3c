"""Autotuner tier (sphexa_tpu/tuning/): knob registry drift, table
round-trip + resolution precedence, the deterministic search driver
over a fake measurement, replay-from-manifest, schema-v5 events, and
the CLI exit-code contracts (docs/TUNING.md)."""

import json
import os

import pytest

from sphexa_tpu.tuning import knobs as knobs_mod
from sphexa_tpu.tuning.knobs import (
    BLOCKDT_KNOBS,
    GRAVITY_KNOBS,
    KNOBS,
    NEIGHBOR_KNOBS,
    SIMULATION_KNOBS,
    KnobSpec,
    knob_names,
    validate_registry,
)
from sphexa_tpu.tuning.replay import (
    ReplaySpec,
    measure_candidate,
    spec_from_manifest,
)
from sphexa_tpu.tuning.search import domains_for, run_sweep
from sphexa_tpu.tuning.table import (
    TABLE_SCHEMA,
    coverage,
    load_table,
    make_entry,
    n_bucket,
    new_table,
    resolve_entry,
    resolve_knobs,
    save_table,
    upsert_entry,
    validate_table,
)
from sphexa_tpu.telemetry import MemorySink, Telemetry, write_manifest
from sphexa_tpu.telemetry.registry import (
    KIND_SINCE,
    SCHEMA_VERSION,
    validate_event,
)


def _entry(knobs, workload="sedov", n=1000, p=1, backend="xla",
           provenance=None):
    return make_entry(workload, n, p, backend, knobs,
                      provenance or {"source_run": "test"})


class TestKnobRegistry:
    def test_registry_matches_live_configs(self):
        # the import-time drift gate, run explicitly: every KnobSpec
        # must still name a real field on its owning dataclass/signature
        validate_registry()

    def test_drifted_spec_raises(self, monkeypatch):
        monkeypatch.setitem(
            knobs_mod.KNOBS, "target_block",
            KnobSpec("target_block", "GravityConfig", "renamed_away",
                     (64,), knobs_mod.COST_RECONFIGURE))
        with pytest.raises(RuntimeError, match="target_block"):
            validate_registry()

    def test_unknown_owner_raises(self, monkeypatch):
        monkeypatch.setitem(
            knobs_mod.KNOBS, "bogus",
            KnobSpec("bogus", "NoSuchConfig", "bogus", (1,),
                     knobs_mod.COST_STATIC))
        with pytest.raises(RuntimeError, match="unknown owner"):
            validate_registry()

    def test_groupings_cover_registry(self):
        grouped = set(GRAVITY_KNOBS) | set(NEIGHBOR_KNOBS) | set(
            SIMULATION_KNOBS) | set(BLOCKDT_KNOBS)
        assert grouped == set(knob_names())
        # domains are non-empty and lead with the production default
        for spec in KNOBS.values():
            assert spec.domain, spec.name


class TestTable:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "t.json")
        table = upsert_entry(new_table(), _entry({"gap": 128}))
        save_table(path, table)
        loaded = load_table(path)
        assert loaded["schema"] == TABLE_SCHEMA
        assert validate_table(loaded) == []
        assert loaded["entries"][0]["knobs"] == {"gap": 128}

    def test_load_errors(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_table(str(tmp_path / "missing.json"))
        bad = tmp_path / "bad.json"
        bad.write_text('{"not": "a table"}')
        with pytest.raises(ValueError, match="entries"):
            load_table(str(bad))

    def test_validate_flags_stale_knob_and_dupes(self):
        table = new_table()
        e = _entry({"gap": 128})
        e["knobs"]["ye_olde_knob"] = 1
        table["entries"] = [e, _entry({"gap": 256})]  # same key twice
        problems = validate_table(table)
        assert any("stale knob 'ye_olde_knob'" in p for p in problems)
        assert any("duplicate key" in p for p in problems)

    def test_make_entry_rejects_unregistered(self):
        with pytest.raises(ValueError, match="unregistered"):
            _entry({"warp_speed": 9})

    def test_n_bucket_decades(self):
        assert n_bucket(125) == "1e2"
        assert n_bucket(999) == "1e2"
        assert n_bucket(1000) == "1e3"
        assert n_bucket(500_000) == "1e5"

    def test_resolve_entry_prefers_exact_over_generic(self):
        table = new_table()
        upsert_entry(table, _entry({"gap": 128}, workload="generic"))
        upsert_entry(table, _entry({"gap": 512}, workload="sedov"))
        assert resolve_entry(table, "sedov", 1000, 1,
                             "xla")["knobs"] == {"gap": 512}
        assert resolve_entry(table, "noh", 1000, 1,
                             "xla")["knobs"] == {"gap": 128}
        assert resolve_entry(table, "sedov", 1000, 4, "xla") is None

    def test_coverage(self):
        table = upsert_entry(new_table(), _entry({"gap": 128}))
        assert coverage(table) == {
            "sedov/xla": {"n_buckets": ["1e3"], "p": [1]}}


class TestResolveKnobs:
    def test_precedence_explicit_beats_table(self, tmp_path):
        path = str(tmp_path / "t.json")
        save_table(path, upsert_entry(
            new_table(), _entry({"gap": 512, "cell_target": 64})))
        ov, prov = resolve_knobs(path, "sedov", 1000, 1, "xla",
                                 explicit={"gap": 999})
        # explicit kwarg wins: the table's gap never reaches overrides
        assert ov == {"cell_target": 64}
        assert prov["source"] == "table"
        assert prov["explicit"] == ["gap"]
        assert prov["key"]["n_bucket"] == "1e3"

    def test_fully_masked_entry_is_explicit(self, tmp_path):
        path = str(tmp_path / "t.json")
        save_table(path, upsert_entry(new_table(), _entry({"gap": 512})))
        ov, prov = resolve_knobs(path, "sedov", 1000, 1, "xla",
                                 explicit={"gap": 999})
        assert ov == {} and prov["source"] == "explicit"

    def test_none_is_heuristic_even_with_kwargs(self):
        # tuned=None must NEVER report "explicit": the app/bench always
        # pass kwargs, and a tuning event per ordinary run is noise
        ov, prov = resolve_knobs(None, "sedov", 1000, 1, "xla",
                                 explicit={"gap": 999})
        assert ov == {} and prov["source"] == "heuristic"

    def test_direct_dict_source(self):
        ov, prov = resolve_knobs({"gap": 256}, "sedov", 1000, 1, "xla",
                                 explicit={})
        assert ov == {"gap": 256} and prov["source"] == "direct"
        with pytest.raises(ValueError, match="unregistered"):
            resolve_knobs({"warp_speed": 9}, "sedov", 1000, 1, "xla",
                          explicit={})

    def test_table_miss_is_heuristic(self, tmp_path):
        path = str(tmp_path / "t.json")
        save_table(path, upsert_entry(new_table(), _entry({"gap": 512})))
        ov, prov = resolve_knobs(path, "evrard", 1000, 1, "xla",
                                 explicit={})
        assert ov == {} and prov["source"] == "heuristic"

    def test_simulation_consumes_table(self, tmp_path):
        # Simulation-level precedence at tiny N: table applies, an
        # explicit kwarg masks its knob, and provenance says so
        from sphexa_tpu.init import make_initializer
        from sphexa_tpu.simulation import Simulation

        path = str(tmp_path / "t.json")
        save_table(path, upsert_entry(new_table(), _entry(
            {"gap": 128, "check_every": 4}, n=125)))
        state, box, const = make_initializer("sedov")(5)
        sim = Simulation(state, box, const, backend="xla",
                         tuned=path, workload="sedov")
        assert sim.tuning_provenance["source"] == "table"
        assert sim.check_every == 4
        sim2 = Simulation(state, box, const, backend="xla",
                          tuned=path, workload="sedov", check_every=2)
        assert sim2.check_every == 2
        assert sim2.tuning_provenance["explicit"] == ["check_every"]

    def test_simulation_emits_tuning_event_only_when_tuned(self):
        from sphexa_tpu.init import make_initializer
        from sphexa_tpu.simulation import Simulation

        state, box, const = make_initializer("sedov")(5)
        mem = MemorySink()
        Simulation(state, box, const, backend="xla",
                   telemetry=Telemetry(sinks=[mem]))
        assert mem.of_kind("tuning") == []
        mem2 = MemorySink()
        Simulation(state, box, const, backend="xla",
                   tuned={"gap": 128}, workload="sedov",
                   telemetry=Telemetry(sinks=[mem2]))
        evs = mem2.of_kind("tuning")
        assert len(evs) == 1 and evs[0]["source"] == "direct"
        assert validate_event(evs[0]) == []


class TestSearch:
    def test_domains_for(self):
        d = domains_for(["gap", "cell_target"])
        # registry order, not argument order
        assert list(d) == ["cell_target", "gap"]
        with pytest.raises(KeyError, match="warp_speed"):
            domains_for(["warp_speed"])

    def test_deterministic_sweep(self):
        # fake measurement: gap=256 is the unique optimum, one value
        # crashes — the sweep must record it as failed and move on
        def measure(knobs):
            if knobs.get("gap") == 512:
                raise RuntimeError("boom")
            cost = {None: 10.0, 128: 9.0, 256: 7.0, 384: 8.0}
            return {"status": "ok", "value": cost[knobs.get("gap")]}

        mem = MemorySink()
        out = run_sweep(measure, {"gap": (384, 128, 256, 512)},
                        budget=16, telemetry=Telemetry(sinks=[mem]))
        assert out["baseline"]["value"] == 10.0
        assert out["best"] == {"knobs": {"gap": 256}, "value": 7.0}
        assert out["improved"]
        failed = [r for r in out["history"] if r["status"] == "failed"]
        assert failed and all("boom" in f["error"] for f in failed)
        # every attempt (incl. the dead one) is a valid v5 sweep event
        evs = mem.of_kind("sweep")
        assert len(evs) == out["candidates"] == len(out["history"])
        assert all(validate_event(e) == [] for e in evs)
        assert all(e["v"] == SCHEMA_VERSION for e in evs)
        # identical inputs -> identical trajectory (pure driver)
        again = run_sweep(measure, {"gap": (384, 128, 256, 512)},
                          budget=16)
        assert [r["knobs"] for r in again["history"]] == [
            r["knobs"] for r in out["history"]]

    def test_budget_respected_and_baseline_only(self):
        calls = []

        def measure(knobs):
            calls.append(knobs)
            return {"status": "ok", "value": 1.0}

        out = run_sweep(measure, {"gap": (384, 128, 256, 512)}, budget=2)
        assert out["candidates"] == 2 == len(calls)
        assert out["best"]["knobs"] == {}  # nothing beat the baseline
        assert not out["improved"]

    def test_overflow_never_becomes_incumbent(self):
        def measure(knobs):
            if knobs:
                return {"status": "overflow", "value": 0.001}
            return {"status": "ok", "value": 1.0}

        out = run_sweep(measure, {"gap": (384, 128)}, budget=4)
        assert out["best"]["knobs"] == {}


class TestReplay:
    def test_spec_from_manifest_round_trip(self, tmp_path):
        run = str(tmp_path / "run")
        write_manifest(run, config={"side": 5, "backend": "xla",
                                    "theta": 0.6},
                       particles=125,
                       extra={"case": "sedov", "prop": "std"})
        spec = spec_from_manifest(run)
        assert spec == ReplaySpec(case="sedov", side=5, prop="std",
                                  backend="xla", theta=0.6)
        assert spec.n == 125

    def test_spec_from_manifest_errors(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            spec_from_manifest(str(tmp_path / "nope"))
        run = str(tmp_path / "bad")
        write_manifest(run, config={}, extra={"case": "sedov"})
        with pytest.raises(ValueError, match="case/side"):
            spec_from_manifest(run)
        run2 = str(tmp_path / "snap")
        write_manifest(run2, config={"side": 5},
                       extra={"case": "snapshot.npz"})
        with pytest.raises(ValueError, match="snapshot"):
            spec_from_manifest(run2)

    def test_measure_candidate_from_manifest(self, tmp_path):
        # e2e at tiny N: manifest -> spec -> one measured candidate
        run = str(tmp_path / "run")
        write_manifest(run, config={"side": 5, "backend": "xla"},
                       particles=125, extra={"case": "sedov"})
        spec = spec_from_manifest(run)
        r = measure_candidate(spec, {"gap": 128}, steps=2, warmup=1)
        assert r["status"] == "ok"
        assert r["steps"] >= 2 and r["per_step_s"] > 0
        assert r["value"] == r["per_step_s"]


class TestSchemaV5:
    def test_v5_kinds_registered(self):
        assert KIND_SINCE["sweep"] == 5
        assert KIND_SINCE["tuning"] == 5

    def test_v5_events_validate(self):
        ok = {"v": 5, "seq": 0, "t": 1.0, "kind": "sweep",
              "candidate": 0, "knobs": {}, "status": "ok"}
        assert validate_event(ok) == []
        assert any("missing field 'status'" in p for p in validate_event(
            {"v": 5, "seq": 0, "t": 1.0, "kind": "sweep",
             "candidate": 0, "knobs": {}}))
        tuning = {"v": 5, "seq": 1, "t": 1.0, "kind": "tuning",
                  "source": "table"}
        assert validate_event(tuning) == []

    def test_v5_kind_on_older_version_flagged(self):
        bad = {"v": 4, "seq": 0, "t": 1.0, "kind": "sweep",
               "candidate": 0, "knobs": {}, "status": "ok"}
        assert any("v5-only" in p for p in validate_event(bad))

    def test_older_versions_still_clean(self):
        # one representative kind per older schema version keeps
        # validating (the compatibility promise of SUPPORTED_VERSIONS)
        for v, kind, payload in (
                (1, "step", {"it": 0, "wall_s": 0.1}),
                (2, "exchange", {"it": 0, "shipped_rows": 1, "rows": 1}),
                (3, "physics", {"it": 0, "etot": 1.0}),
                (4, "crash", {"reason": "test"}),
                (5, "sweep", {"candidate": 0, "knobs": {},
                              "status": "ok"})):
            e = {"v": v, "seq": 0, "t": 1.0, "kind": kind, **payload}
            assert validate_event(e) == [], (v, kind)


class TestSchemaV6:
    def test_v6_kind_registered(self):
        assert KIND_SINCE["dt_bins"] == 6

    def test_v6_event_validates(self):
        ok = {"v": 6, "seq": 0, "t": 1.0, "kind": "dt_bins", "it": 3,
              "pop": [100, 50, 25, 337], "updates": 512,
              "updates_full": 4096}
        assert validate_event(ok) == []
        assert any("missing field 'pop'" in p for p in validate_event(
            {"v": 6, "seq": 0, "t": 1.0, "kind": "dt_bins", "it": 3,
             "updates": 1, "updates_full": 1}))

    def test_v6_kind_on_older_version_flagged(self):
        bad = {"v": 5, "seq": 0, "t": 1.0, "kind": "dt_bins", "it": 0,
               "pop": [1], "updates": 1, "updates_full": 1}
        assert any("v6-only" in p for p in validate_event(bad))


class TestSchemaV7:
    def test_v7_keeps_no_kinds(self):
        # v7 adds the optional staged-exchange payload, no new kinds: no
        # KIND_SINCE entry may claim 7 (v8 added the snapshot kind, v9
        # the span kind — tests/test_serve.py pins the current version);
        # v10 did the same for ``rebuild_lists`` (optional WHY payload),
        # v11 for the planned window (``window.planned_steps``,
        # ``rebuild_lists.rate`` / ``cover_steps``), v12 for the flat
        # lane table (``rebuild_lists.slots_live`` / ``slots_cap``), v13
        # for the tree solve's list fills on ``window`` / ``step``, v14
        # for the run axis of ``exchange`` (``run_slots``, ``live_runs_max``),
        # v15 for the cooling extrema on ``numerics``, v16 for the
        # radiated-energy counter on it, v17 for the compaction kernel's
        # live-chunk shares beside v13's fills, v18 for the pair lists'
        # run tiles (``rebuild_lists.chunks_live`` / ``runs_live`` /
        # ``run_rows``)
        assert SCHEMA_VERSION >= 18
        assert not ({7, 10, 11, 12, 13, 14, 15, 16, 17, 18}
                    & set(KIND_SINCE.values()))

    def test_v7_staged_exchange_validates(self):
        for stage in ("sph", "gravity"):
            ok = {"v": 7, "seq": 0, "t": 1.0, "kind": "exchange", "it": 1,
                  "shipped_rows": 460, "rows": [460, 460],
                  "stage": stage}
            assert validate_event(ok) == []

    def test_v6_exchange_without_stage_still_validates(self):
        # pre-v7 writers never staged; the field stays optional
        ok = {"v": 6, "seq": 0, "t": 1.0, "kind": "exchange", "it": 1,
              "shipped_rows": 10, "rows": [10]}
        assert validate_event(ok) == []


class TestCli:
    def test_tune_unknown_case_exits_2(self, tmp_path, capsys):
        from sphexa_tpu.tuning.cli import main

        rc = main(["--case", "warpdrive", "--out",
                   str(tmp_path / "out")])
        assert rc == 2

    def test_tune_unknown_knob_exits_2(self, tmp_path):
        from sphexa_tpu.tuning.cli import main

        rc = main(["--case", "sedov", "--side", "5",
                   "--knobs", "warp_speed",
                   "--out", str(tmp_path / "out")])
        assert rc == 2

    def test_telemetry_tuning_no_table_exits_2(self, tmp_path):
        from sphexa_tpu.telemetry.cli import main

        assert main(["tuning", str(tmp_path / "missing.json")]) == 2

    def test_telemetry_tuning_stale_knob_exits_1(self, tmp_path,
                                                 capsys):
        from sphexa_tpu.telemetry.cli import main

        path = tmp_path / "t.json"
        table = upsert_entry(new_table(), _entry({"gap": 128}))
        table["entries"][0]["knobs"] = {"ye_olde_knob": 1}
        path.write_text(json.dumps(table))
        assert main(["tuning", str(path)]) == 1
        assert "stale knob" in capsys.readouterr().out

    def test_telemetry_tuning_coverage_gap_exits_1(self, tmp_path,
                                                   capsys):
        from sphexa_tpu.telemetry.cli import main

        path = tmp_path / "t.json"
        save_table(str(path), upsert_entry(new_table(),
                                           _entry({"gap": 128})))
        assert main(["tuning", str(path)]) == 0
        assert main(["tuning", str(path),
                     "--require", "sedov,1000,1,xla"]) == 0
        assert main(["tuning", str(path),
                     "--require", "noh,1000000,16,pallas"]) == 1

    def test_committed_table_is_valid(self):
        # the repo-root TUNING_TABLE.json must stay registry-clean
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        table = load_table(os.path.join(root, "TUNING_TABLE.json"))
        assert validate_table(table) == []


class TestStaticCostObjective:
    """static-cost:<phase> — the chip-free sweep objective (jaxcost)."""

    def test_candidate_scores_without_running_steps(self):
        from sphexa_tpu.tuning import static_cost_candidate

        spec = ReplaySpec(case="sedov", side=6, prop="std",
                          backend="auto", theta=0.5, devices=None)
        rec = static_cost_candidate(spec, {"target_block": 64},
                                    "density", device="v5e")
        assert rec["status"] == "ok"
        assert rec["objective"] == "static-cost:density"
        assert rec["value"] > 0
        assert rec["value"] == rec["predicted_ms"]
        assert rec["bound"] in ("compute", "memory", "ici")
        assert rec["steps"] == 0          # nothing executed, only traced

    def test_unknown_phase_raises(self):
        from sphexa_tpu.tuning import static_cost_candidate

        spec = ReplaySpec(case="sedov", side=6, prop="std",
                          backend="auto", theta=0.5, devices=None)
        with pytest.raises(ValueError):
            static_cost_candidate(spec, {}, "warpdrive")

    def test_cli_micro_sweep_emits_valid_v5_events(self, tmp_path):
        from sphexa_tpu.tuning.cli import main

        out = tmp_path / "sweep"
        rc = main(["--case", "sedov", "--side", "6",
                   "--knobs", "target_block", "--budget", "2",
                   "--objective", "static-cost:density",
                   "--out", str(out), "--quiet"])
        assert rc == 0
        events = [json.loads(line) for line in
                  (out / "events.jsonl").read_text().splitlines()]
        sweeps = [e for e in events if e.get("kind") == "sweep"]
        assert len(sweeps) == 2
        for e in sweeps:
            assert validate_event(e) == []
            assert e["status"] == "ok"
            assert e["objective"] == "static-cost:density"
            assert e["value"] > 0
