"""statecheck: symbolic schema inference, the STATE_SCHEMA.json lock,
the JXA5xx rules, the CLI, and the ensemble-mode seed.

The schema's value is the same stability contract jaxdiff pins for the
lowering: same program -> same rows, across processes (the committed
lock is verified cross-process by scripts/check.sh and the slow tier
here), with axis polynomials fitted EXACTLY (rational arithmetic) from
the registry's two-point grow probes. The JXA5xx fixtures live in
tests/statecheck_fixtures/ because they need a controlled context
(doctored lock path, vmap_members on) that the shared
tests/audit_fixtures runner does not set.
"""

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sphexa_tpu.devtools.audit.core import (
    Auditor,
    EntryCase,
    EntryTrace,
    audit_context,
    entries_from_namespace,
    entrypoint,
    set_audit_context,
)
from sphexa_tpu.devtools.audit.statecheck import (
    DEFAULT_SCHEMA_PATH,
    SCHEMA_VERSION,
    LockError,
    _fit_axes,
    entry_schema,
    format_axes,
    load_lock,
    main as schema_main,
    schema_diff,
    vmap_probe,
    write_lock,
)
from sphexa_tpu.devtools.primitives import HOST_CALLBACK_PRIMS

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "statecheck_fixtures"

_EXPECT_RE = re.compile(
    r"#\s*expect:\s*([A-Z]{3}\d{3}(?:\s*,\s*[A-Z]{3}\d{3})*)"
)


def expected_findings(path: Path):
    out = []
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        m = _EXPECT_RE.search(line)
        if m:
            for code in m.group(1).split(","):
                out.append((i, code.strip()))
    return sorted(out)


def load_fixture_entries(name: str):
    path = FIXTURES / name
    spec = importlib.util.spec_from_file_location(
        f"statecheck_fixture_{path.stem}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return entries_from_namespace(vars(mod))


# ---------------------------------------------------------------------------
# axis-polynomial fits
# ---------------------------------------------------------------------------


class TestAxisFit:
    def test_const_extensive_affine(self):
        axes = _fit_axes((216, 216, 220, 648), (512, 512, 516, 1536),
                         216, 512)
        assert axes[0] == {"kind": "const", "dim": 216} or \
            axes[0]["kind"] == "extensive"
        # d == n at both points: extensive with unit slope
        assert axes[1] == {"kind": "extensive", "per_n": "1"}
        # d == n + 4: affine with integral offset
        assert axes[2] == {"kind": "affine", "per_n": "1", "offset": 4}
        # d == 3n: extensive with slope 3
        assert axes[3] == {"kind": "extensive", "per_n": "3"}

    def test_unchanged_dim_is_const(self):
        assert _fit_axes((7,), (7,), 216, 512) == \
            [{"kind": "const", "dim": 7}]

    def test_capacity_padded_pow2_is_data(self):
        # pow2 capacity of N=12 -> 16 and N=21 -> 32 fits no integral
        # affine polynomial: stays raw data with both observations
        axes = _fit_axes((16,), (32,), 12, 21)
        assert axes == [{"kind": "data", "observed": [16, 32]}]

    def test_format_axes_renders_every_kind(self):
        s = format_axes([
            {"kind": "const", "dim": 3},
            {"kind": "extensive", "per_n": "1"},
            {"kind": "extensive", "per_n": "4/3"},
            {"kind": "affine", "per_n": "1", "offset": 4},
            {"kind": "data", "observed": [16, 32]},
        ])
        assert s == "[3, N, 4/3N, N+4, data(16..32)]"


# ---------------------------------------------------------------------------
# schema inference on toy entries
# ---------------------------------------------------------------------------


def _toy_grow_entry():
    """A toy with an extensive leaf, a const leaf, an O(tree)-style
    capacity leaf (pow2 of N), and a scalar — plus a grow probe."""

    def make(n):
        cap = 1 << (n - 1).bit_length()

        def fn(x):
            return x * 2.0, jnp.zeros(cap), jnp.float32(1.0)

        return EntryCase(fn=fn, args=(jnp.zeros(n, jnp.float32),))

    @entrypoint("toy_grow", phase_coverage_min=0.0)
    def toy_grow():
        case = make(12)
        return dataclasses.replace(
            case, grow=lambda: (make(21), 21 / 12))

    return toy_grow


class TestEntrySchema:
    def test_rows_and_kinds(self):
        entry = _toy_grow_entry()
        trace = EntryTrace(entry, entry.build())
        row = entry_schema(trace)
        assert row["n_base"] == 12
        assert row["grow"] == "7/4"
        leaves = row["leaves"]
        assert leaves["[0]"]["shape"] == \
            [{"kind": "extensive", "per_n": "1"}]
        assert leaves["[1]"]["shape"] == \
            [{"kind": "data", "observed": [16, 32]}]
        assert leaves["[2]"]["shape"] == []
        assert all(leaf["dtype"] == "float32" for leaf in leaves.values())
        # cached: the second call returns the same object, no retrace
        assert entry_schema(trace) is row

    def test_no_grow_means_const_axes(self):
        @entrypoint("toy_static", phase_coverage_min=0.0)
        def toy_static():
            return EntryCase(fn=lambda x: x @ x.T,
                             args=(jnp.zeros((4, 3)),))

        trace = EntryTrace(toy_static, toy_static.build())
        row = entry_schema(trace)
        assert row["grow"] is None
        assert row["leaves"][""]["shape"] == \
            [{"kind": "const", "dim": 4}, {"kind": "const", "dim": 4}]

    def test_weak_type_recorded(self):
        @entrypoint("toy_weak", phase_coverage_min=0.0)
        def toy_weak():
            # a bare Python-float product leaks a weak-typed output
            return EntryCase(fn=lambda x: (x, x.sum() * 2.0),
                             args=(jnp.zeros(4, jnp.float32),))

        row = entry_schema(EntryTrace(toy_weak, toy_weak.build()))
        weak = {p: leaf["weak_type"] for p, leaf in row["leaves"].items()}
        assert weak == {"[0]": False, "[1]": False}

    def test_schema_diff_names_paths(self):
        entry = _toy_grow_entry()
        row = entry_schema(EntryTrace(entry, entry.build()))
        doctored = json.loads(json.dumps(row))
        doctored["leaves"]["[0]"]["dtype"] = "float64"
        del doctored["leaves"]["[2]"]
        doctored["leaves"]["[9]"] = doctored["leaves"]["[1]"]
        lines = "\n".join(schema_diff("toy_grow", doctored, row))
        assert "~ [0]: float64[N] -> float32[N]" in lines
        assert "+ [2]" in lines and "- [9]" in lines
        assert "+1 -1 ~1 leaves" in lines


# ---------------------------------------------------------------------------
# lock IO
# ---------------------------------------------------------------------------


class TestLockIO:
    def test_roundtrip(self, tmp_path):
        entry = _toy_grow_entry()
        row = entry_schema(EntryTrace(entry, entry.build()))
        path = tmp_path / "schema.json"
        write_lock(path, {"toy_grow": row})
        entries = load_lock(path)
        assert entries["toy_grow"] == row
        assert json.loads(path.read_text())["version"] == SCHEMA_VERSION

    def test_corrupt_and_wrong_version_raise(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        with pytest.raises(LockError):
            load_lock(bad)
        versioned = tmp_path / "old.json"
        versioned.write_text(json.dumps({"version": 999, "entries": {}}))
        with pytest.raises(LockError):
            load_lock(versioned)
        with pytest.raises(LockError):
            load_lock(tmp_path / "missing.json")


# ---------------------------------------------------------------------------
# the JXA5xx firing fixtures (exact-marker contract, controlled context)
# ---------------------------------------------------------------------------


def _run_with_context(fixture: str, select, **ctx_overrides):
    prev = set_audit_context(
        dataclasses.replace(audit_context(), **ctx_overrides))
    try:
        return Auditor(select=select).run_entries(
            load_fixture_entries(fixture))
    finally:
        set_audit_context(prev)


class TestRuleFixtures:
    def test_jxa501_fires_on_drift_only(self):
        active, _sup, errors, skipped = _run_with_context(
            "jxa501_drift.py", ["JXA501"],
            state_schema_path=str(FIXTURES / "jxa501_schema.json"))
        assert not errors and not skipped
        actual = sorted((f.line, f.rule) for f in active)
        assert actual == expected_findings(FIXTURES / "jxa501_drift.py")
        assert "float64" in active[0].message  # the locked-side aval

    def test_jxa501_skips_when_lock_absent(self, tmp_path):
        active, _sup, errors, _sk = _run_with_context(
            "jxa501_drift.py", ["JXA501"],
            state_schema_path=str(tmp_path / "nonexistent.json"))
        assert not active and not errors

    def test_jxa501_flags_corrupt_lock(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        active, _sup, errors, _sk = _run_with_context(
            "jxa501_drift.py", ["JXA501"], state_schema_path=str(bad))
        assert not errors
        assert {f.rule for f in active} == {"JXA501"}
        assert all("unreadable" in f.message for f in active)

    def test_jxa502_fires_under_vmap_context(self):
        active, _sup, errors, skipped = _run_with_context(
            "jxa502_vmap.py", ["JXA502"], vmap_members=2)
        assert not errors and not skipped
        actual = sorted((f.line, f.rule) for f in active)
        assert actual == expected_findings(FIXTURES / "jxa502_vmap.py")
        msgs = " ".join(f.message for f in active)
        assert "does not trace" in msgs          # vmap_trace_break
        # vmap_callback: whichever primitive this jax prints through
        assert any(f"`{p}`" in msgs for p in HOST_CALLBACK_PRIMS)
        assert "serialized loops" in msgs        # vmap_serialized

    def test_jxa502_off_by_default(self):
        active, _sup, errors, _sk = _run_with_context(
            "jxa502_vmap.py", ["JXA502"])  # vmap_members stays 0
        assert not active and not errors

    def test_jxa503_fires_on_open_carries(self):
        active, _sup, errors, skipped = _run_with_context(
            "jxa503_carry.py", ["JXA503"])
        assert not errors and not skipped
        actual = sorted((f.line, f.rule) for f in active)
        assert actual == expected_findings(FIXTURES / "jxa503_carry.py")
        msgs = " ".join(f.message for f in active)
        assert "STRUCTURE" in msgs               # the None<->array flip
        assert "float32[2,8]" in msgs            # the aval drift


class TestVmapProbe:
    def test_clean_entry_report(self):
        @entrypoint("probe_clean", phase_coverage_min=0.0)
        def probe_clean():
            return EntryCase(fn=lambda x: jnp.sin(x),
                             args=(jnp.zeros(8),))

        trace = EntryTrace(probe_clean, probe_clean.build())
        report = vmap_probe(trace, 3)
        assert report["error"] is None
        assert report["callbacks"] == []
        assert report["vmap_loops"] == report["base_loops"] == 0
        # cached per (trace, members)
        assert vmap_probe(trace, 3) is report


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


_TOY_REGISTRY = '''
import jax.numpy as jnp

from sphexa_tpu.devtools.audit.core import EntryCase, entrypoint


@entrypoint("toy_a", phase_coverage_min=0.0)
def toy_a():
    return EntryCase(fn=lambda x: x * 2.0, args=(jnp.ones(4),))


@entrypoint("toy_b", phase_coverage_min=0.0)
def toy_b():
    return EntryCase(
        fn=lambda x, s: (x + s, s),
        args=(jnp.ones(4), jnp.float32(0.0)),
        carry=lambda a, out: (a[0], out[1]),
    )
'''


class TestCli:
    @pytest.fixture()
    def toy(self, tmp_path):
        reg = tmp_path / "toy_registry.py"
        reg.write_text(_TOY_REGISTRY)
        lock = tmp_path / "schema.json"
        rc = schema_main([str(reg), "--lock", str(lock), "--write",
                          "--cpu-devices", "0"])
        assert rc == 0 and lock.exists()
        return reg, lock

    def test_write_then_verify(self, toy, capsys):
        reg, lock = toy
        rc = schema_main([str(reg), "--lock", str(lock),
                          "--cpu-devices", "0"])
        assert rc == 0
        assert "2/2 entries match" in capsys.readouterr().out

    def test_doctored_dtype_exits_1_with_diff(self, toy, capsys):
        reg, lock = toy
        payload = json.loads(lock.read_text())
        leaf = payload["entries"]["toy_a"]["leaves"][""]
        leaf["dtype"] = "float64"
        lock.write_text(json.dumps(payload))
        rc = schema_main([str(reg), "--lock", str(lock),
                          "--cpu-devices", "0"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "toy_a: state schema drifted" in out
        assert "float64[4] -> float32[4]" in out

    def test_corrupt_lock_exits_2(self, toy):
        reg, lock = toy
        lock.write_text("{not json")
        assert schema_main([str(reg), "--lock", str(lock),
                            "--cpu-devices", "0"]) == 2

    def test_unknown_entry_exits_2(self, toy):
        reg, lock = toy
        assert schema_main([str(reg), "--lock", str(lock),
                            "--entries", "no_such_entry",
                            "--cpu-devices", "0"]) == 2

    def test_stale_and_missing_rows_exit_1(self, toy, capsys):
        reg, lock = toy
        payload = json.loads(lock.read_text())
        payload["entries"]["ghost"] = payload["entries"].pop("toy_b")
        lock.write_text(json.dumps(payload))
        rc = schema_main([str(reg), "--lock", str(lock),
                          "--cpu-devices", "0"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "ghost" in out   # stale row flagged
        assert "toy_b" in out   # unlocked entry flagged
        # an --entries-filtered run must NOT flag staleness
        assert schema_main([str(reg), "--lock", str(lock),
                            "--entries", "toy_a",
                            "--cpu-devices", "0"]) == 0

    def test_mesh_mismatch_rows_are_skipped(self, toy, capsys):
        reg, lock = toy
        payload = json.loads(lock.read_text())
        payload["entries"]["toy_a"]["mesh"] = 99
        lock.write_text(json.dumps(payload))
        rc = schema_main([str(reg), "--lock", str(lock),
                          "--cpu-devices", "0"])
        assert rc == 0  # locked at another mesh: neither drift nor stale
        assert "mesh-skipped" in capsys.readouterr().err

    def test_json_payload(self, toy, capsys):
        reg, lock = toy
        rc = schema_main([str(reg), "--lock", str(lock), "--json",
                          "--cpu-devices", "0"])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["tool"] == "statecheck"
        assert {e["entry"] for e in payload["entries"]} == \
            {"toy_a", "toy_b"}
        assert all(e["match"] for e in payload["entries"])
        assert payload["findings"] == []
        assert payload["errors"] == []

    def test_vmap_flag_reports_clean_toys(self, toy, capsys):
        reg, lock = toy
        rc = schema_main([str(reg), "--lock", str(lock), "--vmap",
                          "--members", "3", "--cpu-devices", "0"])
        assert rc == 0
        assert "2/2 single-device entries batch clean over 3 members" \
            in capsys.readouterr().out

    def test_open_carry_fails_via_jxa503(self, tmp_path, capsys):
        reg = tmp_path / "bad_registry.py"
        # feed the f32[4] output back into the SCALAR carry slot
        reg.write_text(_TOY_REGISTRY.replace(
            "carry=lambda a, out: (a[0], out[1])",
            "carry=lambda a, out: (a[0], out[0])"))
        lock = tmp_path / "schema.json"
        assert schema_main([str(reg), "--lock", str(lock), "--write",
                            "--cpu-devices", "0"]) == 0
        rc = schema_main([str(reg), "--lock", str(lock),
                          "--cpu-devices", "0"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "JXA503" in out

    def test_subcommand_reachable_from_audit_cli(self, toy):
        from sphexa_tpu.devtools.audit.cli import main as audit_main

        reg, lock = toy
        assert audit_main(["schema", str(reg), "--lock", str(lock),
                           "--cpu-devices", "0"]) == 0


# ---------------------------------------------------------------------------
# ensemble-mode seed: the vmapped SimState step (ROADMAP item 3)
# ---------------------------------------------------------------------------


class TestEnsembleSeed:
    def test_two_member_sedov_member0_bitwise(self):
        """A 2-member ensemble stepped as ONE vmapped SimState program:
        member 0 (unperturbed) must be bitwise-identical to the plain
        unvmapped step, and the perturbed member must actually diverge —
        the seed the JXA502 gate keeps admissible."""
        from sphexa_tpu import propagator
        from sphexa_tpu.init import init_sedov
        from sphexa_tpu.simulation import make_propagator_config
        from sphexa_tpu.state import SimState

        state, box, const = init_sedov(6)
        cfg = make_propagator_config(state, box, const)

        def step(sim):
            return propagator.step_sim_state(
                propagator.step_hydro_std, sim, cfg, None)

        sim0 = SimState(particles=state, box=box)
        out_single, diag_single = step(sim0)

        member1 = SimState(
            particles=dataclasses.replace(state, temp=state.temp * 1.01),
            box=box)
        batched = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                               sim0, member1)
        out, diag = jax.vmap(step)(batched)

        for name in ("x", "y", "z", "vx", "vy", "vz", "temp", "du", "h"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out_single.particles, name)),
                np.asarray(getattr(out.particles, name))[0],
                err_msg=f"member 0 diverges from the unvmapped run: {name}")
        np.testing.assert_array_equal(
            np.asarray(out_single.box.lo), np.asarray(out.box.lo)[0])
        assert not np.array_equal(np.asarray(out.particles.temp)[0],
                                  np.asarray(out.particles.temp)[1]), \
            "perturbed member did not diverge — the ensemble is degenerate"
        assert set(diag) == set(diag_single)
        # aux slots stay empty through the batched step (carry closure)
        assert out.turb is None and out.chem is None and out.bdt is None


# ---------------------------------------------------------------------------
# the committed lock (slow tier; check.sh repeats this cross-process)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestCommittedLock:
    def test_package_schema_verifies(self):
        rc = schema_main([
            "--lock", str(REPO_ROOT / DEFAULT_SCHEMA_PATH),
            "--cpu-devices", "0"])
        assert rc == 0
