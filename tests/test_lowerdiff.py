"""jaxdiff: the canonical lowering fingerprint, the lock, the
structural differ, and the JXA402 knob-inertness probes.

The fingerprint's value is its stability contract: same program ->
same digest, across retraces in one process (jax's pretty-print var
counter must not leak in) and across processes (no object addresses, no
hash-randomized iteration). tests here pin the contract on toy
programs; tests/test_parallel.py keeps the ONE raw ``as_text()``
byte-identity pin that guards the canonicalizer itself, and
scripts/check.sh verifies the committed LOWERING_LOCK.json across a
process boundary every run.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sphexa_tpu.devtools.audit.lowerdiff import (
    DEFAULT_LOCK_PATH,
    LOCK_VERSION,
    UNATTRIBUTED,
    KnobProbe,
    LockError,
    fingerprint_callable,
    load_lock,
    main as lowering_main,
    production_knob_probes,
    structural_diff,
    write_lock,
)
from sphexa_tpu.devtools.primitives import CALL_PRIMS
from sphexa_tpu.util.phases import phase_scope

REPO_ROOT = Path(__file__).resolve().parent.parent


def _double(x):
    return x * 2.0


class TestFingerprint:
    def test_deterministic_across_retraces(self):
        # jax's global pretty-print var counter advances with every
        # trace; an unrelated trace in between must not move the digest
        fp1 = fingerprint_callable(_double, jnp.ones(4))
        fingerprint_callable(lambda y: jnp.sin(y).sum(), jnp.ones((3, 3)))
        fp2 = fingerprint_callable(_double, jnp.ones(4))
        assert fp1.digest == fp2.digest
        assert fp1.eqn_hashes == fp2.eqn_hashes

    def test_alpha_invariance_vs_real_change(self):
        # a re-created lambda with identical structure collides; a
        # different literal does not
        fp_a = fingerprint_callable(lambda x: x * 2.0 + 1.0, jnp.ones(4))
        fp_b = fingerprint_callable(lambda x: x * 2.0 + 1.0, jnp.ones(4))
        fp_c = fingerprint_callable(lambda x: x * 3.0 + 1.0, jnp.ones(4))
        assert fp_a.digest == fp_b.digest
        assert fp_a.digest != fp_c.digest

    def test_jitted_and_inner_jaxprs(self):
        # a jitted callable traces to one call eqn whose body the walk
        # expands inline — the eqn count must see the body, not the call
        fp = fingerprint_callable(jax.jit(_double), jnp.ones(4))
        assert fp.eqns >= 2  # the jit call + at least the mul
        prims = [re.search(r" = (\w+)\[", ln).group(1) for ln in fp.lines]
        assert prims[0] in CALL_PRIMS and "mul" in prims[1:]

    def test_phase_attribution(self):
        def fn(x):
            with phase_scope("density"):
                d = x * x
            with phase_scope("eos"):
                p = jnp.sqrt(d)
            return p + 1.0  # outside every scope

        fp = fingerprint_callable(fn, jnp.ones(8))
        assert fp.phases["density"].eqns >= 1
        assert fp.phases["eos"].eqns >= 1
        assert fp.phases[UNATTRIBUTED].eqns >= 1
        assert sum(p.eqns for p in fp.phases.values()) == fp.eqns

    def test_consts_move_the_digest(self):
        # same eqn structure, different baked const value: the global
        # digest must move even though the eqn-hash stream is identical
        w1 = np.arange(4, dtype=np.float32)
        w2 = np.arange(4, dtype=np.float32) + 1.0

        fp1 = fingerprint_callable(jax.jit(lambda x: x * jnp.asarray(w1)),
                                   jnp.ones(4))
        fp2 = fingerprint_callable(jax.jit(lambda x: x * jnp.asarray(w2)),
                                   jnp.ones(4))
        assert fp1.consts_digest != fp2.consts_digest
        assert fp1.digest != fp2.digest

    def test_collective_count(self):
        mesh = jax.make_mesh((2,), ("p",))
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        @partial(shard_map, mesh=mesh, in_specs=P("p"), out_specs=P())
        def fn(x):
            return jax.lax.psum(x.sum(), "p")[None]

        fp = fingerprint_callable(fn, jnp.ones(8))
        assert fp.collectives == 1


class TestLockIO:
    def test_roundtrip(self, tmp_path):
        fp = fingerprint_callable(_double, jnp.ones(4))
        path = tmp_path / "lock.json"
        write_lock(path, {"toy": fp.lock_payload()})
        entries = load_lock(path)
        assert entries["toy"]["digest"] == fp.digest
        assert entries["toy"]["eqns"] == fp.eqns
        assert json.loads(path.read_text())["version"] == LOCK_VERSION

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


class TestStructuralDiff:
    def test_first_divergence_and_phase_rows(self):
        def base(x):
            with phase_scope("density"):
                return (x * 2.0).sum()

        def changed(x):
            with phase_scope("density"):
                return (x * 2.0 + 1.0).sum()

        fp_base = fingerprint_callable(base, jnp.ones(4))
        fp_new = fingerprint_callable(changed, jnp.ones(4))
        report = "\n".join(
            structural_diff("toy", fp_base.lock_payload(), fp_new))
        assert "first divergence: eqn #" in report
        assert "phase density" in report
        assert "density" in report.split("phases:")[-1]

    def test_const_only_change_reports_no_eqn_divergence(self):
        w1 = np.arange(4, dtype=np.float32)
        w2 = np.arange(4, dtype=np.float32) + 1.0
        fp1 = fingerprint_callable(jax.jit(lambda x: x * jnp.asarray(w1)),
                                   jnp.ones(4))
        fp2 = fingerprint_callable(jax.jit(lambda x: x * jnp.asarray(w2)),
                                   jnp.ones(4))
        report = "\n".join(
            structural_diff("toy", fp1.lock_payload(), fp2))
        assert "no per-eqn divergence" in report


_TOY_REGISTRY = '''
import jax.numpy as jnp

from sphexa_tpu.devtools.audit.core import EntryCase, entrypoint


@entrypoint("toy_a", phase_coverage_min=0.0)
def toy_a():
    return EntryCase(fn=lambda x: x * 2.0, args=(jnp.ones(4),))


@entrypoint("toy_b", phase_coverage_min=0.0)
def toy_b():
    return EntryCase(fn=lambda x: x.sum(), args=(jnp.ones(4),))
'''


class TestCli:
    @pytest.fixture()
    def toy(self, tmp_path):
        reg = tmp_path / "toy_registry.py"
        reg.write_text(_TOY_REGISTRY)
        lock = tmp_path / "lock.json"
        rc = lowering_main([str(reg), "--lock", str(lock), "--write",
                            "--cpu-devices", "0"])
        assert rc == 0 and lock.exists()
        return reg, lock

    def test_write_then_verify(self, toy, capsys):
        reg, lock = toy
        rc = lowering_main([str(reg), "--lock", str(lock),
                            "--cpu-devices", "0"])
        assert rc == 0
        assert "2/2 entries match" in capsys.readouterr().out

    def test_doctored_digest_exits_1_with_diff(self, toy, capsys):
        reg, lock = toy
        payload = json.loads(lock.read_text())
        payload["entries"]["toy_a"]["digest"] = "0" * 32
        stream = payload["entries"]["toy_a"]["eqn_hashes"]
        payload["entries"]["toy_a"]["eqn_hashes"] = "deadbeef" + stream[8:]
        lock.write_text(json.dumps(payload))
        rc = lowering_main([str(reg), "--lock", str(lock),
                            "--cpu-devices", "0"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "toy_a: lowering drifted" in out
        assert "first divergence: eqn #0" in out

    def test_corrupt_lock_exits_2(self, toy):
        reg, lock = toy
        lock.write_text("{not json")
        rc = lowering_main([str(reg), "--lock", str(lock),
                            "--cpu-devices", "0"])
        assert rc == 2

    def test_unknown_entry_exits_2(self, toy):
        reg, lock = toy
        rc = lowering_main([str(reg), "--lock", str(lock),
                            "--entries", "no_such_entry",
                            "--cpu-devices", "0"])
        assert rc == 2

    def test_stale_and_missing_rows_exit_1(self, toy, capsys):
        reg, lock = toy
        payload = json.loads(lock.read_text())
        payload["entries"]["ghost"] = payload["entries"].pop("toy_b")
        lock.write_text(json.dumps(payload))
        rc = lowering_main([str(reg), "--lock", str(lock),
                            "--cpu-devices", "0"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "ghost" in out  # stale row flagged
        assert "toy_b" in out  # unlocked entry flagged
        # an --entries-filtered run must NOT flag staleness
        rc = lowering_main([str(reg), "--lock", str(lock),
                            "--entries", "toy_a", "--cpu-devices", "0"])
        assert rc == 0

    def test_json_payload(self, toy, capsys):
        reg, lock = toy
        rc = lowering_main([str(reg), "--lock", str(lock), "--json",
                            "--cpu-devices", "0"])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["tool"] == "jaxdiff"
        assert {e["entry"] for e in payload["entries"]} == {"toy_a", "toy_b"}
        assert all(e["match"] for e in payload["entries"])
        assert payload["mismatched"] == []
        assert payload["errors"] == []


class TestKnobProbes:
    def test_production_probes_cover_every_off_sentinel(self):
        from sphexa_tpu.tuning.knobs import off_sentinel_knobs

        probes = production_knob_probes()
        assert [p.knob for p in probes] == \
            [s.name for s in off_sentinel_knobs()]
        assert len(probes) >= 7  # incl. dt_bins, grav_window, donate
        leaky = [p.knob for p in probes if p.off.digest != p.base.digest]
        assert not leaky, f"off sentinels perturb the lowering: {leaky}"

    def test_validate_off_sentinels_catches_renamed_site(self, monkeypatch):
        import sphexa_tpu.simulation as sim_mod
        from sphexa_tpu.tuning.knobs import validate_off_sentinels

        monkeypatch.setattr(
            sim_mod, "CONSUMED_KNOBS",
            tuple(k for k in sim_mod.CONSUMED_KNOBS if k != "dt_bins"))
        with pytest.raises(RuntimeError, match="dt_bins"):
            validate_off_sentinels()

    def test_jxa402_fires_on_manufactured_leak(self):
        # the rule itself, without a Simulation: a probe whose off
        # program lowers one extra eqn must produce exactly one finding
        from sphexa_tpu.devtools.audit.core import (
            EntryCase,
            EntryTrace,
            entrypoint,
        )
        from sphexa_tpu.devtools.audit.rules.jxa402_knob_inertness import (
            check,
        )

        probes = [KnobProbe(
            knob="leak", off_value=0,
            base=fingerprint_callable(lambda x: x * 2.0, jnp.ones(4)),
            off=fingerprint_callable(lambda x: x * 2.0 + 0.0, jnp.ones(4)),
        )]

        @entrypoint("manufactured", phase_coverage_min=0.0)
        def manufactured():
            return EntryCase(fn=lambda x: x, args=(jnp.ones(4),),
                             knob_probes=lambda: probes)

        # the decorator binding IS the EntryPoint
        findings = check(EntryTrace(manufactured, manufactured.build()))
        assert len(findings) == 1
        assert "leak" in findings[0].message


@pytest.mark.slow
class TestCommittedLock:
    def test_package_lock_verifies(self):
        """The committed LOWERING_LOCK.json must hold against the
        committed sources over the full registry (the check.sh gate,
        repeated here so the slow tier catches it without bash)."""
        rc = lowering_main([
            "--lock", str(REPO_ROOT / DEFAULT_LOCK_PATH),
            "--cpu-devices", "8"])
        assert rc == 0
