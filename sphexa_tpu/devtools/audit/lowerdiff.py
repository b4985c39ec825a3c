"""jaxdiff: canonical lowering fingerprints, the committed lock, and the
structural jaxpr differ.

    sphexa-audit lowering [targets] [--lock F] [--diff] [--write]
                          [--entries ...] [--json]

The fifth static-analysis layer (docs/STATIC_ANALYSIS.md): every
registered audit entry's jaxpr is canonicalized — variables renamed in
traversal order, params rendered address-free with nested jaxprs
expanded inline depth-first, consts hashed by shape/dtype/value — and
digested into a ``LoweringFingerprint``: one whole-program digest, one
per-canonical-eqn hash stream, and per-phase sub-digests keyed by the
``util/phases.py`` ``sphexa/<phase>`` name-stack taxonomy (the same
attribution jaxcost and traceview join on). The fingerprints for the
whole registry live in the committed ``LOWERING_LOCK.json``; a digest
mismatch exits 1 with a *structural* diff — first-divergence equation,
per-phase added/removed eqn counts, collective/const deltas — so an
intentional lowering change is reviewed as a diff and re-locked with
``--write``, and an unintentional one never survives to a chip round.

The same canonicalizer powers the JXA402 knob-inertness meta-rule:
``production_knob_probes()`` builds, for every ``KnobSpec`` carrying an
``off_sentinel``, a tiny probe ``Simulation`` with ``tuned={knob: off}``
and compares its step fingerprint against the never-mentioned baseline —
the generalization of the hand-written dt_bins=None / grav_window=0
byte-identity pins to the whole registry with zero per-knob test code.

Alpha-stability contract: two traces of the same program produce
identical fingerprints in the same environment (same jax build, same
virtual device count); tests/test_lowerdiff.py pins this, and ONE raw
``as_text()`` byte-identity pin stays behind in tests/test_parallel.py
as the guard on the canonicalizer itself.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import hashlib
import json
import os
import re
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from sphexa_tpu.devtools.primitives import (
    COLLECTIVE_PRIMS,
    sub_jaxprs,
    walk_consts,
)

__all__ = [
    "LOCK_VERSION",
    "DEFAULT_LOCK_PATH",
    "LockError",
    "PhaseFingerprint",
    "LoweringFingerprint",
    "fingerprint_closed_jaxpr",
    "fingerprint_callable",
    "lowering_fingerprint",
    "load_lock",
    "write_lock",
    "structural_diff",
    "KnobProbe",
    "production_knob_probes",
    "main",
]

LOCK_VERSION = 1
DEFAULT_LOCK_PATH = "LOWERING_LOCK.json"

#: hex chars per canonical-eqn hash in the lock's eqn streams
_HASH_W = 8
#: phase key for eqns outside every ``sphexa/<phase>`` scope
UNATTRIBUTED = "(unattributed)"

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")
# "<lambda> at /path/to/file.py:761" inside source-info reprs: keep the
# name, drop the location, so an unrelated line shift cannot drift the
# lock
_SRCLOC_RE = re.compile(r" at [^\s,()<>]+:\d+")


def _sha(data) -> str:
    if isinstance(data, str):
        data = data.encode()
    return hashlib.sha256(data).hexdigest()


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------


def _is_jaxprish(v) -> bool:
    return hasattr(v, "eqns") or (
        hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"))


def _aux_jaxpr_digest(v) -> str:
    """Alpha-invariant digest of a jaxpr buried inside a non-jaxpr param
    (e.g. a pallas GridMapping's index_map_jaxpr). These are NOT
    expanded inline by the walk, so their content enters the line as a
    digest of their own canonical rendering — a plain repr would carry
    jax's global pretty-print var counter and drift between traces of
    the same program in one process."""
    raw = getattr(v, "jaxpr", v)
    c = _Canonicalizer()
    c.walk(raw, "")
    sig = ",".join(str(x.aval) for x in
                   tuple(raw.constvars) + tuple(raw.invars))
    return f"jaxpr:{_sha(sig + chr(10) + chr(10).join(c.lines))[:16]}"


def _canon_value(v, inline: bool = False) -> str:
    """Render one param value position-independently: no object
    addresses, dicts sorted, arrays by shape/dtype/value-digest.

    ``inline`` is True exactly where ``sub_jaxprs`` expands jaxpr
    values after the call eqn (direct param values and items of
    list/tuple params) — there a jaxpr renders as a marker; everywhere
    else (dict values, dataclass fields) it renders as an
    alpha-invariant digest."""
    import numpy as np

    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        return repr(v)
    if isinstance(v, np.dtype):
        return str(v)
    if isinstance(v, type):
        return f"type:{v.__name__}"
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_canon_value(x, inline) for x in v) + ")"
    if isinstance(v, dict):
        return "{" + ",".join(
            f"{k!r}:{_canon_value(v[k])}" for k in sorted(v, key=str)) + "}"
    if _is_jaxprish(v):
        return "<jaxpr>" if inline else _aux_jaxpr_digest(v)
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        try:
            a = np.asarray(v)
            # np.asarray wraps ANY object into a 0-d object array whose
            # bytes are its memory address — only hash real numerics
            if a.dtype != np.dtype(object):
                return f"arr({a.shape},{a.dtype},{_sha(a.tobytes())[:16]})"
        except Exception:  # noqa: BLE001 - fall through to repr
            pass
    if dataclasses.is_dataclass(v):
        return f"{type(v).__name__}(" + ",".join(
            f"{f.name}={_canon_value(getattr(v, f.name))}"
            for f in dataclasses.fields(v)) + ")"
    if callable(v):
        return f"fn:{getattr(v, '__name__', type(v).__name__)}"
    return _SRCLOC_RE.sub(" at ·", _ADDR_RE.sub("0x·", repr(v)))


class _Canonicalizer:
    """One walk over a ClosedJaxpr producing canonical per-eqn lines.

    Variables are renamed ``v0, v1, ...`` in traversal order (binders
    first: constvars/invars at jaxpr entry, outvars at their defining
    eqn), so the digest is alpha-invariant. Nested jaxprs (jit bodies,
    scan/while/cond branches, shard_map bodies) expand inline
    depth-first after their call eqn's own line, inheriting its phase —
    the costmodel._walk convention, so the per-phase sub-digests group
    exactly like the jaxcost/traceview taxonomy.
    """

    def __init__(self):
        self._names: Dict[int, str] = {}
        self.lines: List[str] = []
        self.line_phases: List[str] = []
        self.collectives = 0

    def _name(self, v) -> str:
        return self._names.setdefault(id(v), f"v{len(self._names)}")

    def _atom(self, v) -> str:
        if hasattr(v, "val"):  # Literal
            return f"lit({_canon_value(v.val)}:{getattr(v, 'aval', '?')})"
        return self._name(v)

    def _eqn_line(self, eqn, phase: str) -> str:
        prim = eqn.primitive.name
        params = ",".join(
            f"{k}={_canon_value(eqn.params[k], inline=True)}"
            for k in sorted(eqn.params, key=str))
        ins = " ".join(self._atom(v) for v in eqn.invars)
        outs = " ".join(f"{self._name(v)}:{v.aval}" for v in eqn.outvars)
        return f"{phase}|{outs} = {prim}[{params}] {ins}"

    def walk(self, jaxpr, inherited: str) -> None:
        from sphexa_tpu.devtools.audit.costmodel import _phase_of

        for v in tuple(jaxpr.constvars) + tuple(jaxpr.invars):
            self._name(v)
        for eqn in jaxpr.eqns:
            phase = _phase_of(eqn, inherited)
            self.lines.append(self._eqn_line(eqn, phase))
            self.line_phases.append(phase or UNATTRIBUTED)
            prim = eqn.primitive.name
            if prim in COLLECTIVE_PRIMS:
                self.collectives += 1
            for sub in sub_jaxprs(eqn):
                self.walk(sub, phase)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PhaseFingerprint:
    digest: str
    eqns: int
    eqn_hashes: str      # _HASH_W hex chars per eqn, traversal order


@dataclasses.dataclass(frozen=True)
class LoweringFingerprint:
    digest: str          # whole-program: canonical lines + consts
    eqns: int
    collectives: int
    const_bytes: int
    consts_digest: str
    phases: Dict[str, PhaseFingerprint]
    eqn_hashes: str      # global per-eqn hash stream, traversal order
    # in-memory only (not persisted in the lock): the canonical lines
    # and their phases, for the structural diff's first-divergence text
    lines: Tuple[str, ...] = dataclasses.field(default=(), repr=False)
    line_phases: Tuple[str, ...] = dataclasses.field(default=(), repr=False)

    def lock_payload(self) -> Dict[str, Any]:
        # the per-phase hash streams are NOT stored: they reconstruct
        # from the global stream + the run-length phase map (phases are
        # contiguous runs in traversal order), halving the lock size
        runs: List[List[Any]] = []
        for ph in self.line_phases:
            if runs and runs[-1][0] == ph:
                runs[-1][1] += 1
            else:
                runs.append([ph, 1])
        return {
            "digest": self.digest,
            "eqns": self.eqns,
            "collectives": self.collectives,
            "const_bytes": self.const_bytes,
            "consts_digest": self.consts_digest,
            "eqn_hashes": self.eqn_hashes,
            "phase_runs": runs,
            "phases": {
                name: {"digest": p.digest, "eqns": p.eqns}
                for name, p in sorted(self.phases.items())
            },
        }


def _consts_fingerprint(closed) -> Tuple[str, int]:
    """(digest, total bytes) over every const of every nested
    ClosedJaxpr, in traversal order — a swapped const is a change even
    when shapes agree."""
    import numpy as np

    h = hashlib.sha256()
    total = 0
    for c in walk_consts(closed):
        try:
            a = np.asarray(c)
            if a.dtype == np.dtype(object):  # address bytes — no
                raise TypeError("object const")
            h.update(f"{a.shape}:{a.dtype}:".encode())
            h.update(a.tobytes())
            total += a.nbytes
        except Exception:  # noqa: BLE001 - non-array const
            h.update(_canon_value(c).encode())
    return h.hexdigest()[:32], total


def fingerprint_closed_jaxpr(closed) -> LoweringFingerprint:
    """Canonicalize + digest one ClosedJaxpr (the tentpole primitive)."""
    canon = _Canonicalizer()
    canon.walk(closed.jaxpr, "")
    line_hashes = [_sha(ln)[:_HASH_W] for ln in canon.lines]
    consts_digest, const_bytes = _consts_fingerprint(closed)
    by_phase: Dict[str, List[str]] = collections.defaultdict(list)
    by_phase_h: Dict[str, List[str]] = collections.defaultdict(list)
    for ln, ph, lh in zip(canon.lines, canon.line_phases, line_hashes):
        by_phase[ph].append(ln)
        by_phase_h[ph].append(lh)
    phases = {
        ph: PhaseFingerprint(
            digest=_sha("\n".join(lns))[:32],
            eqns=len(lns),
            eqn_hashes="".join(by_phase_h[ph]),
        )
        for ph, lns in by_phase.items()
    }
    digest = _sha("\n".join(canon.lines) + "\n#" + consts_digest)[:32]
    return LoweringFingerprint(
        digest=digest,
        eqns=len(canon.lines),
        collectives=canon.collectives,
        const_bytes=const_bytes,
        consts_digest=consts_digest,
        phases=phases,
        eqn_hashes="".join(line_hashes),
        lines=tuple(canon.lines),
        line_phases=tuple(canon.line_phases),
    )


def fingerprint_callable(fn: Callable, *args) -> LoweringFingerprint:
    """Trace ``fn(*args)`` and fingerprint it — the shared helper the
    migrated byte-identity pins (tests/test_blockdt.py,
    tests/test_parallel.py) and the knob probes build on."""
    import jax

    return fingerprint_closed_jaxpr(jax.make_jaxpr(fn)(*args))


def lowering_fingerprint(trace) -> LoweringFingerprint:
    """Cached per-entry fingerprint (the spmd_report/cost_report cache
    contract: one canonical walk per EntryTrace, shared by the lock CLI
    and the JXA4xx rules)."""
    cached = getattr(trace, "_lowering_fp", None)
    if cached is not None:
        return cached
    fp = fingerprint_closed_jaxpr(trace.closed_jaxpr)
    trace._lowering_fp = fp
    return fp


# ---------------------------------------------------------------------------
# lock IO
# ---------------------------------------------------------------------------


class LockError(ValueError):
    """Unreadable/corrupt/wrong-version lock file (CLI exit 2)."""


def load_lock(path) -> Dict[str, Dict[str, Any]]:
    p = Path(path)
    try:
        payload = json.loads(p.read_text())
    except OSError as e:
        raise LockError(f"cannot read lock {p}: {e}") from e
    except json.JSONDecodeError as e:
        raise LockError(f"corrupt lock {p}: {e}") from e
    if not isinstance(payload, dict) or "entries" not in payload:
        raise LockError(f"corrupt lock {p}: no 'entries' object")
    if payload.get("version") != LOCK_VERSION:
        raise LockError(
            f"lock {p} has version {payload.get('version')!r}, this tool "
            f"writes {LOCK_VERSION} (regenerate with --write)")
    return payload["entries"]


def write_lock(path, entries: Dict[str, Dict[str, Any]]) -> None:
    p = Path(path)
    payload = {
        "version": LOCK_VERSION,
        "tool": "jaxdiff",
        "comment": "canonical lowering fingerprints per audit entry; "
                   "regenerate with: sphexa-audit lowering --write",
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")


# ---------------------------------------------------------------------------
# structural diff
# ---------------------------------------------------------------------------


def _chunks(stream: str) -> List[str]:
    return [stream[i:i + _HASH_W] for i in range(0, len(stream), _HASH_W)]


def _locked_phase_hashes(locked: Dict[str, Any]) -> Dict[str, List[str]]:
    """Per-phase eqn-hash lists of a locked row, reconstructed from the
    global stream + the run-length phase map."""
    out: Dict[str, List[str]] = collections.defaultdict(list)
    chunks = _chunks(locked.get("eqn_hashes", ""))
    i = 0
    for ph, n in locked.get("phase_runs", []):
        out[ph] += chunks[i:i + int(n)]
        i += int(n)
    return out


def structural_diff(name: str, locked: Dict[str, Any],
                    fp: LoweringFingerprint,
                    verbose: bool = False) -> List[str]:
    """Human-readable structural diff of one entry vs its locked row —
    the PR-review artifact an intentional lowering change produces."""
    out: List[str] = []
    out.append(f"entry {name}: lowering drifted from the lock")
    out.append(f"  digest: {locked.get('digest')} -> {fp.digest}")
    for field in ("eqns", "collectives", "const_bytes"):
        old = locked.get(field)
        new = getattr(fp, field)
        delta = ""
        if isinstance(old, int):
            d = new - old
            delta = f"  ({d:+d})" if d else ""
        if old != new or delta:
            out.append(f"  {field}: {old} -> {new}{delta}")
    if locked.get("consts_digest") != fp.consts_digest:
        out.append(f"  consts: {locked.get('consts_digest')} -> "
                   f"{fp.consts_digest}")

    old_stream = _chunks(locked.get("eqn_hashes", ""))
    new_stream = _chunks(fp.eqn_hashes)
    div = next((i for i, (a, b) in enumerate(zip(old_stream, new_stream))
                if a != b), None)
    if div is None and len(old_stream) != len(new_stream):
        div = min(len(old_stream), len(new_stream))
    if div is None:
        out.append("  no per-eqn divergence (consts changed, or the lock "
                   "digest itself was edited)")
    else:
        phase = (fp.line_phases[div] if div < len(fp.line_phases)
                 else "(past end of current program)")
        out.append(f"  first divergence: eqn #{div} (phase {phase})")
        if div < len(fp.lines):
            out.append(f"    now: {fp.lines[div]}")
        else:
            out.append(f"    now: <program ends at eqn "
                       f"#{len(fp.lines) - 1}; locked stream continues>")

    # per-phase added/removed counts via eqn-hash multiset difference
    locked_phases = locked.get("phases", {})
    locked_hashes = _locked_phase_hashes(locked)
    all_phases = sorted(set(locked_phases) | set(fp.phases))
    phase_rows: List[str] = []
    for ph in all_phases:
        lp = locked_phases.get(ph)
        np_ = fp.phases.get(ph)
        if lp is None:
            phase_rows.append(f"    + {ph}: added ({np_.eqns} eqns)")
            continue
        if np_ is None:
            phase_rows.append(f"    - {ph}: removed ({lp.get('eqns')} eqns)")
            continue
        if lp.get("digest") == np_.digest:
            continue
        old_c = collections.Counter(locked_hashes.get(ph, []))
        new_c = collections.Counter(_chunks(np_.eqn_hashes))
        added = sum((new_c - old_c).values())
        removed = sum((old_c - new_c).values())
        note = "reordered" if not (added or removed) else \
            f"+{added}/-{removed} eqns"
        phase_rows.append(f"    ~ {ph}: {note} "
                          f"({lp.get('eqns')} -> {np_.eqns})")
    if phase_rows:
        out.append("  phases:")
        out += phase_rows
    if verbose and div is not None:
        lo = max(0, div - 2)
        hi = min(len(fp.lines), div + 6)
        out.append(f"  canonical context (current program, eqns "
                   f"#{lo}-#{hi - 1}):")
        out += [f"    {i}: {fp.lines[i]}" for i in range(lo, hi)]
    return out


def _deltas(locked: Dict[str, Any], fp: LoweringFingerprint
            ) -> Dict[str, Any]:
    """Machine-readable mismatch summary for the --json payload."""
    old_stream = _chunks(locked.get("eqn_hashes", ""))
    new_stream = _chunks(fp.eqn_hashes)
    div = next((i for i, (a, b) in enumerate(zip(old_stream, new_stream))
                if a != b), None)
    if div is None and len(old_stream) != len(new_stream):
        div = min(len(old_stream), len(new_stream))
    locked_phases = locked.get("phases", {})
    return {
        "eqns": fp.eqns - int(locked.get("eqns", 0)),
        "collectives": fp.collectives - int(locked.get("collectives", 0)),
        "const_bytes": fp.const_bytes - int(locked.get("const_bytes", 0)),
        "consts_changed": locked.get("consts_digest") != fp.consts_digest,
        "first_divergence": div,
        "first_divergence_phase": (
            fp.line_phases[div]
            if div is not None and div < len(fp.line_phases) else None),
        "phases_added": sorted(set(fp.phases) - set(locked_phases)),
        "phases_removed": sorted(set(locked_phases) - set(fp.phases)),
        "phases_changed": sorted(
            ph for ph in set(fp.phases) & set(locked_phases)
            if locked_phases[ph].get("digest") != fp.phases[ph].digest),
    }


# ---------------------------------------------------------------------------
# JXA402 knob-inertness probes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KnobProbe:
    """One off-vs-unset comparison: the knob, its off value, and the
    two fingerprints the JXA402 rule compares."""

    knob: str
    off_value: object
    base: LoweringFingerprint
    off: LoweringFingerprint
    detail: str = ""


#: probe workload sides: big enough for a real neighbor grid / gravity
#: tree (the registry's tiny-but-nondegenerate convention)
_PROBE_SIDE = 6


@functools.lru_cache(maxsize=None)
def _probe_fp(prop_name: str, tuned_items: Tuple[Tuple[str, Any], ...]
              ) -> LoweringFingerprint:
    """Fingerprint of the step program a probe Simulation would launch.

    The fingerprinted callable is ``sim._step_fn(donated=
    sim._donate_active)`` — the EXACT launch routing, so a knob that
    silently re-routes the step (donate twins, a leaked blockdt branch)
    shows up even when the per-eqn bodies agree.
    """
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    case = "evrard" if prop_name == "nbody" else "sedov"
    state, box, const = make_initializer(case)(_PROBE_SIDE)
    tuned = dict(tuned_items)
    sim = Simulation(state, box, const, prop=prop_name,
                     tuned=tuned or None)
    if sim._blockdt:
        raise RuntimeError(
            "knob probe unexpectedly activated block time steps "
            f"(tuned={tuned!r}) — off sentinels must stay on the "
            "baseline path")
    cfg = sim._cfg
    fn = sim._step_fn(donated=sim._donate_active)
    if prop_name == "nbody":
        return fingerprint_callable(
            lambda s, b, g: fn(s, b, cfg, g),
            sim.state, sim.box, sim._gtree)
    return fingerprint_callable(
        lambda s, b: fn(s, b, cfg, None), sim.state, sim.box)


def production_knob_probes() -> List[KnobProbe]:
    """Off-vs-unset probes for every off-sentinel KnobSpec — the JXA402
    payload of the ``knob_inertness`` registry entry. Driven entirely by
    the tuning knob registry: a new knob declares ``off_sentinel=...``
    and is probed here with zero per-knob code. GravityConfig-owned
    knobs probe the nbody step (the std probe has no gravity stage to
    leak into); everything else probes the std step."""
    from sphexa_tpu.tuning.knobs import (
        off_sentinel_knobs,
        validate_off_sentinels,
    )

    # fail LOUDLY on a renamed resolution site before trusting any
    # probe result (the satellite-6 contract)
    validate_off_sentinels()
    probes: List[KnobProbe] = []
    for spec in off_sentinel_knobs():
        prop_name = "nbody" if spec.owner == "GravityConfig" else "std"
        base = _probe_fp(prop_name, ())
        off = _probe_fp(prop_name, ((spec.name, spec.off_sentinel),))
        probes.append(KnobProbe(
            knob=spec.name, off_value=spec.off_sentinel,
            base=base, off=off,
            detail=f"prop={prop_name} side={_PROBE_SIDE} "
                   f"tuned={{{spec.name}: {spec.off_sentinel!r}}} vs unset",
        ))
    return probes


# ---------------------------------------------------------------------------
# CLI: sphexa-audit lowering
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="sphexa-audit lowering",
        description="jaxdiff: verify every registered entry's canonical "
                    "lowering fingerprint against the committed "
                    "LOWERING_LOCK.json; mismatches exit 1 with a "
                    "phase-attributed structural diff. Re-lock an "
                    "intentional change with --write.",
    )
    ap.add_argument("targets", nargs="*", default=["sphexa_tpu"],
                    help="registry modules (default: the package registry)")
    ap.add_argument("--lock", default=DEFAULT_LOCK_PATH, metavar="FILE",
                    help=f"lock file (default: {DEFAULT_LOCK_PATH})")
    ap.add_argument("--write", action="store_true",
                    help="rewrite the lock from the current fingerprints "
                         "(merges over rows of entries not audited in "
                         "this run) and exit 0")
    ap.add_argument("--diff", action="store_true",
                    help="print canonical-eqn context around the first "
                         "divergence of each mismatching entry")
    ap.add_argument("--entries", metavar="NAMES",
                    help="comma-separated entry names (default: all; "
                         "staleness of lock rows is only checked on "
                         "full-registry runs)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable payload (per-entry "
                         "digest/deltas) instead of the text report")
    ap.add_argument("--cpu-devices", type=int,
                    default=int(os.environ.get("SPHEXA_AUDIT_DEVICES", "2")),
                    metavar="N",
                    help="bootstrap an N-virtual-device CPU backend so "
                         "sharded entries trace (default: "
                         "$SPHEXA_AUDIT_DEVICES or 2; 0 = ambient "
                         "backend). The committed lock is written at "
                         "--cpu-devices 8.")
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.cpu_devices and args.cpu_devices > 0:
        from sphexa_tpu.util.cpu_mesh import force_cpu_mesh

        try:
            force_cpu_mesh(args.cpu_devices)
        except RuntimeError as e:
            print(f"sphexa-audit lowering: note: CPU-mesh bootstrap "
                  f"skipped ({e})", file=sys.stderr)

    import dataclasses as _dc

    from sphexa_tpu.devtools.audit.cli import _load_target
    from sphexa_tpu.devtools.audit.core import (
        EntrySkip,
        EntryTrace,
        audit_context,
        entries_from_namespace,
        set_audit_context,
    )

    ctx = audit_context()
    if args.cpu_devices > 2:
        ctx = _dc.replace(ctx, mesh_size=args.cpu_devices)
    prev = set_audit_context(ctx)
    try:
        entries = []
        for target in args.targets:
            try:
                mod = _load_target(target)
            except (ImportError, OSError, SyntaxError) as e:
                print(f"sphexa-audit lowering: cannot load target "
                      f"{target!r}: {e}", file=sys.stderr)
                return 2
            entries += entries_from_namespace(vars(mod))
        filtered = bool(args.entries)
        if filtered:
            want = {s.strip() for s in args.entries.split(",") if s.strip()}
            unknown = want - {e.name for e in entries}
            if unknown:
                print(f"sphexa-audit lowering: unknown entry name(s): "
                      f"{sorted(unknown)}", file=sys.stderr)
                return 2
            entries = [e for e in entries if e.name in want]

        locked: Dict[str, Dict[str, Any]] = {}
        if not args.write or Path(args.lock).exists():
            try:
                locked = load_lock(args.lock)
            except LockError as e:
                if args.write and not Path(args.lock).exists():
                    locked = {}
                else:
                    print(f"sphexa-audit lowering: {e}", file=sys.stderr)
                    return 2

        current: Dict[str, LoweringFingerprint] = {}
        errors: List[str] = []
        skipped: List[str] = []
        for entry in entries:
            try:
                case = entry.build()
                current[entry.name] = lowering_fingerprint(
                    EntryTrace(entry, case))
            except EntrySkip as e:
                skipped.append(f"{entry.name}: {e}")
            except Exception as e:  # noqa: BLE001 - reported, exit 1
                errors.append(f"{entry.name}: {e.__class__.__name__}: {e}")

        if args.write:
            merged = dict(locked)
            for name, fp in current.items():
                merged[name] = fp.lock_payload()
            write_lock(args.lock, merged)
            print(f"sphexa-audit lowering: wrote {len(current)} "
                  f"fingerprint(s) to {args.lock} "
                  f"({len(merged)} total)")
            for note in skipped:
                print(f"sphexa-audit lowering: skipped {note}",
                      file=sys.stderr)
            return 1 if errors else 0

        mismatched: List[str] = []
        missing: List[str] = []
        stale: List[str] = []
        report: List[str] = []
        payload: List[Dict[str, Any]] = []
        for name, fp in current.items():
            row = locked.get(name)
            if row is None:
                missing.append(name)
                payload.append({"entry": name, "digest": fp.digest,
                                "locked_digest": None, "match": False,
                                "eqns": fp.eqns, "deltas": None})
                continue
            match = row.get("digest") == fp.digest
            payload.append({
                "entry": name, "digest": fp.digest,
                "locked_digest": row.get("digest"), "match": match,
                "eqns": fp.eqns, "collectives": fp.collectives,
                "const_bytes": fp.const_bytes,
                "deltas": None if match else _deltas(row, fp),
            })
            if not match:
                mismatched.append(name)
                report += structural_diff(name, row, fp,
                                          verbose=args.diff)
        if not filtered:
            audited = set(current) | {s.split(":", 1)[0] for s in skipped}
            stale = sorted(set(locked) - audited)

        bad = bool(mismatched or missing or stale or errors)
        if args.json:
            print(json.dumps({
                "tool": "jaxdiff",
                "lock": str(args.lock),
                "entries": payload,
                "mismatched": sorted(mismatched),
                "missing_from_lock": sorted(missing),
                "stale_lock_rows": stale,
                "errors": errors,
                "skipped": skipped,
            }, indent=2, sort_keys=True))
            return 1 if bad else 0

        for note in skipped:
            print(f"sphexa-audit lowering: skipped {note}", file=sys.stderr)
        for line in report:
            print(line)
        for name in missing:
            print(f"entry {name}: not in the lock (re-lock with --write)")
        for name in stale:
            print(f"lock row {name}: no such registry entry (stale — "
                  f"re-lock with --write)")
        for err in errors:
            print(f"entry error: {err}", file=sys.stderr)
        ok = len(current) - len(mismatched) - len(missing)
        print(f"sphexa-audit lowering: {ok}/{len(current)} entries match "
              f"{args.lock}"
              + (f"; {len(mismatched)} mismatched" if mismatched else "")
              + (f"; {len(missing)} unlocked" if missing else "")
              + (f"; {len(stale)} stale" if stale else "")
              + (f"; {len(errors)} errors" if errors else ""))
        return 1 if bad else 0
    finally:
        set_audit_context(prev)


if __name__ == "__main__":
    sys.exit(main())
