"""statecheck: symbolic state-schema lock + vmap-batchability report.

    sphexa-audit schema [targets] [--lock F] [--diff] [--write]
                        [--vmap] [--entries ...] [--json]

The sixth static-analysis layer (docs/STATIC_ANALYSIS.md): where jaxdiff
locks what each entry's program IS, statecheck locks what each entry's
program RETURNS — the carry/output schema the ensemble mode (ROADMAP
item 3) depends on. For every registered audit entry the output pytree
is flattened to per-leaf rows: path, dtype, weak_type, and each axis as
a linear polynomial in the particle count N, fitted exactly (rational
arithmetic, no tolerance) from the entry's existing two-point ``grow``
probe — the JXA204 byte-growth probe generalized to per-leaf symbolic
shapes. ``const`` axes don't scale, ``extensive`` axes are a·N,
``affine`` axes are a·N+b, and anything else (capacity-padded pow2
working sets, O(tree) arrays) stays ``data`` with both observed sizes.
The rows for the whole registry live in the committed
``STATE_SCHEMA.json``; drift exits 1 with a per-leaf structural diff and
is re-locked with ``--write`` after review.

``--vmap`` adds the JXA502 batchability report: each single-device
entry is traced under ``jax.vmap`` over a synthetic member axis and
every construct that breaks or degrades batching is reported as a
finding, not a crash — trace-time failures captured per entry, host
callbacks in the vmapped body, and batched ops falling back to
serialized while/scan loops. A non-batchable entry carries an explicit
inline waiver (``# jaxaudit: disable=JXA502 -- reason``) or fails the
gate: the ensemble mode's admission check is static.

jax-free at import (the lint layer's own hygiene rule); every expensive
artifact is cached on the shared ``EntryTrace``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from fractions import Fraction
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from sphexa_tpu.devtools.audit.spmd import slab_rows
from sphexa_tpu.devtools.primitives import HOST_CALLBACK_PRIMS, walk_eqns

__all__ = [
    "SCHEMA_VERSION",
    "DEFAULT_SCHEMA_PATH",
    "LockError",
    "entry_schema",
    "vmap_probe",
    "load_lock",
    "write_lock",
    "schema_diff",
    "format_axes",
    "main",
]

SCHEMA_VERSION = 1
DEFAULT_SCHEMA_PATH = "STATE_SCHEMA.json"

#: leaf-change rows rendered per entry in the text diff
_DIFF_LIMIT = 12


class LockError(ValueError):
    """Unreadable/corrupt/wrong-version schema lock (CLI exit 2)."""


# ---------------------------------------------------------------------------
# symbolic schema inference
# ---------------------------------------------------------------------------


def _fit_axes(dims1, dims2, n1: int, n2: int) -> List[Dict[str, Any]]:
    """Per-axis linear polynomial in N from the two probe points,
    fitted EXACTLY in rational arithmetic: d(N) = a·N + b with a, b
    recovered from (n1, d1), (n2, d2). No tolerance — an axis either
    is a polynomial in N or it is ``data`` (both observations kept)."""
    axes: List[Dict[str, Any]] = []
    for d1, d2 in zip(dims1, dims2):
        d1, d2 = int(d1), int(d2)
        if d1 == d2:
            axes.append({"kind": "const", "dim": d1})
            continue
        a = Fraction(d2 - d1, n2 - n1)
        b = Fraction(d1) - a * n1
        if b == 0:
            axes.append({"kind": "extensive", "per_n": str(a)})
        elif b.denominator == 1 and a > 0:
            axes.append({"kind": "affine", "per_n": str(a),
                         "offset": int(b)})
        else:
            axes.append({"kind": "data", "observed": [d1, d2]})
    return axes


def format_axes(axes) -> str:
    """Human form of a shape row: ``f32[N, 3]``-style axis list."""
    parts = []
    for ax in axes:
        kind = ax.get("kind")
        if kind == "const":
            parts.append(str(ax["dim"]))
        elif kind == "extensive":
            a = ax["per_n"]
            parts.append("N" if a == "1" else f"{a}N")
        elif kind == "affine":
            off = int(ax["offset"])
            a = ax["per_n"]
            head = "N" if a == "1" else f"{a}N"
            parts.append(f"{head}{off:+d}")
        else:
            lo, hi = ax.get("observed", ["?", "?"])
            parts.append(f"data({lo}..{hi})")
    return "[" + ", ".join(parts) + "]"


def _fmt_leaf(leaf: Dict[str, Any]) -> str:
    return (f"{leaf.get('dtype')}{format_axes(leaf.get('shape', []))}"
            + (" weak" if leaf.get("weak_type") else ""))


def _flat_leaves(trace) -> List[Tuple[str, Any, bool]]:
    """[(path, ShapeDtypeStruct, weak_type)] over the entry's output
    pytree — the out_shape tree and the jaxpr's out_avals share one
    trace and one flatten order, so weak_type zips on exactly."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(trace.out_shape)[0]
    avals = trace.closed_jaxpr.out_avals
    return [
        (jax.tree_util.keystr(path), leaf,
         bool(getattr(aval, "weak_type", False)))
        for (path, leaf), aval in zip(leaves, avals)
    ]


def entry_schema(trace) -> Dict[str, Any]:
    """Cached symbolic output schema of one entry (the lock row): pytree
    paths, dtype, weak_type, and each axis as a polynomial in N. Shares
    the EntryTrace's single ``return_shape`` trace; the grown probe is
    traced once and only for entries that declare ``case.grow``."""
    cached = getattr(trace, "_schema", None)
    if cached is not None:
        return cached
    from sphexa_tpu.devtools.audit.core import EntryTrace, audit_context

    base = _flat_leaves(trace)
    n1 = slab_rows(trace.closed_jaxpr.jaxpr)
    row: Dict[str, Any] = {
        "mesh": audit_context().mesh_size,
        "n_base": n1 or None,
        "grow": None,
        "leaves": {},
    }
    grown = None
    n2 = 0
    if trace.case.grow is not None and n1:
        grown_case, _ratio = trace.case.grow()
        gtrace = EntryTrace(trace.entry, grown_case)
        grown = _flat_leaves(gtrace)
        n2 = slab_rows(gtrace.closed_jaxpr.jaxpr)
        if len(grown) != len(base) or n2 == n1:
            raise ValueError(
                f"entry {trace.entry.name}: grow probe changed the output "
                f"STRUCTURE ({len(base)} -> {len(grown)} leaves at "
                f"N {n1} -> {n2}) — the schema is not well-defined")
        row["grow"] = str(Fraction(n2, n1))
    for i, (path, leaf, weak) in enumerate(base):
        if grown is not None:
            gpath, gleaf, _gw = grown[i]
            if gpath != path or len(gleaf.shape) != len(leaf.shape):
                raise ValueError(
                    f"entry {trace.entry.name}: leaf {path} changed "
                    f"path/rank across the grow probe")
            axes = _fit_axes(leaf.shape, gleaf.shape, n1, n2)
        else:
            axes = [{"kind": "const", "dim": int(d)} for d in leaf.shape]
        row["leaves"][path] = {
            "dtype": str(leaf.dtype),
            "weak_type": weak,
            "shape": axes,
        }
    trace._schema = row
    return row


# ---------------------------------------------------------------------------
# vmap-batchability probe (JXA502's shared analysis)
# ---------------------------------------------------------------------------


def _loop_count(closed) -> int:
    return sum(
        1 for eqn in walk_eqns(closed.jaxpr)
        if eqn.primitive.name in ("while", "scan")
    )


def vmap_probe(trace, members: int) -> Dict[str, Any]:
    """Trace the entry under ``jax.vmap`` over a leading member axis of
    width ``members`` (abstract args — no member batch is materialized)
    and report what happens to batching. Cached per EntryTrace."""
    cached = getattr(trace, "_vmap", None)
    if cached is not None and cached.get("members") == members:
        return cached
    import jax
    from jax.api_util import shaped_abstractify

    def member_struct(leaf):
        aval = shaped_abstractify(leaf)
        return jax.ShapeDtypeStruct((members,) + tuple(aval.shape),
                                    aval.dtype)

    report: Dict[str, Any] = {
        "members": members,
        "error": None,
        "callbacks": [],
        "base_loops": _loop_count(trace.closed_jaxpr),
        "vmap_loops": 0,
    }
    batched_args = jax.tree.map(member_struct, trace.case.args)
    try:
        with trace._x64_scope():
            closed = jax.make_jaxpr(jax.vmap(trace.case.fn))(*batched_args)
    except Exception as e:  # noqa: BLE001 - captured as a finding
        report["error"] = f"{e.__class__.__name__}: {e}"
        trace._vmap = report
        return report
    callbacks: Dict[str, int] = {}
    for eqn in walk_eqns(closed.jaxpr):
        if eqn.primitive.name in HOST_CALLBACK_PRIMS:
            callbacks[eqn.primitive.name] = \
                callbacks.get(eqn.primitive.name, 0) + 1
    report["callbacks"] = sorted(callbacks.items())
    report["vmap_loops"] = _loop_count(closed)
    trace._vmap = report
    return report


# ---------------------------------------------------------------------------
# lock IO (the lowerdiff contract: version, corrupt -> LockError -> exit 2)
# ---------------------------------------------------------------------------


def load_lock(path) -> Dict[str, Dict[str, Any]]:
    p = Path(path)
    try:
        payload = json.loads(p.read_text())
    except OSError as e:
        raise LockError(f"cannot read schema lock {p}: {e}") from e
    except json.JSONDecodeError as e:
        raise LockError(f"corrupt schema lock {p}: {e}") from e
    if not isinstance(payload, dict) or "entries" not in payload:
        raise LockError(f"corrupt schema lock {p}: no 'entries' object")
    if payload.get("version") != SCHEMA_VERSION:
        raise LockError(
            f"schema lock {p} has version {payload.get('version')!r}, this "
            f"tool writes {SCHEMA_VERSION} (regenerate with --write)")
    return payload["entries"]


def write_lock(path, entries: Dict[str, Dict[str, Any]]) -> None:
    p = Path(path)
    payload = {
        "version": SCHEMA_VERSION,
        "tool": "statecheck",
        "comment": "symbolic carry/output schema per audit entry (axis "
                   "polynomials in N from the two-point grow probe); "
                   "regenerate with: sphexa-audit schema --write",
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")


# ---------------------------------------------------------------------------
# structural diff
# ---------------------------------------------------------------------------


def schema_diff(name: str, locked: Dict[str, Any], current: Dict[str, Any],
                verbose: bool = False) -> List[str]:
    """Reviewable per-leaf diff of a drifted schema row — the PR
    artifact, so a relock is reviewed as added/removed/changed leaves,
    never as an opaque digest flip."""
    lines = [f"entry {name}: state schema drifted vs lock"]
    lo = locked.get("leaves", {})
    cu = current.get("leaves", {})
    added = sorted(set(cu) - set(lo))
    removed = sorted(set(lo) - set(cu))
    changed = sorted(p for p in set(lo) & set(cu) if lo[p] != cu[p])
    for meta in ("mesh", "n_base", "grow"):
        if locked.get(meta) != current.get(meta):
            lines.append(f"  {meta}: {locked.get(meta)} -> "
                         f"{current.get(meta)}")
    limit = len(added) + len(removed) + len(changed) if verbose \
        else _DIFF_LIMIT
    rows = ([("+", p, None, cu[p]) for p in added]
            + [("-", p, lo[p], None) for p in removed]
            + [("~", p, lo[p], cu[p]) for p in changed])
    for mark, p, old, new in rows[:limit]:
        if mark == "+":
            lines.append(f"  + {p}: {_fmt_leaf(new)}")
        elif mark == "-":
            lines.append(f"  - {p}: {_fmt_leaf(old)}")
        else:
            lines.append(f"  ~ {p}: {_fmt_leaf(old)} -> {_fmt_leaf(new)}")
    if len(rows) > limit:
        lines.append(f"  ... {len(rows) - limit} more leaf change(s) "
                     f"(--diff for all)")
    lines.append(f"  summary: +{len(added)} -{len(removed)} ~{len(changed)} "
                 f"leaves (locked {len(lo)}, current {len(cu)})")
    return lines


def _delta_summary(locked: Dict[str, Any], current: Dict[str, Any]
                   ) -> Dict[str, Any]:
    lo = locked.get("leaves", {})
    cu = current.get("leaves", {})
    return {
        "added": sorted(set(cu) - set(lo)),
        "removed": sorted(set(lo) - set(cu)),
        "changed": sorted(p for p in set(lo) & set(cu) if lo[p] != cu[p]),
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="sphexa-audit schema",
        description="statecheck: verify every registered entry's symbolic "
                    "carry/output schema (pytree paths, dtype, weak_type, "
                    "axis polynomials in N) against the committed "
                    "STATE_SCHEMA.json; mismatches exit 1 with a per-leaf "
                    "structural diff. Re-lock an intentional change with "
                    "--write. --vmap adds the JXA502 member-axis "
                    "batchability report.",
    )
    ap.add_argument("targets", nargs="*", default=["sphexa_tpu"],
                    help="registry modules (default: the package registry)")
    ap.add_argument("--lock", default=DEFAULT_SCHEMA_PATH, metavar="FILE",
                    help=f"schema lock file (default: {DEFAULT_SCHEMA_PATH})")
    ap.add_argument("--write", action="store_true",
                    help="rewrite the lock from the current schemas (merges "
                         "over rows of entries not audited in this run) "
                         "and exit 0")
    ap.add_argument("--diff", action="store_true",
                    help="print EVERY leaf change of each drifted entry "
                         "(default: first %d)" % _DIFF_LIMIT)
    ap.add_argument("--vmap", action="store_true",
                    help="also trace each single-device entry under "
                         "jax.vmap over a member axis and report "
                         "batchability breaks as JXA502 findings")
    ap.add_argument("--members", type=int, default=2, metavar="M",
                    help="member-axis width for --vmap (default: 2)")
    ap.add_argument("--entries", metavar="NAMES",
                    help="comma-separated entry names (default: all; "
                         "staleness of lock rows is only checked on "
                         "full-registry runs)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable payload instead of "
                         "the text report")
    ap.add_argument("--cpu-devices", type=int,
                    default=int(os.environ.get("SPHEXA_AUDIT_DEVICES", "2")),
                    metavar="N",
                    help="bootstrap an N-virtual-device CPU backend so "
                         "sharded entries trace (default: "
                         "$SPHEXA_AUDIT_DEVICES or 2; 0 = ambient "
                         "backend). The committed lock is written at "
                         "the default mesh.")
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.cpu_devices and args.cpu_devices > 0:
        from sphexa_tpu.util.cpu_mesh import force_cpu_mesh

        try:
            force_cpu_mesh(args.cpu_devices)
        except RuntimeError as e:
            print(f"sphexa-audit schema: note: CPU-mesh bootstrap "
                  f"skipped ({e})", file=sys.stderr)

    import dataclasses as _dc

    from sphexa_tpu.devtools.audit.cli import _load_target
    from sphexa_tpu.devtools.audit.core import (
        Auditor,
        EntrySkip,
        EntryTrace,
        audit_context,
        entries_from_namespace,
        set_audit_context,
    )

    ctx = audit_context()
    if args.cpu_devices > 2:
        ctx = _dc.replace(ctx, mesh_size=args.cpu_devices)
    if args.vmap:
        ctx = _dc.replace(ctx, vmap_members=max(args.members, 1))
    ctx = _dc.replace(ctx, state_schema_path=args.lock)
    prev = set_audit_context(ctx)
    try:
        entries = []
        for target in args.targets:
            try:
                mod = _load_target(target)
            except (ImportError, OSError, SyntaxError) as e:
                print(f"sphexa-audit schema: cannot load target "
                      f"{target!r}: {e}", file=sys.stderr)
                return 2
            entries += entries_from_namespace(vars(mod))
        filtered = bool(args.entries)
        if filtered:
            want = {s.strip() for s in args.entries.split(",") if s.strip()}
            unknown = want - {e.name for e in entries}
            if unknown:
                print(f"sphexa-audit schema: unknown entry name(s): "
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
                    print(f"sphexa-audit schema: {e}", file=sys.stderr)
                    return 2

        # the carry-closure and (under --vmap) batchability rules run on
        # the SAME traces as the schema rows; JXA501 itself is the lock
        # compare below, so it is not re-run here
        select = ["JXA503"] + (["JXA502"] if args.vmap else [])
        auditor = Auditor(select=select)

        current: Dict[str, Dict[str, Any]] = {}
        findings: List[Any] = []
        suppressed: List[Any] = []
        vmap_reports: Dict[str, Any] = {}
        errors: List[str] = []
        skipped: List[str] = []
        for entry in entries:
            try:
                case = entry.build()
            except EntrySkip as e:
                skipped.append(f"{entry.name}: {e}")
                continue
            except Exception as e:  # noqa: BLE001 - reported, exit 1
                errors.append(f"{entry.name}: {e.__class__.__name__}: {e}")
                continue
            trace = EntryTrace(entry, case)
            try:
                current[entry.name] = entry_schema(trace)
            except Exception as e:  # noqa: BLE001 - reported, exit 1
                errors.append(f"{entry.name}: {e.__class__.__name__}: {e}")
                continue
            table = auditor._suppression_table(entry.path)
            for rule in auditor.rules.values():
                try:
                    found = rule.check(trace)
                except Exception as e:  # noqa: BLE001 - reported, exit 1
                    errors.append(f"{entry.name}: {rule.id} crashed: "
                                  f"{e.__class__.__name__}: {e}")
                    continue
                for f in found:
                    (suppressed if table.is_suppressed(f.rule, f.line)
                     else findings).append(f)
            if args.vmap and not entry.mesh_axes:
                vmap_reports[entry.name] = vmap_probe(
                    trace, max(args.members, 1))

        if args.write:
            merged = dict(locked)
            merged.update(current)
            write_lock(args.lock, merged)
            print(f"sphexa-audit schema: wrote {len(current)} schema "
                  f"row(s) to {args.lock} ({len(merged)} total)")
            for note in skipped:
                print(f"sphexa-audit schema: skipped {note}",
                      file=sys.stderr)
            return 1 if errors else 0

        mismatched: List[str] = []
        missing: List[str] = []
        stale: List[str] = []
        mesh_skipped: List[str] = []
        report: List[str] = []
        payload: List[Dict[str, Any]] = []
        for name, row in current.items():
            lrow = locked.get(name)
            if lrow is None:
                missing.append(name)
                payload.append({"entry": name, "match": False,
                                "locked": False, "deltas": None})
                continue
            if lrow.get("mesh") != row.get("mesh"):
                # a row locked at another mesh size is neither stale nor
                # drifted — sharded shapes legitimately depend on P
                mesh_skipped.append(
                    f"{name}: locked at mesh={lrow.get('mesh')}, "
                    f"running mesh={row.get('mesh')}")
                payload.append({"entry": name, "match": None,
                                "locked": True, "deltas": None})
                continue
            match = lrow == row
            payload.append({
                "entry": name, "match": match, "locked": True,
                "leaves": len(row.get("leaves", {})),
                "deltas": None if match else _delta_summary(lrow, row),
            })
            if not match:
                mismatched.append(name)
                report += schema_diff(name, lrow, row, verbose=args.diff)
        if not filtered:
            audited = set(current) | {s.split(":", 1)[0] for s in skipped}
            stale = sorted(set(locked) - audited)

        bad = bool(mismatched or missing or stale or errors or findings)
        if args.json:
            print(json.dumps({
                "tool": "statecheck",
                "lock": str(args.lock),
                "entries": payload,
                "mismatched": sorted(mismatched),
                "missing_from_lock": sorted(missing),
                "stale_lock_rows": stale,
                "mesh_skipped": mesh_skipped,
                "findings": [f.to_json() for f in findings],
                "suppressed": [f.to_json() for f in suppressed],
                "vmap": vmap_reports,
                "errors": errors,
                "skipped": skipped,
            }, indent=2, sort_keys=True))
            return 1 if bad else 0

        for note in skipped:
            print(f"sphexa-audit schema: skipped {note}", file=sys.stderr)
        for note in mesh_skipped:
            print(f"sphexa-audit schema: mesh-skipped {note}",
                  file=sys.stderr)
        for line in report:
            print(line)
        for name in missing:
            print(f"entry {name}: not in the schema lock (re-lock with "
                  f"--write)")
        for name in stale:
            print(f"lock row {name}: no such registry entry (stale — "
                  f"re-lock with --write)")
        for f in findings:
            print(f.format())
        for err in errors:
            print(f"entry error: {err}", file=sys.stderr)
        if args.vmap:
            clean = sorted(n for n, r in vmap_reports.items()
                           if not r["error"] and not r["callbacks"]
                           and r["vmap_loops"] <= r["base_loops"])
            print(f"vmap report: {len(clean)}/{len(vmap_reports)} "
                  f"single-device entries batch clean over "
                  f"{max(args.members, 1)} members")
        ok = len(current) - len(mismatched) - len(missing) \
            - len(mesh_skipped)
        print(f"sphexa-audit schema: {ok}/{len(current)} entries match "
              f"{args.lock}"
              + (f"; {len(mismatched)} drifted" if mismatched else "")
              + (f"; {len(missing)} unlocked" if missing else "")
              + (f"; {len(stale)} stale" if stale else "")
              + (f"; {len(findings)} finding(s)" if findings else "")
              + (f"; {len(suppressed)} suppressed" if suppressed else "")
              + (f"; {len(errors)} errors" if errors else ""))
        return 1 if bad else 0
    finally:
        set_audit_context(prev)


if __name__ == "__main__":
    sys.exit(main())
