"""Offline per-phase attribution of a ``jax.profiler`` trace capture.

A ``--trace-dir`` capture used to be an anonymous wall of ``fusion.N``
ops nobody could attribute to tree-build vs neighbors vs force vs
gravity vs exchange. The step programs now wrap every major stage in
``jax.named_scope("sphexa/<phase>")`` (propagator.py, gravity/, sph/,
parallel/exchange.py — the taxonomy lives in util/phases.py and
docs/OBSERVABILITY.md), so XLA op *metadata* carries the phase. This
module turns a finished capture back into the per-phase device-time
table the reference lineage's optimization story is written in (the
Bédorf et al. 2014 per-phase breakdowns; SPH-EXA's own ``Timer``).

A capture session holds two artifacts:

- ``*.xplane.pb`` — the xprof XSpace proto: per-op execution events
  (``hlo_op``/``hlo_module`` stats + picosecond durations) AND the
  serialized HLO modules whose instruction metadata carries the
  ``op_name`` scope path (``jit(step)/.../sphexa/density/...``). This
  is the PRIMARY source: it is complete.
- ``*.trace.json.gz`` — the perfetto dump of the same events, capped
  (~1M events; a python-tracer-heavy capture floods the cap and drops
  the device ops). Used as a FALLBACK when no xplane sidecar exists.

Both are read with a ~80-line generic protobuf wire-format walker — no
tensorflow/xprof dependency, so attribution of a chip capture runs
anywhere: this CPU container today, the chip host the day it arrives
(``sphexa-telemetry trace <dir>``). Deliberately jax-free
(telemetry/cli.py contract).
"""

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

#: phase extraction from an op_name metadata path: the FIRST
#: ``sphexa/<phase>`` segment (in-repo scopes nest specific-inside-
#: coarse only where both name the same stage family, so first wins)
PHASE_RE = re.compile(r"sphexa/([A-Za-z0-9_.:+-]+)")

#: trace-event args fields that may carry a scope path directly (TPU
#: device planes export these; the CPU runtime only exports hlo_op)
_SCOPE_ARGS = ("long_name", "tf_op", "op_name")


class TraceError(Exception):
    """Unreadable/absent capture (CLI exit code 2)."""


# ---------------------------------------------------------------------------
# protobuf wire-format primitives (no schema compile)
# ---------------------------------------------------------------------------


def _varint(data: bytes, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = data[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(data: bytes, start: int, end: int):
    """One message body as (field, wire, varint|span) records; raises
    ValueError/IndexError on non-message bytes (callers probe-and-skip)."""
    i = start
    out = []
    while i < end:
        key, i = _varint(data, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(data, i)
            out.append((f, 0, v))
        elif wire == 1:
            out.append((f, 1, (i, i + 8)))
            i += 8
        elif wire == 5:
            out.append((f, 5, (i, i + 4)))
            i += 4
        elif wire == 2:
            ln, i = _varint(data, i)
            if i + ln > end:
                raise ValueError("length-delimited field overruns message")
            out.append((f, 2, (i, i + ln)))
            i += ln
        else:
            raise ValueError(f"unsupported wire type {wire}")
    return out


def _ascii(data: bytes, span) -> Optional[str]:
    try:
        s = data[span[0]:span[1]].decode()
    except UnicodeDecodeError:
        return None
    return s if s and all(32 <= ord(c) < 127 for c in s) else None


def _map_entry(data: bytes, span):
    """(key:int, value_span) of one proto map<int64, Msg> entry."""
    k, vspan = None, None
    for f, w, v in _fields(data, *span):
        if f == 1 and w == 0:
            k = v
        elif f == 2 and w == 2:
            vspan = v
    return k, vspan


# ---------------------------------------------------------------------------
# HLO instruction metadata: {instr_name -> op_name scope path}
# ---------------------------------------------------------------------------


def _instr_record(data: bytes, fields) -> Optional[dict]:
    """{name, op_name?, called} when this message walks like an
    xla.HloInstructionProto: name (f1) + opcode (f2, a short slash-free
    token — the discriminator against xla.OpMetadata, whose f2 op_name
    is a scope path), optional metadata.op_name (f7.f2) and
    called_computation_ids (f38, bare or packed varints)."""
    f1 = [s for f, w, s in fields if f == 1 and w == 2]
    f2 = [s for f, w, s in fields if f == 2 and w == 2]
    if not f1 or not f2:
        return None
    name = _ascii(data, f1[0])
    opcode = _ascii(data, f2[0])
    if (name is None or opcode is None or len(opcode) > 24
            or "/" in opcode or "(" in opcode):
        return None
    rec = {"name": name, "op_name": None, "called": []}
    for f, w, v in fields:
        if f == 7 and w == 2:  # metadata: xla.OpMetadata
            try:
                meta = _fields(data, *v)
            except (ValueError, IndexError):
                continue
            op = [s for mf, mw, s in meta if mf == 2 and mw == 2]
            if op:
                rec["op_name"] = _ascii(data, op[0])
        elif f == 38 and w == 0:  # called_computation_ids, bare
            rec["called"].append(v)
        elif f == 38 and w == 2:  # packed
            i = v[0]
            try:
                while i < v[1]:
                    cid, i = _varint(data, i)
                    rec["called"].append(cid)
            except IndexError:
                pass
    return rec


def _scan_hlo(data: bytes, start: int, end: int, instrs: List[dict],
              comps: Dict[int, List[dict]]):
    """Recursively harvest HLO instruction records AND group them by
    their enclosing computation (HloComputationProto: instrs in f2,
    computation id in f5) — the blobs embed whole serialized modules."""
    try:
        fields = _fields(data, start, end)
    except (ValueError, IndexError):
        return
    # computation-shaped message? its f2 children parse as instrs
    children = []
    comp_id = next((v for f, w, v in fields if f == 5 and w == 0), None)
    for f, w, span in fields:
        if f == 2 and w == 2 and span[1] - span[0] > 8:
            try:
                rec = _instr_record(data, _fields(data, *span))
            except (ValueError, IndexError):
                rec = None
            if rec is not None:
                children.append(rec)
    if children:
        instrs.extend(children)
        if comp_id is not None:
            comps.setdefault(comp_id, []).extend(children)
    for f, w, span in fields:
        if w == 2 and span[1] - span[0] > 8 and not (f == 2 and children):
            _scan_hlo(data, span[0], span[1], instrs, comps)


def _resolve_scopes(instrs: List[dict],
                    comps: Dict[int, List[dict]]) -> Dict[str, str]:
    """{instr_name: op_name}: own metadata first; instructions the
    optimizer rebuilt WITHOUT metadata (cumsum -> reduce-window, late
    rewrites) inherit the first attributed op of a computation they
    call — the reduction/comparator subcomputation keeps the original
    scope path when the calling op loses it."""
    comp_scope: Dict[int, Optional[str]] = {}
    for cid, recs in comps.items():
        comp_scope[cid] = next(
            (r["op_name"] for r in recs if r["op_name"]), None)
    out: Dict[str, str] = {}
    for r in instrs:
        op_name = r["op_name"]
        if not op_name:
            op_name = next(
                (comp_scope.get(c) for c in r["called"]
                 if comp_scope.get(c)), None)
        if op_name:
            out[r["name"]] = op_name
    return out


# ---------------------------------------------------------------------------
# xplane.pb: op events + scope maps in one pass
# ---------------------------------------------------------------------------


def parse_xplane(path: str) -> Tuple[Dict[str, Dict[str, str]], List[dict]]:
    """(scope_maps, op_events) from one XSpace proto.

    scope_maps: {module_name: {instr_name: op_name}} harvested from the
    embedded HLO modules (metadata-plane entries named
    ``<module>(<program_id>)``; ``""`` holds the merged fallback).
    op_events: [{op, module, dur_us}] — every XEvent carrying an
    ``hlo_op`` stat (op/module are interned stat-metadata refs; the
    xprof trace viewer renders these same events as the perfetto
    dump's device-op rows)."""
    with open(path, "rb") as f:
        data = f.read()
    maps: Dict[str, Dict[str, str]] = defaultdict(dict)
    events: List[dict] = []
    try:
        top = _fields(data, 0, len(data))
    except (ValueError, IndexError):
        raise TraceError(f"{path}: not an xplane proto")
    for f, w, span in top:
        if f != 1 or w != 2:  # XSpace.planes
            continue
        try:
            plane = _fields(data, *span)
        except (ValueError, IndexError):
            continue
        # pass 1: this plane's interned metadata tables
        stat_names: Dict[int, str] = {}   # XStatMetadata id -> name
        for pf, pw, pspan in plane:
            if pw != 2 or pf not in (4, 5):
                continue
            try:
                k, vspan = _map_entry(data, pspan)
            except (ValueError, IndexError):
                continue
            if vspan is None:
                continue
            try:
                md = _fields(data, *vspan)
            except (ValueError, IndexError):
                continue
            names = [_ascii(data, s) for f2, w2, s in md
                     if f2 == 2 and w2 == 2]
            name = names[0] if names and names[0] else ""
            kid = k
            if kid is None:  # id also lives in the metadata msg (field 1)
                ids = [v for f2, w2, v in md if f2 == 1 and w2 == 0]
                kid = ids[0] if ids else None
            if pf == 5:
                if kid is not None:
                    stat_names[kid] = name
            else:
                # module entries ("<module>(<id>)") embed the HLO proto:
                # harvest instruction op_name metadata (+ computation
                # inheritance for optimizer-rebuilt metadata-less ops)
                m = re.match(r"(.+)\((\d+)\)$", name)
                instrs: List[dict] = []
                comps: Dict[int, List[dict]] = {}
                _scan_hlo(data, vspan[0], vspan[1], instrs, comps)
                found = _resolve_scopes(instrs, comps)
                if found:
                    module = m.group(1) if m else ""
                    maps[module].update(found)
                    if module:
                        maps[""].update(found)
        if not stat_names:
            continue
        hlo_op_ids = {i for i, n in stat_names.items() if n == "hlo_op"}
        hlo_mod_ids = {i for i, n in stat_names.items()
                       if n == "hlo_module"}
        if not hlo_op_ids:
            continue
        # pass 2: line events with an hlo_op stat = device-op samples
        for pf, pw, pspan in plane:
            if pf != 3 or pw != 2:  # XPlane.lines
                continue
            try:
                line = _fields(data, *pspan)
            except (ValueError, IndexError):
                continue
            for lf, lw, lspan in line:
                if lf != 4 or lw != 2:  # XLine.events
                    continue
                try:
                    ev = _fields(data, *lspan)
                except (ValueError, IndexError):
                    continue
                dur_ps = 0
                op = module = None
                for ef, ew, v in ev:
                    if ef == 3 and ew == 0:
                        dur_ps = v
                    elif ef == 4 and ew == 2:  # XEvent.stats
                        try:
                            st = _fields(data, *v)
                        except (ValueError, IndexError):
                            continue
                        smid = next((sv for sf, sw, sv in st
                                     if sf == 1 and sw == 0), None)
                        ref = next((sv for sf, sw, sv in st
                                    if sf == 7 and sw == 0), None)
                        if smid in hlo_op_ids and ref is not None:
                            op = stat_names.get(ref)
                        elif smid in hlo_mod_ids and ref is not None:
                            module = stat_names.get(ref)
                if op:
                    # events WITHOUT an hlo_op stat are host TraceMe
                    # spans — not device time, skipped
                    events.append({
                        "op": op,
                        "module": module or "",
                        "dur_us": dur_ps / 1e6,
                    })
    return dict(maps), events


# ---------------------------------------------------------------------------
# trace.json.gz fallback (no xplane sidecar in the dir)
# ---------------------------------------------------------------------------


def load_op_events(trace_json_path: str) -> List[dict]:
    """Device-op execution samples of one perfetto dump:
    {op, module, dur_us, scope?} per complete ("X") event that names an
    HLO op. NOTE the dump is event-capped upstream (~1M) — a
    python-tracer-heavy capture can flood device ops out of it, which
    is why the xplane is the primary source."""
    try:
        with gzip.open(trace_json_path, "rt") as f:
            trace = json.load(f)
    except (OSError, json.JSONDecodeError, EOFError) as e:
        raise TraceError(f"{trace_json_path}: unreadable trace ({e})")
    out = []
    for e in trace.get("traceEvents", []):
        if e.get("ph") != "X" or not isinstance(e.get("dur"), (int, float)):
            continue
        args = e.get("args") or {}
        op = args.get("hlo_op")
        if not op:
            continue
        ev = {"op": str(op), "module": str(args.get("hlo_module", "")),
              "dur_us": float(e["dur"])}
        for k in _SCOPE_ARGS:  # TPU planes may carry the path directly
            v = args.get(k)
            if isinstance(v, str) and "sphexa/" in v:
                ev["scope"] = v
                break
        out.append(ev)
    return out


def find_capture(trace_dir: str) -> Tuple[List[str], List[str]]:
    """(xplane_paths, trace_json_paths) under a --trace-dir; newest
    capture session only (a dir can hold several timestamped sessions).
    Bare dirs with the files dropped in directly (the committed test
    fixture's shape) work too."""
    sessions = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*")))
    roots = sessions[-1:] if sessions else [trace_dir]
    xplanes: List[str] = []
    traces: List[str] = []
    for root in roots:
        xplanes += sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                                    recursive=True))
        traces += sorted(glob.glob(os.path.join(root, "**",
                                                "*.trace.json.gz"),
                                   recursive=True))
    if not xplanes and not traces:
        raise TraceError(f"no *.xplane.pb / *.trace.json.gz under "
                         f"{trace_dir} — was the run started with "
                         f"--trace-dir?")
    return xplanes, traces


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


def _phase_of(op_name: Optional[str]) -> Optional[str]:
    if not op_name:
        return None
    m = PHASE_RE.search(op_name)
    return m.group(1) if m else None


def _base(op: str) -> str:
    """'reduce-window.47' -> 'reduce-window' (the CPU runtime sometimes
    reports a thunk under the suffixless base name)."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def _base_phases(m: Dict[str, str]) -> Dict[str, Optional[str]]:
    """base op name -> phase, ONLY where every instr sharing the base
    agrees (an ambiguous base attributes nothing rather than guessing)."""
    out: Dict[str, Optional[str]] = {}
    for name, op_name in m.items():
        b = _base(name)
        p = _phase_of(op_name)
        if b in out and out[b] != p:
            out[b] = None
        else:
            out[b] = p
    return out


def summarize_trace(trace_dir: str, top: int = 8) -> Dict:
    """Aggregate one capture into the per-phase attribution summary.

    ``coverage`` = attributed device-op time / total device-op time —
    the acceptance number the chip-harvest gate pins (>= 0.8 on a
    5-step Sedov capture, scripts/check.sh)."""
    xplanes, traces = find_capture(trace_dir)
    maps: Dict[str, Dict[str, str]] = {}
    all_events: List[dict] = []
    for xp in xplanes:
        try:
            m, evs = parse_xplane(xp)
        except TraceError:
            continue  # a corrupt sidecar degrades to the json fallback
        for module, mm in m.items():
            maps.setdefault(module, {}).update(mm)
        all_events.extend(evs)
    if not all_events:
        for tp in traces:
            all_events.extend(load_op_events(tp))
    fallback = maps.get("", {})
    base_maps = {mod: _base_phases(m) for mod, m in maps.items()}

    phase_us: Dict[str, float] = defaultdict(float)
    phase_events: Dict[str, int] = defaultdict(int)
    phase_ops: Dict[str, set] = defaultdict(set)
    unattr_us: Dict[Tuple[str, str], float] = defaultdict(float)
    module_us: Dict[str, float] = defaultdict(float)
    total_us = 0.0
    for ev in all_events:
        total_us += ev["dur_us"]
        module_us[ev["module"]] += ev["dur_us"]
        scope = ev.get("scope")
        if scope is None:
            mod_map = maps.get(ev["module"], fallback)
            scope = mod_map.get(ev["op"]) or fallback.get(ev["op"])
        phase = _phase_of(scope)
        if phase is None and ev["op"] not in maps.get(ev["module"], {}):
            # suffixless thunk name: attribute via the base name when
            # every same-base instruction of the module agrees
            phase = base_maps.get(ev["module"], {}).get(_base(ev["op"]))
        if phase is None:
            unattr_us[(ev["module"], ev["op"])] += ev["dur_us"]
            continue
        phase_us[phase] += ev["dur_us"]
        phase_events[phase] += 1
        phase_ops[phase].add(ev["op"])
    attributed = sum(phase_us.values())
    phases = [
        {"phase": p, "us": round(us, 3),
         "share": us / total_us if total_us else 0.0,
         "ops": len(phase_ops[p]), "events": phase_events[p]}
        for p, us in sorted(phase_us.items(), key=lambda kv: -kv[1])
    ]
    unattributed = [
        {"module": m, "op": op, "us": round(us, 3),
         "share": us / total_us if total_us else 0.0}
        for (m, op), us in sorted(unattr_us.items(),
                                  key=lambda kv: -kv[1])[:top]
    ]
    return {
        "trace_dir": trace_dir,
        "xplane_files": [os.path.basename(x) for x in xplanes],
        "trace_files": [os.path.basename(t) for t in traces],
        "device_op_events": len(all_events),
        "total_device_us": round(total_us, 3),
        "attributed_us": round(attributed, 3),
        "coverage": attributed / total_us if total_us else 0.0,
        "phases": phases,
        "modules": {m: round(us, 3) for m, us in sorted(
            module_us.items(), key=lambda kv: -kv[1])},
        "unattributed_top": unattributed,
    }


def phase_attr_digest(summary: Dict) -> Dict:
    """The compact per-capture digest persisted into the run record:
    the app's ``phase_attr`` event payload."""
    return {
        "phases": {p["phase"]: round(p["us"], 1)
                   for p in summary["phases"]},
        "coverage": round(summary["coverage"], 4),
        "total_device_us": summary["total_device_us"],
    }


def render_trace(s: Dict) -> str:
    from sphexa_tpu.devtools.common import render_table

    lines = [f"trace: {s['trace_dir']}"]
    lines.append(
        f"  {s['device_op_events']} device-op events, "
        f"{s['total_device_us'] / 1e3:.3f} ms device-op time, "
        f"{len(s['xplane_files'])} xplane(s), "
        f"{len(s['trace_files'])} perfetto dump(s)"
    )
    if not s["phases"]:
        lines.append("  no sphexa/ phases found — pre-attribution capture, "
                     "or the named scopes were stripped (run the HLO pin "
                     "test in tests/test_phase_attr.py)")
        return "\n".join(lines)
    rows = [(p["phase"], f"{p['us'] / 1e3:.3f} ms", f"{p['share']:.1%}",
             p["ops"], p["events"]) for p in s["phases"]]
    lines.append(render_table(
        rows, headers=("phase", "device time", "share", "ops", "events")))
    lines.append(f"attributed: {s['attributed_us'] / 1e3:.3f} ms "
                 f"({s['coverage']:.1%} of device-op time)")
    if s["unattributed_top"]:
        lines.append("top unattributed ops:")
        rows = [(u["module"], u["op"], f"{u['us'] / 1e3:.3f} ms",
                 f"{u['share']:.1%}") for u in s["unattributed_top"]]
        lines.append(render_table(rows))
    return "\n".join(lines)
