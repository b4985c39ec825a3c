"""From a ``jax.profiler`` capture to the numbers the layer readers use.

Two stages, so the second can be checked on a small recorded capture
(tests/fixtures):

1. ``load_capture(trace_dir)`` reads the ``*.xplane.pb`` and keeps, as plain
   lists, the device planes' op events (name, start, duration, scope path)
   and the host's ``sphexa:`` / ``bench:`` annotations.
2. ``reduce_capture(capture, steps)`` computes, per device, the busy union,
   self time per ``sphexa/<phase>`` scope, the coverage of that attribution,
   the top operations and the idle gaps labelled by what the host was doing.

What a TPU capture of this program looks like (read by hand from PR 22's
first traced run; ``python benchmarks/trace_reduce.py <dir>`` prints the same
for any capture): see README.md "The trace".

The program's own reducer (sphexa_tpu/telemetry/traceview.py) is not used:
the yardstick lives here, where a PR that claims a gain cannot change it.
"""

import bisect
import glob
import gzip
import json
import os
import re
import sys

#: phase = the FIRST ``sphexa/<phase>`` segment of an op's scope path
PHASE_RE = re.compile(r"sphexa/([A-Za-z0-9_.:+-]+)")
#: host annotations kept: the program's (simulation.py) and the harness's
ANNOTATION_PREFIXES = ("sphexa:", "bench:")
#: the harness annotation that delimits the traced stretch
TRACED = "bench:traced"
#: annotations that enclose everything and so label nothing
_ENCLOSING = (TRACED, "bench:cycle")
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
#: the device plane's line of per-operation events
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def _xspace_class():
    """The XSpace message class, built from a hand-written descriptor of
    the few fields of tsl/profiler/protobuf/xplane.proto that are read here
    (protobuf skips the rest). ``jax.profiler.ProfileData`` is not enough:
    it shows an event's own stats, and a TPU capture keeps the scope path
    (``tf_op``) in the stats of the event's *metadata*."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "double": F.TYPE_DOUBLE, "string": F.TYPE_STRING,
              "bytes": F.TYPE_BYTES}
    pkg = "sphexa_bench_xplane"
    fd = descriptor_pb2.FileDescriptorProto(
        name=pkg + ".proto", package=pkg, syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if ftype in scalar:
                f.type = scalar[ftype]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{ftype}"

    message("XStat", ("metadata_id", 1, "int64", False),
            ("double_value", 2, "double", False),
            ("uint64_value", 3, "uint64", False),
            ("int64_value", 4, "int64", False),
            ("str_value", 5, "string", False),
            ("bytes_value", 6, "bytes", False),
            ("ref_value", 7, "uint64", False))
    message("XEvent", ("metadata_id", 1, "int64", False),
            ("offset_ps", 2, "int64", False),
            ("duration_ps", 3, "int64", False))
    message("XLine", ("name", 2, "string", False),
            ("timestamp_ns", 3, "int64", False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("id", 1, "int64", False),
            ("name", 2, "string", False),
            ("display_name", 4, "string", False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, "int64", False),
            ("name", 2, "string", False))
    # map<int64, X> fields are repeated (key, value) entries on the wire
    message("EventMetadataEntry", ("key", 1, "int64", False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, "int64", False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, "string", False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(pkg + ".XSpace"))


def load_capture(trace_dir):
    """Plain capture: ``{"devices": {ordinal: [[name, start_ns, dur_ns,
    scope], ...]}, "annotations": [[name, start_ns, dur_ns], ...]}``.

    ``name`` is the HLO instruction's name (``fusion.12``,
    ``momentum-energy.1``: the metadata's display name), ``scope`` its
    ``tf_op`` metadata stat (``jit(_step_hydro_std)/sphexa/density/...``),
    times are on the capture's own clock, shared by host and device."""
    space = _xspace_class()()
    with open(find_xplane(trace_dir), "rb") as f:
        space.ParseFromString(f.read())
    devices, annotations = {}, []
    for plane in space.planes:
        names = {e.key: e.value for e in plane.event_metadata}
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            tf_op = {e.key for e in plane.stat_metadata
                     if e.value.name == "tf_op"}
            scope = {k: next((s.str_value for s in md.stats
                              if s.metadata_id in tf_op), "")
                     for k, md in names.items()}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                t0 = line.timestamp_ns
                devices[m.group(1)] = [
                    [names[e.metadata_id].display_name
                     or names[e.metadata_id].name,
                     t0 + e.offset_ps * 1e-3, e.duration_ps * 1e-3,
                     scope[e.metadata_id]] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                t0 = line.timestamp_ns
                for e in line.events:
                    name = names[e.metadata_id].name
                    if name.startswith(ANNOTATION_PREFIXES):
                        annotations.append([name, t0 + e.offset_ps * 1e-3,
                                            e.duration_ps * 1e-3])
    annotations.sort(key=lambda a: a[1])
    return {"devices": devices, "annotations": annotations}


def save_capture(capture, path):
    with gzip.open(path, "wt") as f:
        json.dump(capture, f, separators=(",", ":"))


def read_capture(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """Self time of each event of one line, where events may nest (a
    ``while`` encloses its body's ops): duration minus the children's.
    Returns two dicts keyed by event index: self time in ns, and the index
    of the enclosing event (None at top level)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = {i: events[i][2] for i in order}
    parent = {i: None for i in order}
    stack = []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            p = stack[-1]
            p_end = events[p][1] + events[p][2]
            self_ns[p] -= min(end, p_end) - start
            parent[i] = p
        stack.append(i)
    return self_ns, parent


def _phase(events, i, parent):
    """Phase of event ``i``: its own scope's, else its nearest enclosing
    op's (a loop body inherits the loop's phase)."""
    while i is not None:
        m = PHASE_RE.search(events[i][3])
        if m:
            return m.group(1)
        i = parent[i]
    return None


def _host_timeline(annotations):
    """Non-overlapping [start, end, label] segments: at each instant the
    innermost (latest-started) annotation that covers it, the enclosing
    harness annotations left out."""
    spans = [(s, s + d, name) for name, s, d in annotations
             if name not in _ENCLOSING]
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [sp for sp in spans if sp[0] <= a and sp[1] >= b]
        if covering:
            out.append([a, b, max(covering, key=lambda sp: sp[0])[2]])
    return out


def _label_gaps(gaps, timeline):
    """Idle seconds by what the host was doing: each gap is split over the
    host timeline's segments; what no annotation covers is
    'unattributed'."""
    starts = [seg[0] for seg in timeline]
    out = {}
    for g0, g1 in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(timeline) and timeline[i][0] < g1:
            a, b, label = timeline[i]
            cover = min(g1, b) - max(g0, a)
            if cover > 0:
                out[label] = out.get(label, 0.0) + cover
                covered += cover
            i += 1
        if g1 - g0 > covered:
            out["unattributed"] = out.get("unattributed", 0.0) \
                + (g1 - g0 - covered)
    return out


def _clip(events, w0, w1):
    out = []
    for name, start, dur, scope in events:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            out.append([name, s, e - s, scope])
    return out


def reduce_capture(capture, steps):
    """Summary of the traced stretch (the ``bench:traced`` annotation, or
    the span of the device events where there is none).

    ``steps``: simulation steps completed inside the traced stretch, for the
    per-step phase times.
    """
    annotations = capture["annotations"]
    traced = [a for a in annotations if a[0] == TRACED]
    if traced:
        w0, w1 = traced[0][1], traced[0][1] + traced[0][2]
    else:
        starts = [e[1] for ev in capture["devices"].values() for e in ev]
        ends = [e[1] + e[2] for ev in capture["devices"].values() for e in ev]
        if not starts:
            return None
        w0, w1 = min(starts), max(ends)
    window_ns = w1 - w0
    timeline = _host_timeline(annotations)
    per_device = {}
    for ordinal, raw in capture["devices"].items():
        events = _clip(raw, w0, w1)
        if not events:
            continue
        busy = _union([[e[1], e[1] + e[2]] for e in events])
        busy_ns = sum(e - s for s, e in busy)
        self_ns, parent = _self_times(events)
        phases, ops, attributed = {}, {}, 0.0
        for i, ev in enumerate(events):
            t = max(self_ns[i], 0.0)
            ops[ev[0]] = ops.get(ev[0], 0.0) + t
            ph = _phase(events, i, parent)
            if ph is not None:
                phases[ph] = phases.get(ph, 0.0) + t
                attributed += t
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps = _label_gaps(
            [g for g in zip(edges[0::2], edges[1::2]) if g[1] > g[0]],
            timeline)
        per_device[ordinal] = {
            "busy_s": busy_ns * 1e-9,
            "idle_share": 1.0 - busy_ns / window_ns,
            "coverage": attributed / busy_ns if busy_ns else 0.0,
            "phase_s": {k: v * 1e-9 for k, v in phases.items()},
            "op_s": {k: v * 1e-9 for k, v in ops.items()},
            "gap_s": {k: v * 1e-9 for k, v in gaps.items()},
        }
    if not per_device:
        return None
    devs = list(per_device.values())
    worst = max(devs, key=lambda d: d["idle_share"])
    phase_names = sorted({p for d in devs for p in d["phase_s"]})
    top = lambda table: [[k, v] for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_ns * 1e-9,
        "steps": steps,
        "devices": len(devs),
        # averaged over the chips used (the contract's device.busy_s)
        "busy_s": sum(d["busy_s"] for d in devs) / len(devs),
        "idle_share_worst": worst["idle_share"],
        "coverage_min": min(d["coverage"] for d in devs),
        # the slowest device sets the step
        "phase_s_max": {p: max(d["phase_s"].get(p, 0.0) for d in devs)
                        for p in phase_names},
        "device_ops": top(worst["op_s"]),
        "idle_gaps": top(worst["gap_s"]),
        "per_device": {k: {"busy_s": d["busy_s"],
                           "idle_share": d["idle_share"],
                           "coverage": d["coverage"]}
                       for k, d in per_device.items()},
    }


def phase_ms_per_step(summary, phases):
    """Device self time under ``phases`` per traced step, in ms, on the
    slowest device; None without a trace or traced steps."""
    if not summary or not summary.get("steps"):
        return None
    total = sum(summary["phase_s_max"].get(p, 0.0) for p in phases)
    return 1e3 * total / summary["steps"]


def describe(trace_dir, out=sys.stdout, head=8):
    """Print what a capture holds, for reading it by hand: every plane,
    its lines and event counts, the first events of each device line with
    their metadata's stats, and the host's annotations."""
    space = _xspace_class()()
    path = find_xplane(trace_dir)
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    print(f"capture {path} ({os.path.getsize(path)} bytes)", file=out)
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        names = {e.key: e.value for e in plane.event_metadata}
        print(f"PLANE {plane.name!r} event_metadata={len(names)}", file=out)
        for line in plane.lines:
            print(f"  LINE {line.name!r} events={len(line.events)} "
                  f"timestamp_ns={line.timestamp_ns}", file=out)
            shown = 0
            for e in line.events:
                md = names[e.metadata_id]
                host = plane.name.startswith("/host:")
                if shown >= head or (host and not md.name.startswith(
                        ANNOTATION_PREFIXES)):
                    continue
                shown += 1
                stats = {stat_names.get(s.metadata_id): str(
                    s.str_value or s.int64_value or s.uint64_value
                    or s.double_value)[:90] for s in md.stats}
                print(f"    {(md.display_name or md.name)[:60]!r} "
                      f"offset_ps={e.offset_ps} dur_ps={e.duration_ps} "
                      f"metadata_stats={stats}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
    cap = load_capture(sys.argv[1])
    print(json.dumps(reduce_capture(cap, steps=None), indent=1)[:6000])
