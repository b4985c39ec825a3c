"""Arithmetic on the program's own host spans.

The program's ``Telemetry.span`` (sphexa_tpu/telemetry/registry.py) emits
one ``span`` event per closed span into the run's events:
``{"kind": "span", "name", "id", "parent", "it", "t0_ns", "dur_ns", ...}``
on ``time.perf_counter_ns``, the clock of the harness's own spans. ``it``
is the iteration at which the check window opened, so the spans of one
window (``sphexa:pin``, its ``sphexa:launch``es, ``sphexa:flush`` with
``sphexa:fetch`` and ``sphexa:settle`` inside) and of the dump at its
boundary (``sphexa:dump-*``) share it; ``parent`` is the id of the span
that was open when this one opened.

Pure functions of ``run["events"]``, like windows.py. A program without
spans (the parent of the PR that brought them) has no ``span`` event, and
every function here then returns an empty list or ``None``.
"""

import windows

#: spans that are a recovery the driver paid for (the span twins of
#: ``windows.RECOVERY``'s events)
RECOVERY = ("sphexa:reconfigure", "sphexa:rebuild-lists", "sphexa:rollback")


def spans(events, name=None):
    """The ``span`` events, in the order they closed; of one name if
    given."""
    return [e for e in events if e["kind"] == "span"
            and (name is None or e["name"] == name)]


def seconds(found):
    return sum(s["dur_ns"] for s in found) * 1e-9


def children(found, parent, name=None):
    """Those of the spans ``found`` that opened directly inside the span
    ``parent``; of one name if given."""
    return [s for s in found if s["parent"] == parent["id"]
            and (name is None or s["name"] == name)]


def by_iteration(found):
    """``{it: [span, ...]}``: the spans of one window or one dump."""
    out = {}
    for s in found:
        out.setdefault(s["it"], []).append(s)
    return out


def clean_windows(events):
    """``[(it, steps)]`` of every clean deferred check window, by the rule
    of ``windows.clean_step_seconds``: a ``window`` event with no DIRTY
    event since the fetch boundary before it. ``it`` is the iteration the
    window opened at (its spans carry it), ``steps`` its length."""
    out, dirty = [], False
    for e in events:
        kind = e["kind"]
        if kind in windows.DIRTY:
            dirty = True
        elif kind in ("window", "step"):
            if kind == "window" and not dirty:
                out.append((e["it"] - e["steps"], e["steps"]))
            dirty = False
    return out


def outermost_recoveries(events):
    """RECOVERY spans that lie inside no other RECOVERY span (a rollback's
    own reconfigure and list rebuild are part of the rollback)."""
    all_spans = {s["id"]: s for s in spans(events)}

    def inside_recovery(s):
        p = all_spans.get(s["parent"])
        while p is not None:
            if p["name"] in RECOVERY:
                return True
            p = all_spans.get(p["parent"])
        return False

    return [s for s in all_spans.values()
            if s["name"] in RECOVERY and not inside_recovery(s)]


def window_table(events):
    """One row per clean window that has its spans: ``steps`` and the
    seconds of ``pin``, ``launch`` (summed), ``launches`` (each),
    ``flush``, ``fetch`` and ``settle`` (children of the flush), and
    ``recovery`` (outermost RECOVERY spans of the window)."""
    groups = by_iteration(spans(events))
    recovered = by_iteration(outermost_recoveries(events))
    rows = []
    for it, steps in clean_windows(events):
        group = groups.get(it, [])
        flushes = [s for s in group if s["name"] == "sphexa:flush"]
        if len(flushes) != 1:
            continue
        of = lambda name: [s for s in group if s["name"] == name]
        launches = [s for s in of("sphexa:launch") if s["parent"] is None]
        rows.append({
            "it": it, "steps": steps,
            "pin": seconds(of("sphexa:pin")),
            "launch": seconds(launches),
            "launches": [s["dur_ns"] * 1e-9 for s in launches],
            "flush": seconds(flushes),
            "fetch": seconds(children(group, flushes[0], "sphexa:fetch")),
            "settle": seconds(children(group, flushes[0], "sphexa:settle")),
            "recovery": seconds(recovered.get(it, [])),
        })
    return rows


def per_dump_seconds(events, name):
    """Seconds under the spans of one name, summed per dump (spans that
    share ``it``), in order; empty where the program has no such span."""
    return [seconds(group)
            for _, group in sorted(by_iteration(spans(events, name)).items())]
