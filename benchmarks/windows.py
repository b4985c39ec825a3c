"""Arithmetic on the run's telemetry events and harness spans.

Pure functions of plain lists, so the layer readers and the tests use the
same code. The clean-window rule is chip_smoke.py:leg_facts' (the program's
copy is listed in PERF.md "Open questions" for deletion).

Events are the dicts the program's ``Telemetry`` hands its sinks
(``kind`` plus payload). Spans are the harness's own:
``{"name": str, "t0": float, "t1": float}`` on ``time.perf_counter``.
"""

#: events that make the stretch up to the next fetch boundary "dirty":
#: its launches compiled, or it followed a recovery
DIRTY = ("retrace", "reconfigure", "rollback")
#: events that are a recovery the driver paid for inside the clock
RECOVERY = ("rebuild_lists", "reconfigure", "rollback")


def clean_step_seconds(events):
    """Per-step seconds of every clean fetch stretch: a ``window`` (its
    ``per_step_s``) or a checked ``step`` (its ``wall_s``) with no DIRTY
    event since the fetch boundary before it."""
    clean, dirty = [], False
    for e in events:
        kind = e["kind"]
        if kind in DIRTY:
            dirty = True
        elif kind in ("window", "step"):
            if not dirty:
                clean.append(e["per_step_s"] if kind == "window"
                             else e["wall_s"])
            dirty = False
    return clean


def device_span_seconds(events):
    """Sum of the driver's launch-to-fetch spans: every ``window`` and
    every checked ``step`` (replays after a rollback are checked steps)."""
    return sum(e["wall_s"] for e in events if e["kind"] in ("window", "step"))


def recoveries(events):
    return sum(1 for e in events if e["kind"] in RECOVERY)


def steps_attempted(events):
    """Steps launched, replays included: deferred launches plus checked
    steps."""
    return sum(1 for e in events if e["kind"] in ("launch", "step"))


def unexplained_retraces(events):
    """``retrace`` events that follow no ``reconfigure`` or ``rollback`` of
    the same list: inside a measured window they are shapes the warm-up
    missed. (A reconfigure or rollback legitimately brings new programs for
    the rest of the window: the resized step and the checked replay.)"""
    out, explained = [], False
    for e in events:
        if e["kind"] in ("reconfigure", "rollback"):
            explained = True
        elif e["kind"] == "retrace" and not explained:
            out.append(e)
    return out


def span_seconds(spans, *names):
    return sum(s["t1"] - s["t0"] for s in spans if s["name"] in names)


def span_durations(spans, name):
    return [s["t1"] - s["t0"] for s in spans if s["name"] == name]


def median(values):
    """Plain median; None for an empty list (a reader then reports
    nothing)."""
    v = sorted(values)
    if not v:
        return None
    mid = len(v) // 2
    return v[mid] if len(v) % 2 else 0.5 * (v[mid - 1] + v[mid])


def window_seconds(cycles):
    """The wall the window's cycles took, made steady against the host.

    ``cycles`` are the window's whole traffic cycles in order, each
    ``{"wall_s", "recoveries", "dumped"}``. Cycles in which the driver
    recovered nothing do the same work (one kind with a dump, one without),
    so each counts as the median of its kind: a stall of the host inside one
    of them (PR 22: 0.5-2.3 s, about one run in ten on a shared host) does
    not move the sum. A cycle with a RECOVERY event, and the cycle after it
    (where the device work a recovery queued lands), count at their own
    wall: the program's own extra work stays inside the clock."""
    own, same, after = 0.0, {}, False
    for c in cycles:
        if c["recoveries"] or after:
            own += c["wall_s"]
        else:
            same.setdefault(bool(c["dumped"]), []).append(c["wall_s"])
        after = bool(c["recoveries"])
    return own + sum(len(v) * median(v) for v in same.values())


def should_close(elapsed_s, cycles_done, seconds, min_cycles):
    """Cycle closing: the window ends at the end of the cycle that is
    running when ``seconds`` have elapsed, and never before ``min_cycles``
    whole cycles."""
    return elapsed_s >= seconds and cycles_done >= min_cycles
