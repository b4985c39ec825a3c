"""Arithmetic on the life of the persistent pair lists, from the run's events.

Since schema v10 the driver's ``rebuild_lists`` event (emitted once per
rebuild that built a list) says why and when: ``reason`` (``first`` |
``proactive`` | ``expiry`` | ``rollback`` | ``reconfigure``), ``age_steps``
(verified steps the outgoing list served), ``slack`` (the ``list_slack``
that triggered it, where one did), ``slot_need`` / ``slot_cap`` and
``attempts`` (RECOVERIES.md). Pure functions of ``run["events"]``, like
windows.py and program_spans.py. A program from before the fields carries
none of them: ``rebuilds`` is then empty and every reader returns ``None``.
"""

import program_spans


def rebuilds(events):
    """The ``rebuild_lists`` events that carry the v10 fields, in order."""
    return [e for e in events
            if e["kind"] == "rebuild_lists" and "reason" in e]


def ages(events):
    """``age_steps`` of every list a rebuild replaced (the first list of a
    run replaces none)."""
    return [e["age_steps"] for e in rebuilds(events)
            if e["reason"] != "first"]


def replayed_steps(events):
    """Steps the window discarded and ran again: the ``steps`` of every
    ``rollback``."""
    return sum(e["steps"] for e in events if e["kind"] == "rollback")


def rebuild_span_seconds(events):
    """Seconds of each ``sphexa:rebuild-lists`` span (one per attempt)."""
    return [s["dur_ns"] * 1e-9
            for s in program_spans.spans(events, "sphexa:rebuild-lists")]
