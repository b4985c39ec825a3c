"""Arithmetic on what the program recorded BEFORE the measured window.

``run["events"]`` is the window's events only. What the initialiser, the
``Simulation`` constructor and the warm-up emitted is still in the run's
``MemorySink``, which hangs on the program's process-current registry
(``sphexa_tpu.telemetry.registry.current()``, the one ``Simulation.__init__``
names); the readers run in the same process after the window, so they
reach it with no hand-over from ``run.py`` (SETUP.md). A recorded run
carries the list itself, as ``run["setup_events"]``.

Two kinds of event make the account. ``span`` (program_spans.py) with the
start-up's names: ``sphexa:init-case``, ``sphexa:construct`` with
``sphexa:reconfigure`` and ``sphexa:size-*`` inside, and the warm-up's
``sphexa:rebuild-lists`` / ``sphexa:launch`` / ``sphexa:fetch``. And
``compile`` (schema v21), one per program traced, lowered and compiled or
loaded: ``{"kind": "compile", "fun", "trace_s", "lower_s", "backend_s",
"cache": "hit" | "miss" | "off", "retrieval_s", "saved_s", "t1_ns", "it",
"parent"}``, ``parent`` being the id of the span it happened under.

The leaf rule: a span's SELF time is its duration less its child spans and
less ``trace_s + lower_s + backend_s`` of every ``compile`` parented to it.
Every second of start-up is then counted once: under the span it was spent
in, or as a compile's cost.

Pure functions of an event list. A program from before ``compile`` and
``registry.current`` gives ``setup_events`` nothing to return, and every
reader then returns ``None``.
"""

import program_spans
import windows

SIZING = ("sphexa:reconfigure", "sphexa:size-neighbors",
          "sphexa:size-gravity", "sphexa:size-halo")
#: a backend compile at least this long is one jax stores in its persistent
#: cache (``jax_persistent_cache_min_compile_time_secs``): missing it there
#: means evicted, keyed anew, or never run before
STORED_COMPILE_S = 1.0
#: the leaves that are seconds of the harness's init-construct + warm
TIMES = ("ic_s", "sizing_s", "list_build_s", "steps_s", "trace_lower_s",
         "exe_load_s", "backend_compile_s", "construct_s")


def setup_events(run):
    """The events the program emitted before the window's first, or None
    where they cannot be reached."""
    if "setup_events" in run:
        return run["setup_events"]
    from sphexa_tpu.telemetry import registry

    tel = getattr(registry, "current", lambda: None)()
    events = next((s.events for s in getattr(tel, "sinks", ())
                   if hasattr(s, "events")), None)
    if events is None or not run["events"]:
        return None
    first = run["events"][0]["seq"]
    return [e for e in events if e["seq"] < first]


def compiles(events):
    return [e for e in events if e["kind"] == "compile"]


def cost(c):
    """Seconds one ``compile`` event took out of the span it is under."""
    return c["trace_s"] + c["lower_s"] + c["backend_s"]


def self_seconds(events):
    """``{span id: seconds}``: each span's duration less the spans and the
    compiles directly inside it."""
    found = program_spans.spans(events)
    own = {s["id"]: s["dur_ns"] * 1e-9 for s in found}
    for s in found:
        if s["parent"] in own:
            own[s["parent"]] -= s["dur_ns"] * 1e-9
    for c in compiles(events):
        if c["parent"] in own:
            own[c["parent"]] -= cost(c)
    return own


def account(events):
    """The leaves of the start-up account, from the events before the
    window."""
    own = self_seconds(events)
    found = program_spans.spans(events)
    programs = compiles(events)
    hits = [c for c in programs if c["cache"] == "hit"]
    rest = [c for c in programs if c["cache"] != "hit"]

    def self_of(*names):
        return sum(own[s["id"]] for s in found if s["name"] in names)

    return {
        "ic_s": self_of("sphexa:init-case"),
        "sizing_s": self_of(*SIZING),
        "list_build_s": self_of("sphexa:rebuild-lists"),
        "steps_s": self_of("sphexa:fetch", "sphexa:launch"),
        "trace_lower_s": sum(c["trace_s"] + c["lower_s"] for c in programs),
        "exe_load_s": sum(c["retrieval_s"] for c in hits),
        "backend_compile_s": sum(c["backend_s"] for c in rest),
        "construct_s": self_of("sphexa:construct"),
        "cache_misses": sum(1 for c in rest if c["cache"] == "miss"
                            and c["backend_s"] >= STORED_COMPILE_S),
        "programs": len(programs),
    }


def leaf(run, name):
    """One leaf of the run's account; None where the program recorded no
    start-up."""
    events = setup_events(run)
    return None if events is None else account(events)[name]


def accounted_share(run):
    """Percent of the harness's init-construct + warm spans that the
    account's times name."""
    events = setup_events(run)
    wall = windows.span_seconds(run["setup_spans"], "init-construct", "warm")
    if events is None or not wall:
        return None
    leaves = account(events)
    return 100.0 * sum(leaves[k] for k in TIMES) / wall
