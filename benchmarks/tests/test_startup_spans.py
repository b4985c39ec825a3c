"""The start-up account (startup_spans.py, SETUP.md) on a recorded sink
(fixtures/: PR 47's warm chip run of evrard-ve-1m.steady on a v5e, every
event from the initialiser's to the window's), on a run whose program
recorded no start-up, and on hand-made lists for the leaf rule."""

import gzip
import json
import os

import pytest

import run as harness
import startup_spans
import windows

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
READERS = ("setup_ic_s", "setup_sizing_s", "setup_list_build_s",
           "setup_steps_s", "setup_trace_lower_s", "setup_exe_load_s",
           "setup_backend_compile_s", "setup_cache_misses",
           "setup_programs", "setup_accounted_share")


@pytest.fixture(scope="module")
def warm():
    with gzip.open(os.path.join(
            FIXTURES, "evrard_ve_1m_steady.setup.json.gz"), "rt") as f:
        rec = json.load(f)
    first = rec["first_window_seq"]
    return {"setup_events": [e for e in rec["events"] if e["seq"] < first],
            "events": [e for e in rec["events"] if e["seq"] >= first],
            "setup_spans": rec["setup_spans"], "setup_s": rec["setup_s"]}


@pytest.fixture(scope="module")
def parent():
    """PR 22's run of the steady cell: no registry is current here and
    the record carries no start-up, as under a program from before v21."""
    with open(os.path.join(FIXTURES, "sedov_std_4m_steady.events.json")) as f:
        rec = json.load(f)
    return {"events": rec["events"], "setup_spans": []}


def read(name, run):
    return harness.load_reader("layers", name)(run)


def span(name, id, parent, t0_s, dur_s, **payload):
    return {"kind": "span", "name": name, "id": id, "parent": parent,
            "it": 0, "t0_ns": int(t0_s * 1e9), "dur_ns": int(dur_s * 1e9),
            **payload}


def compile_(fun, parent, trace_s, lower_s, backend_s, cache="hit",
             retrieval_s=0.0):
    return {"kind": "compile", "fun": fun, "parent": parent,
            "trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
            "cache": cache, "retrieval_s": retrieval_s, "saved_s": 0.0,
            "t1_ns": 0, "it": 0}


@pytest.mark.parametrize("name,lo,hi", [
    ("setup_ic_s", 0.45, 0.47),
    ("setup_sizing_s", 1.25, 1.27),
    ("setup_list_build_s", 0.48, 0.50),
    ("setup_steps_s", 2.15, 2.17),
    ("setup_trace_lower_s", 12.8, 13.0),
    ("setup_exe_load_s", 1.85, 1.87),
    ("setup_backend_compile_s", 5.0, 5.1),
    ("setup_cache_misses", 0.0, 0.0),
    ("setup_programs", 59.0, 59.0),
    ("setup_accounted_share", 93.4, 93.7),
])
def test_reader_on_the_recorded_run(warm, name, lo, hi):
    assert lo <= read(name, warm) <= hi


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_a_recorded_start(parent, name):
    assert read(name, parent) is None


def test_every_reader_is_declared_for_every_cell():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ours = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("setup_")}
    assert tuple(ours) == READERS
    assert all("workloads" not in m and m["moves"] == "setup_s"
               and m["layer"] == "host runtime and initialisers"
               for m in ours.values())


def test_the_leaf_rule():
    """A span's self time is its duration less its child spans and less
    the compiles parented to it; each second lands in one leaf."""
    events = [
        compile_("jit(iota)", 1, 0.0, 0.1, 0.2, cache="miss"),
        span("sphexa:init-case", 1, None, 0.0, 3.0, case="evrard"),
        compile_("jit(sizing)", 4, 0.5, 0.25, 1.25, retrieval_s=1.0),
        span("sphexa:size-neighbors", 4, 3, 4.5, 3.0),
        span("sphexa:reconfigure", 3, 2, 4.0, 4.0, reason="initial"),
        span("sphexa:construct", 2, None, 3.5, 5.0),
        compile_("jit(rebuild)", 5, 1.0, 0.5, 2.0, cache="miss"),
        span("sphexa:rebuild-lists", 5, None, 9.0, 4.0),
        compile_("jit(step)", 6, 2.0, 1.0, 3.5, retrieval_s=3.0),
        span("sphexa:launch", 6, None, 13.0, 7.0),
        span("sphexa:fetch", 8, 7, 20.0, 1.0),
        span("sphexa:flush", 7, None, 20.0, 1.25),
    ]
    leaves = startup_spans.account(events)
    assert leaves == pytest.approx({
        "ic_s": 3.0 - 0.3, "sizing_s": (3.0 - 2.0) + (4.0 - 3.0),
        "list_build_s": 4.0 - 3.5, "steps_s": (7.0 - 6.5) + 1.0,
        "trace_lower_s": 0.1 + 0.75 + 1.5 + 3.0, "exe_load_s": 4.0,
        "backend_compile_s": 0.2 + 2.0, "construct_s": 5.0 - 4.0,
        "cache_misses": 1, "programs": 4})
    run = {"setup_events": events, "events": [], "setup_spans": [
        {"name": "chip-reach", "t0": -10.0, "t1": 0.0},
        {"name": "init-construct", "t0": 0.0, "t1": 9.0},
        {"name": "warm", "t0": 9.0, "t1": 25.0}]}
    named = sum(leaves[k] for k in startup_spans.TIMES)
    # unnamed: the flush's own 0.25 s, the hits' 0.25 + 0.5 s of backend
    # outside the retrieval, and what lies between the spans
    assert named == pytest.approx(19.25)
    assert read("setup_accounted_share", run) == pytest.approx(
        100.0 * named / 25.0)


def test_init_and_construct_identity(warm):
    """``sphexa:init-case`` + ``sphexa:construct`` are the harness's
    ``init-construct`` span to 0.5 % from where the initialiser starts.
    Before that lie the program's own imports inside ``build_simulation``
    (1.67 s of the recorded 10.37: the stretch the account does not name,
    SETUP.md), so the two spans alone make 84 % of it, not all."""
    events = warm["setup_events"]
    of = lambda name: [e for e in events if e["kind"] == "span"
                       and e["name"] == name]
    (ic,), (construct,) = of("sphexa:init-case"), of("sphexa:construct")
    (outer,) = [s for s in warm["setup_spans"]
                if s["name"] == "init-construct"]
    imports = ic["t0_ns"] * 1e-9 - outer["t0"]
    assert 1.6 < imports < 1.8
    inside = (ic["dur_ns"] + construct["dur_ns"]) * 1e-9
    wall = outer["t1"] - outer["t0"]
    assert inside <= wall - imports
    assert inside == pytest.approx(wall - imports, rel=5e-3)
    assert 0.83 < inside / wall < 0.85


def test_hits_load_inside_their_backend_time(warm):
    hits = [e for e in warm["setup_events"] if e["kind"] == "compile"
            and e["cache"] == "hit"]
    assert hits
    assert (sum(e["backend_s"] for e in hits)
            >= sum(e["retrieval_s"] for e in hits) > 0)
    assert all(e["backend_s"] >= e["retrieval_s"] for e in hits)


def test_the_recorded_account_is_consistent(warm):
    events = warm["setup_events"]
    own = startup_spans.self_seconds(events)
    assert min(own.values()) > -1e-3
    leaves = startup_spans.account(events)
    wall = windows.span_seconds(warm["setup_spans"], "init-construct",
                                "warm")
    assert sum(leaves[k] for k in startup_spans.TIMES) <= wall
    ids = {e["id"] for e in events if e["kind"] == "span"}
    assert all(e["parent"] is None or e["parent"] in ids
               for e in events if e["kind"] == "compile")
    # the measured window compiled nothing: what the sink holds after it
    # are the programs of correct.py's gravity check, outside the clock
    after = warm["events"]
    last = max(i for i, e in enumerate(after) if e["kind"] == "window")
    assert not [e for e in after[:last + 1] if e["kind"] == "compile"]
    assert [e["fun"] for e in after[last:] if e["kind"] == "compile"] == [
        "jit(compute_gravity)", "jit(convert_element_type)",
        "jit(direct_sum_gravity)"]
