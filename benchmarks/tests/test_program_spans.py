"""The readers of the program's own spans, on a recorded ``events`` list
(fixtures/: the measured window of PR 23's traced run of
sedov-std-4m.dumps on a v5e), on the parent's recorded run, which has no
span, and on hand-made lists for the recovery cases that run did not
have."""

import json
import os

import pytest

import program_spans
import run as harness
import windows

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
DRIVER = ("launch_ms_step", "fetch_wait_ms_step", "driver_host_ms_step",
          "host_stall_s_max", "recovery_s")
DUMP = ("dump_program_s", "dump_fetch_s", "dump_h5_s")


def _run(name):
    with open(os.path.join(FIXTURES, name)) as f:
        rec = json.load(f)
    return {"events": rec["events"], "spans": rec["spans"],
            "window": {"wall_s": rec["wall_s"],
                       "cycle_facts": rec.get("cycle_facts", [])}}


@pytest.fixture(scope="module")
def dumps():
    return _run("sedov_std_4m_dumps.events.json")


@pytest.fixture(scope="module")
def parent():
    """PR 22's run of the steady cell: a program without spans."""
    return _run("sedov_std_4m_steady.events.json")


def read(name, run):
    return harness.load_reader("layers", name)(run)


def test_window_table_of_the_recorded_run(dumps):
    rows = program_spans.window_table(dumps["events"])
    assert [r["it"] for r in rows] == [2, 6, 10, 14, 18]
    assert all(r["steps"] == 4 and len(r["launches"]) == 4 for r in rows)
    for r in rows:
        # the flush is its fetch, its settle and microseconds of its own
        assert 0 <= r["flush"] - r["fetch"] - r["settle"] < 1e-3
        assert r["recovery"] == 0.0


@pytest.mark.parametrize("name,lo,hi", [
    ("launch_ms_step", 1.0, 3.0),
    ("fetch_wait_ms_step", 1380.0, 1383.0),
    ("driver_host_ms_step", 1.5, 2.5),
    ("host_stall_s_max", 0.0, 0.01),
    ("recovery_s", 0.0, 0.0),
    ("dump_program_s", 0.72, 0.74),
    ("dump_fetch_s", 0.31, 0.34),
    ("dump_h5_s", 0.23, 0.26),
])
def test_reader_on_the_recorded_run(dumps, name, lo, hi):
    assert lo <= read(name, dumps) <= hi


@pytest.mark.parametrize("name", DRIVER + DUMP)
def test_reader_finds_nothing_without_spans(parent, name):
    assert read(name, parent) is None


@pytest.mark.parametrize("name", DUMP)
def test_dump_reader_finds_nothing_in_a_run_without_dumps(dumps, name):
    steady = {**dumps, "events": [
        e for e in dumps["events"]
        if not e.get("name", "").startswith("sphexa:dump")]}
    assert read(name, steady) is None
    assert read("launch_ms_step", steady) == read("launch_ms_step", dumps)


def test_window_identity(dumps):
    """The driver's ``window.wall_s`` runs from before the pin to after
    the fetch: pin + launches + fetch account for it to 0.5 %."""
    step_ms = 1e3 * windows.median(
        windows.clean_step_seconds(dumps["events"]))
    pin_ms = 1e3 * windows.median(
        [r["pin"] / r["steps"]
         for r in program_spans.window_table(dumps["events"])])
    inside = (read("launch_ms_step", dumps)
              + read("fetch_wait_ms_step", dumps) + pin_ms)
    assert inside == pytest.approx(step_ms, rel=5e-3)
    assert inside <= step_ms


def test_dump_identity(dumps):
    """Program, fetch and file write account for the harness's ``dump``
    span to 97 %, and never exceed it."""
    dump_s = windows.median(windows.span_durations(dumps["spans"], "dump"))
    inside = sum(read(name, dumps) for name in DUMP)
    assert 0.97 * dump_s <= inside <= dump_s
    # the fetches inside the harness's two halves of a dump
    recompute = windows.median(
        windows.span_durations(dumps["spans"], "dump-recompute"))
    assert read("dump_program_s", dumps) < recompute


def span(name, id, parent, it, dur_ms, **payload):
    return {"kind": "span", "name": name, "id": id, "parent": parent,
            "it": it, "t0_ns": 0, "dur_ns": int(dur_ms * 1e6), **payload}


def window(it, steps=2, pin=1.0, fetch=100.0, settle=2.0, first_id=1,
           inside_settle=()):
    """The events of one verified window that opened at ``it``."""
    i = first_id
    out = [span("sphexa:pin", i, None, it, pin)]
    out += [span("sphexa:launch", i + 1 + k, None, it, 1.0)
            for k in range(steps)]
    flush = i + 1 + steps
    out += [{"kind": "launch"}] * steps
    out.append(span("sphexa:fetch", flush + 1, flush, it, fetch))
    out.append({"kind": "window", "it": it + steps, "steps": steps,
                "wall_s": 0.0, "per_step_s": 0.0})
    out += [span(name, flush + 3 + k, flush + 2, it, ms)
            for k, (name, ms) in enumerate(inside_settle)]
    out.append(span("sphexa:settle", flush + 2, flush, it, settle))
    out.append(span("sphexa:flush", flush, None, it, fetch + settle))
    return out


def test_recovery_inside_a_window_is_not_a_stall():
    rebuild = [("sphexa:rebuild-lists", 50.0)]
    events = (window(0) + [{"kind": "rebuild_lists"}]
              + window(2, first_id=20, settle=52.0, inside_settle=rebuild)
              + window(4, first_id=40) + window(6, first_id=60, fetch=130.0))
    run = {"events": events}
    assert read("recovery_s", run) == pytest.approx(0.050)
    # the rebuild is taken out of its window's host path; the 30 ms the
    # last window's fetch ran over the median is the stall
    assert read("host_stall_s_max", run) == pytest.approx(0.030)
    assert read("driver_host_ms_step", run) == pytest.approx(1.5)


def test_rollback_counts_once_and_its_window_is_not_clean():
    rolled = [
        span("sphexa:pin", 1, None, 0, 1.0),
        span("sphexa:launch", 2, None, 0, 1.0), {"kind": "launch"},
        span("sphexa:fetch", 4, 3, 0, 100.0),
        {"kind": "rollback", "steps": 1},
        span("sphexa:size-neighbors", 7, 6, 0, 5.0),
        span("sphexa:reconfigure", 6, 5, 0, 20.0, reason="overflow"),
        {"kind": "reconfigure", "reason": "overflow"},
        span("sphexa:launch", 9, 8, 0, 900.0), {"kind": "retrace"},
        span("sphexa:fetch", 10, 8, 0, 80.0),
        span("sphexa:step", 8, 5, 0, 985.0), {"kind": "step", "wall_s": 1.0},
        span("sphexa:rollback", 5, 3, 0, 1010.0), {"kind": "replay"},
        span("sphexa:flush", 3, None, 0, 1111.0),
    ]
    run = {"events": rolled + window(1, first_id=20)}
    # the rollback holds its own reconfigure: counted once
    assert read("recovery_s", run) == pytest.approx(1.010)
    assert [r["it"] for r in program_spans.window_table(run["events"])] \
        == [1]
    assert read("launch_ms_step", run) == pytest.approx(1.0)
