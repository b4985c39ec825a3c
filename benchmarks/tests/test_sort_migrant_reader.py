"""The reader PR 42 brought, ``sort_migrant_share`` (the ``exchange`` events
of stage ``sort``, schema v19), on hand-made records where the answer is
known by inspection, on PR 42's recorded chip run of
evrard-cooling-4m-x4.steady, and on recorded runs of programs that have no
such event (one chip; the mesh gravity cell, whose step carries no aux
state; the parent of PR 42): there the reader finds nothing and does not
raise, which is what the driver asks of a metric new in a PR when it runs
the parent."""

import json
import os

import pytest

import run

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
CELL = "evrard-cooling-4m-x4.steady"


def read(rec):
    return run.load_reader("layers", "sort_migrant_share")(rec)


def sort_event(migrants, rows=4189076, it=8):
    return {"kind": "exchange", "it": it, "steps": 4, "mode": "gspmd",
            "shipped_rows": 3 * rows // 4, "rows": rows,
            "migrant_rows": migrants, "stage": "sort"}


def test_by_hand():
    sph = {"kind": "exchange", "it": 8, "steps": 4, "mode": "sparse",
           "shipped_rows": 400, "rows": [90] * 4, "stage": "sph"}
    rec = {"trace": None,
           "events": [sph, sort_event(100, 1000), sort_event(300, 1000),
                      sort_event(0, 1000), {"kind": "window", "it": 8}]}
    # the median over the window's events, the other stages left out
    assert read(rec) == pytest.approx(0.1)
    assert read({"events": [sort_event(0)]}) == 0.0
    assert read({"events": [sort_event(4189076 // 2)]}) == pytest.approx(0.5)


@pytest.mark.parametrize("events", [
    [], [{"kind": "window", "it": 4}],
    [{"kind": "exchange", "it": 8, "shipped_rows": 4, "rows": [1] * 4,
      "stage": "gravity"}],
    # a writer without the field; no rows
    [{"kind": "exchange", "it": 8, "shipped_rows": 3, "rows": 4,
      "stage": "sort"}],
    [dict(sort_event(0), rows=0)]],
    ids=["empty", "one-chip", "other-stage", "no-field", "no-rows"])
def test_nothing_to_read(events):
    assert read({"trace": None, "events": events}) is None


@pytest.mark.parametrize("fixture", [
    "evrard_ve_4m_x4_steady.run.json", "evrard_cooling_1m_steady.run.json",
    "windshock_cooling_4m_steady.run.json"])
def test_recorded_runs_of_other_programs_read_nothing(fixture):
    with open(os.path.join(FIXTURES, fixture)) as f:
        assert read(json.load(f)) is None


def test_recorded_run():
    """PR 42's traced chip run of the cell, its result line and the
    window's ``exchange`` events as recorded: the reader gives the line's
    value."""
    with open(os.path.join(FIXTURES,
                           "evrard_cooling_4m_x4_steady.run.json")) as f:
        recorded = json.load(f)
    assert recorded["cell"] == CELL and recorded["chips"] == 4
    events = [e for e in recorded["events"]
              if e["kind"] == "exchange" and e.get("stage") == "sort"]
    assert events and all(e["rows"] == recorded["particles"] == 4189076
                          for e in events)
    line = recorded["result"]["metrics"]["sort_migrant_share"]
    assert line["unit"] == "ratio"
    assert read(recorded) == pytest.approx(line["value"])
    assert 0.0 <= line["value"] < 0.05


def test_declared_in_the_benchmark():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    m = next(m for m in bench["per_layer"]
             if m["name"] == "sort_migrant_share")
    assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) == (
        "program_counter", "multi-chip", "updates_per_s_chip", "ratio",
        "lower")
    assert m["workloads"] == [CELL]
    assert bench["per_layer"][-1] is m
