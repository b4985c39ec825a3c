"""The two readers PR 38 brought for the Evrard cooling cell
(``sort_aux_ms_step``, ``cooling_radiated_share``), on hand-made records where
the answer is known by inspection and on a cut of a traced chip run of
evrard-cooling-1m.steady (fixtures/evrard_cooling_1m_steady.run.json, whose
``what`` says which run).

    python3 -m pytest benchmarks/tests/test_collapse_readers.py -q
"""

import json
import os

import pytest

import run
import stage_times

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "evrard_cooling_1m_steady.run.json")
NAMES = ("sort_aux_ms_step", "cooling_radiated_share")
CELL = "evrard-cooling-1m.steady"


def read(name, rec):
    return run.load_reader("layers", name)(rec)


def with_table(cell, rows, steps):
    """Put a (first phase, last token) table where ``stage_times.of_run``
    keeps a parsed capture."""
    stage_times.TABLES[cell] = {
        "steps": steps, "window_s": 1.0, "devices": {"0": {
            "rows": rows, "phase_ns": {}, "unscoped_ns": 0.0}}}


@pytest.fixture(autouse=True)
def no_tables():
    yield
    stage_times.TABLES.clear()


def events(windows, etot=-0.5):
    """``physics`` + ``numerics`` events of windows whose verified steps gave
    the gas the energies listed (None: a program without the counter)."""
    out, total = [], 0.0
    for it, steps in enumerate(windows, start=1):
        out.append({"kind": "physics", "it": 4 * it,
                    "etot": [etot] * 4})
        numerics = {"kind": "numerics", "it": 4 * it, "limiter": {}}
        if steps is not None:
            total += sum(steps)
            numerics.update(e_cool=total, e_cool_step=list(steps))
        out.append(numerics)
    return out


def test_by_hand():
    rec = {"cell": "hand", "trace": {"steps": 4, "phase_s_max": {}},
           "events": events([[-1e-5, -2e-5], [-3e-5, 1e-5]], etot=-0.5)}
    with_table("hand", {("sort", "sort~aux"): 12e6,
                        ("sort", "sort~permute"): 40e6,
                        ("sort", "sort~order"): 20e6,
                        ("gravity-mac", "gravity-mac~compact"): 6e8}, 4)
    # 12 ms under sort~aux over 4 traced steps; 5e-5 radiated of |etot| 0.5
    assert read("sort_aux_ms_step", rec) == pytest.approx(3.0)
    assert read("cooling_radiated_share", rec) == pytest.approx(1e-4)


def test_nothing_to_read():
    # an untraced run of a program without the counter; a traced run of a
    # program that sorts no aux state, or sorts without the stages (the
    # parent of PR 38): nothing, and no exception
    assert all(read(n, {"cell": "hand", "trace": None, "events": []}) is None
               for n in NAMES)
    old = {"cell": "hand", "events": events([None, None]),
           "trace": {"steps": 4, "phase_s_max": {"sort": 0.08}}}
    with_table("hand", {("sort", "sort"): 8e7}, 4)
    assert all(read(n, old) is None for n in NAMES)
    # the counter without a trace still reads
    assert read("cooling_radiated_share",
                dict(old, trace=None, events=events([[-1e-3]], etot=2.0))
                ) == pytest.approx(5e-4)


def test_both_are_listed_where_they_read():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["sort_aux_ms_step"]["workloads"] == [CELL]
    assert by_name["cooling_radiated_share"]["workloads"] == [
        "windshock-cooling-4m.steady", CELL]
    assert by_name["sort_aux_ms_step"]["layer"] == "neighbours and SFC"
    assert by_name["cooling_radiated_share"]["layer"] == "cooling"
    assert all(by_name[n]["moves"] == "updates_per_s_chip" for n in NAMES)


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_run(recorded):
    t = recorded["trace"]
    assert recorded["particles"] == 1_098_340 and t["devices"] == 1
    assert t["steps"] == recorded["window"]["traced_steps"] == 4
    with_table(recorded["cell"],
               {tuple(k.split("|")): ns
                for k, ns in recorded["stage_rows"].items()}, t["steps"])
    printed = recorded["result"]["metrics"]
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in run.metrics_of(bench, "per_layer",
                                                recorded["cell"])]
    # the traced run's line holds every per-layer metric listed for the cell
    assert len(listed) == 29 and set(listed) == set(printed)
    for name in NAMES:
        assert read(name, recorded) == pytest.approx(
            printed[name]["value"], rel=1e-6), name
    # the aux gather is a small part of the sort phase, the sort a few per
    # cent of the step; the window radiated of the order of 1e-4 of |etot|
    step = printed["steady_step_ms"]["value"]
    assert (printed["sort_aux_ms_step"]["value"]
            < printed["sort_nbr_ms_step"]["value"] < 0.1 * step)
    assert 1e-5 < printed["cooling_radiated_share"]["value"] < 1e-3
