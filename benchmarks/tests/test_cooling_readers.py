"""The three readers PR 36 brought for the wind-shock cooling cell
(``cooling_ms_step``, ``cooling_network_ms_step``, ``cooling_dt_ratio``), on
hand-made records where the answer is known by inspection and on a cut of a
traced chip run of windshock-cooling-4m.steady
(fixtures/windshock_cooling_4m_steady.run.json, whose ``what`` says which
run)."""

import json
import os

import pytest

import run
import stage_times

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "windshock_cooling_4m_steady.run.json")
NAMES = ("cooling_ms_step", "cooling_network_ms_step", "cooling_dt_ratio")


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


def events(pairs):
    """``physics`` + ``numerics`` events of windows that ended at dt with the
    window's smallest cooling limit dt_cool."""
    out = []
    for it, (dt, dt_cool) in enumerate(pairs, start=4):
        out.append({"kind": "physics", "it": it, "dt": [dt / 2, dt]})
        numerics = {"kind": "numerics", "it": it, "limiter": {}}
        if dt_cool is not None:
            numerics["dt_cool_min"] = dt_cool
        out.append(numerics)
    return out


def test_by_hand():
    rec = {"cell": "hand", "events": events([(1e-9, 0.2), (4e-9, 0.2),
                                             (2e-9, 0.2)]),
           "trace": {"steps": 4, "phase_s_max": {
               "cooling": 0.028, "momentum-energy": 2.9, "integrate": 0.007}}}
    with_table("hand", {("cooling", "cooling~network"): 24e6,
                        ("cooling", "cooling~limiter"): 4e6,
                        ("momentum-energy", "momentum-energy"): 2.9e9}, 4)
    # 0.028 s under sphexa/cooling over 4 traced steps; 24 of its 28 ms in
    # the network stage; the median window ends at dt 2e-9 of dt_cool 0.2
    assert read("cooling_ms_step", rec) == pytest.approx(7.0)
    assert read("cooling_network_ms_step", rec) == pytest.approx(6.0)
    assert read("cooling_dt_ratio", rec) == pytest.approx(1e-8)


def test_nothing_to_read():
    # an untraced run; a traced run of a program that cools nothing or
    # cools without the stages and the v15 fields (the parent of PR 36)
    assert all(read(n, {"cell": "hand", "trace": None, "events": []}) is None
               for n in NAMES)
    old = {"cell": "hand", "events": events([(1e-9, None)]),
           "trace": {"steps": 4, "phase_s_max": {"iad": 1.0}}}
    with_table("hand", {("iad", "iad"): 1e9}, 4)
    assert all(read(n, old) is None for n in NAMES)
    unstaged = dict(old, trace={"steps": 4, "phase_s_max": {"cooling": 0.1}})
    with_table("hand", {("cooling", "cooling"): 1e8}, 4)
    assert read("cooling_ms_step", unstaged) == pytest.approx(25.0)
    assert read("cooling_network_ms_step", unstaged) is None
    assert read("cooling_dt_ratio", unstaged) is None


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_run(recorded):
    t = recorded["trace"]
    assert recorded["particles"] == 4_037_481 and t["devices"] == 1
    assert t["steps"] == recorded["window"]["traced_steps"] == 4
    with_table(recorded["cell"],
               {tuple(k.split("|")): ns
                for k, ns in recorded["stage_rows"].items()}, t["steps"])
    printed = recorded["result"]["metrics"]
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in run.metrics_of(bench, "per_layer",
                                                recorded["cell"])]
    assert set(NAMES) <= set(listed) and set(NAMES) <= set(printed)
    for name in NAMES:
        assert read(name, recorded) == pytest.approx(
            printed[name]["value"], rel=1e-6), name
    # the network is most of the phase, the phase well under a per cent of
    # the step, and the limiter nowhere near binding in the ramp
    step = printed["steady_step_ms"]["value"]
    assert 0.8 < (printed["cooling_network_ms_step"]["value"]
                  / printed["cooling_ms_step"]["value"]) < 1.0
    assert printed["cooling_ms_step"]["value"] / step < 0.01
    assert printed["cooling_dt_ratio"]["value"] < 1e-6
