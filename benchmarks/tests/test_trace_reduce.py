"""The trace reducer on a small recorded TPU capture (fixtures/: cut from
PR 22's traced run of sedov-std-4m.steady on a v5e) and on hand-made
events where the answer is known by inspection."""

import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "sedov_std_4m_steady.capture.json.gz")


@pytest.fixture(scope="module")
def summary():
    return tr.reduce_capture(tr.read_capture(FIXTURE), steps=1)


def test_recorded_busy_union_and_idle(summary):
    # one 1.385 s step inside a 1.45 s cut
    assert summary["window_s"] == pytest.approx(1.45)
    assert summary["busy_s"] == pytest.approx(1.38455, abs=1e-4)
    assert summary["idle_share_worst"] == pytest.approx(
        1.0 - summary["busy_s"] / summary["window_s"])
    assert summary["devices"] == 1


def test_recorded_phase_grouping_and_coverage(summary):
    ph = summary["phase_s_max"]
    # the three Mosaic pair kernels carry their sphexa/<phase> scope
    assert ph["momentum-energy"] == pytest.approx(0.60988, abs=1e-4)
    assert ph["iad"] == pytest.approx(0.41830, abs=1e-4)
    assert ph["density"] == pytest.approx(0.34375, abs=1e-4)
    assert summary["coverage_min"] > 0.99
    assert sum(ph.values()) == pytest.approx(
        summary["coverage_min"] * summary["busy_s"], rel=1e-9)
    pairs = tr.phase_ms_per_step(
        summary, ("density", "iad", "momentum-energy"))
    assert pairs == pytest.approx(1371.9, abs=0.1)
    assert summary["device_ops"][0][0] == "momentum-energy.1"


def test_recorded_gap_labelling(summary):
    gaps = dict(summary["idle_gaps"])
    # idle seconds add up to window - busy, and most of them fall inside
    # the driver's flush (the host waiting in device_get)
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-9)
    assert max(gaps, key=gaps.get) == "sphexa:flush"
    assert set(gaps) <= {"sphexa:flush", "sphexa:launch", "unattributed"}


def _capture(events, annotations):
    return {"devices": {"0": events}, "annotations": annotations}


def test_nested_ops_use_self_time_and_inherit_the_loop_phase():
    # a while op [0, 100) whose body ops [10, 30) and [40, 90) nest in it
    events = [
        ["while.1", 0.0, 100.0, "jit(step)/sphexa/gravity-mac/while"],
        ["fusion.1", 10.0, 20.0, ""],
        ["fusion.2", 40.0, 50.0, "jit(step)/sphexa/gravity-m2p/mul"],
        ["copy.3", 150.0, 50.0, ""],
    ]
    s = tr.reduce_capture(_capture(events, [[tr.TRACED, 0.0, 300.0]]),
                          steps=2)
    assert s["busy_s"] == pytest.approx(150e-9)
    ph = s["phase_s_max"]
    # while keeps its self time (30) plus the unscoped body op (20)
    assert ph["gravity-mac"] == pytest.approx(50e-9)
    assert ph["gravity-m2p"] == pytest.approx(50e-9)
    assert s["coverage_min"] == pytest.approx(100.0 / 150.0)
    assert tr.phase_ms_per_step(s, ("gravity-mac", "gravity-m2p")) \
        == pytest.approx(1e3 * 100e-9 / 2)


def test_gaps_split_over_innermost_host_annotation():
    events = [["a", 0.0, 10.0, ""], ["b", 60.0, 10.0, ""]]
    annotations = [
        [tr.TRACED, 0.0, 100.0],
        ["bench:cycle", 0.0, 100.0],
        ["bench:dump", 10.0, 40.0],
        ["bench:dump-write", 30.0, 20.0],
    ]
    s = tr.reduce_capture(_capture(events, annotations), steps=1)
    gaps = dict(s["idle_gaps"])
    assert gaps["bench:dump"] == pytest.approx(20e-9)        # [10, 30)
    assert gaps["bench:dump-write"] == pytest.approx(20e-9)  # [30, 50)
    assert gaps["unattributed"] == pytest.approx(40e-9)  # [50,60)+[70,100)


def test_two_devices_report_the_worst_idle_and_the_mean_busy():
    cap = {"devices": {"0": [["a", 0.0, 80.0, "sphexa/halo-exchange"]],
                       "1": [["a", 0.0, 40.0, "sphexa/halo-exchange"]]},
           "annotations": [[tr.TRACED, 0.0, 100.0]]}
    s = tr.reduce_capture(cap, steps=1)
    assert s["busy_s"] == pytest.approx(60e-9)
    assert s["idle_share_worst"] == pytest.approx(0.6)
    assert s["phase_s_max"]["halo-exchange"] == pytest.approx(80e-9)


def test_no_device_events_reduce_to_nothing():
    assert tr.reduce_capture(_capture([], [[tr.TRACED, 0.0, 1.0]]), 1) is None
    assert tr.phase_ms_per_step(None, ("density",)) is None
