"""The window arithmetic on a recorded ``events`` list (fixtures/: the
measured window of PR 22's traced run of sedov-std-4m.steady on a v5e) and
on hand-made lists for the recovery cases that run did not have."""

import json
import os

import pytest

import windows

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "sedov_std_4m_steady.events.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_window(recorded):
    ev = recorded["events"]
    clean = windows.clean_step_seconds(ev)
    assert len(clean) == 6 and windows.median(clean) == pytest.approx(
        1.38480, abs=1e-4)
    assert windows.steps_attempted(ev) == 24
    assert windows.recoveries(ev) == 0
    assert windows.unexplained_retraces(ev) == []
    # the driver's spans cover all but the profiler start/stop and the
    # per-window host work
    wall = recorded["wall_s"] - windows.span_seconds(
        recorded["spans"], "dump", "trace-start", "trace-stop")
    gap = 1.0 - windows.device_span_seconds(ev) / wall
    assert 0.0 < gap < 0.005


EVENTS = [
    {"kind": "launch"}, {"kind": "launch"},
    {"kind": "window", "steps": 2, "wall_s": 2.0, "per_step_s": 1.0},
    {"kind": "rebuild_lists"},
    {"kind": "launch"}, {"kind": "launch"},
    {"kind": "rollback", "steps": 2},
    {"kind": "reconfigure", "reason": "overflow"},
    {"kind": "retrace", "delta": 1},
    {"kind": "step", "wall_s": 9.0}, {"kind": "step", "wall_s": 1.1},
    {"kind": "replay", "steps": 2},
    {"kind": "launch"}, {"kind": "launch"},
    {"kind": "window", "steps": 2, "wall_s": 2.2, "per_step_s": 1.1},
]


def test_clean_window_rule():
    # the window before the rollback is clean; the first replayed step
    # follows the recovery (dirty), the second and the next window do not
    assert windows.clean_step_seconds(EVENTS) == [1.0, 1.1, 1.1]


def test_recoveries_attempts_and_spans():
    assert windows.recoveries(EVENTS) == 3
    assert windows.steps_attempted(EVENTS) == 8  # 6 launches + 2 replays
    assert windows.device_span_seconds(EVENTS) == pytest.approx(14.3)


def test_retrace_is_explained_only_after_a_recovery():
    assert windows.unexplained_retraces(EVENTS) == []
    stray = [{"kind": "window", "wall_s": 1, "per_step_s": 1},
             {"kind": "retrace", "delta": 1}, {"kind": "rollback"}]
    assert windows.unexplained_retraces(stray) == [stray[1]]


def test_cycle_closing():
    # closes at the end of the cycle running when the time is up ...
    assert not windows.should_close(29.9, 5, seconds=30, min_cycles=1)
    assert windows.should_close(30.1, 5, seconds=30, min_cycles=1)
    # ... and never before min_cycles whole cycles
    assert not windows.should_close(31.0, 2, seconds=30, min_cycles=3)
    assert windows.should_close(36.0, 3, seconds=30, min_cycles=3)


def test_median_and_spans():
    assert windows.median([]) is None
    assert windows.median([3.0, 1.0, 2.0]) == 2.0
    assert windows.median([4.0, 1.0, 2.0, 3.0]) == 2.5
    spans = [{"name": "dump", "t0": 1.0, "t1": 3.5},
             {"name": "cycle", "t0": 0.0, "t1": 9.0},
             {"name": "dump", "t0": 10.0, "t1": 12.0}]
    assert windows.span_durations(spans, "dump") == [2.5, 2.0]
    assert windows.span_seconds(spans, "dump", "trace-stop") == 4.5


def _cycles(walls, recoveries=None, dumped=None):
    n = len(walls)
    return [{"wall_s": w, "recoveries": r, "dumped": d} for w, r, d in zip(
        walls, recoveries or [0] * n, dumped or [False] * n)]


def test_window_seconds_ignores_a_host_stall():
    # six equal cycles, one held up by the host for 0.6 s
    quiet = _cycles([5.541, 5.542, 5.541, 5.542, 5.541, 5.542])
    stalled = _cycles([5.541, 5.542, 6.141, 5.542, 5.541, 5.542])
    assert windows.window_seconds(quiet) == pytest.approx(6 * 5.5415)
    assert windows.window_seconds(stalled) == pytest.approx(6 * 5.542)


def test_window_seconds_keeps_the_programs_own_recoveries():
    # a list rebuild in cycle 3 queues device work that lands in cycle 4:
    # both count at their own wall, the other four as their median
    c = _cycles([5.5, 5.5, 5.6, 6.1, 5.5, 5.9], recoveries=[0, 0, 1, 0, 0, 0])
    assert windows.window_seconds(c) == pytest.approx(5.6 + 6.1 + 4 * 5.5)
    # nothing but recoveries: the plain sum
    c = _cycles([5.0, 7.0, 6.0], recoveries=[1, 2, 1])
    assert windows.window_seconds(c) == pytest.approx(18.0)
    assert windows.window_seconds([]) == 0.0


def test_window_seconds_takes_the_median_of_each_kind_of_cycle():
    # a dump every second cycle: cycles with and without are two kinds
    c = _cycles([5.5, 6.8, 5.5, 7.9, 5.6, 6.9],
                dumped=[False, True, False, True, False, True])
    assert windows.window_seconds(c) == pytest.approx(3 * 5.5 + 3 * 6.9)
