"""util/timer.py coverage: Timer lap accumulation, ProfileRecorder
save/summary NaN-padding for ragged rows, and the thin-adapter contract
over the telemetry registry (popped laps become one ``phases`` event)."""

import os
import time

import numpy as np

from sphexa_tpu.telemetry import MemorySink, Telemetry
from sphexa_tpu.util.timer import ProfileRecorder, Timer


class TestTimer:
    def test_step_accumulates_and_pop_clears(self):
        t = Timer()
        t.start()
        e1 = t.step("a")
        e2 = t.step("a")
        t.step("b")
        assert e1 >= 0.0 and e2 >= 0.0
        laps = t.pop()
        assert set(laps) == {"a", "b"}
        # two laps under the same name accumulate (timer.hpp:46)
        assert laps["a"] >= e1 + e2 - 1e-9
        assert t.pop() == {}  # pop clears

    def test_step_measures_elapsed(self):
        t = Timer()
        t.start()
        time.sleep(0.01)
        assert t.step("sleep") >= 0.009

    def test_start_resets_mark(self):
        t = Timer()
        time.sleep(0.01)
        t.start()
        assert t.step("a") < 0.009

    def test_popped_laps_become_a_phases_event(self):
        sink = MemorySink()
        tel = Telemetry(sinks=[sink])
        t = Timer()
        t.start()
        t.step("phase")
        t.step("phase")
        tel.phases(7, t.pop())
        (e,) = sink.of_kind("phases")
        assert e["it"] == 7 and e["phase"] >= 0.0
        assert tel.counters["events.phases"] == 1


class TestProfileRecorder:
    def test_save_empty_writes_nothing(self, tmp_path):
        p = ProfileRecorder()
        path = str(tmp_path / "profile.npz")
        assert p.save(path) is False
        assert not os.path.exists(path)

    def test_ragged_rows_nan_padded(self, tmp_path):
        p = ProfileRecorder()
        p.record(1, {"step": 0.5}, dt=0.1)
        p.record(2, {"step": 0.7, "output": 0.2}, dt=0.3)
        path = str(tmp_path / "profile.npz")
        assert p.save(path) is True
        data = np.load(path)
        np.testing.assert_array_equal(data["iteration"], [1.0, 2.0])
        np.testing.assert_allclose(data["step"], [0.5, 0.7])
        # 'output' missing from row 1 -> NaN, not a shape error
        assert np.isnan(data["output"][0]) and data["output"][1] == 0.2

    def test_summary_nanmean_skips_missing(self):
        p = ProfileRecorder()
        p.record(1, {"step": 0.5})
        p.record(2, {"step": 0.7, "output": 0.2})
        s = p.summary()
        assert s["step"] == np.float64(0.6).item()
        assert s["output"] == 0.2  # mean over present rows only
        assert "iteration" not in s

    def test_summary_empty(self):
        assert ProfileRecorder().summary() == {}

    def test_rows_emit_phases_events(self):
        sink = MemorySink()
        p = ProfileRecorder(telemetry=Telemetry(sinks=[sink]))
        p.record(3, {"step": 0.5}, dt=0.1)
        (e,) = sink.of_kind("phases")
        assert e["it"] == 3 and e["step"] == 0.5 and e["dt"] == 0.1
