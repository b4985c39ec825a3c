"""The reader PR 31 brought for the turbulence cell (``stirring_ms_step``), on
hand-made records where the answer is known by inspection and on a cut of a
traced chip run of turb-ve-8m.steady (fixtures/turb_ve_8m_steady.run.json,
whose ``what`` says which run: the final tree of PR 31, call c31d)."""

import json
import os

import pytest

import run

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "turb_ve_8m_steady.run.json")


def read(rec):
    return run.load_reader("layers", "stirring_ms_step")(rec)


def test_by_hand():
    rec = {"trace": {"steps": 4, "phase_s_max": {
        "turbulence": 0.1, "momentum-energy": 5.0, "integrate": 0.02}}}
    # 0.1 s of device self time under sphexa/turbulence over 4 traced steps
    assert read(rec) == pytest.approx(25.0)


def test_nothing_to_read():
    # an untraced run; a traced run of a program that stirs nothing (every
    # other cell: the parent of PR 31 included); a trace with no step in it
    assert read({"trace": None}) is None
    assert read({"trace": {"steps": 4, "phase_s_max": {"iad": 1.0}}}) is None
    assert read({"trace": {"steps": 0,
                           "phase_s_max": {"turbulence": 0.1}}}) is None


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_run(recorded):
    t = recorded["trace"]
    assert recorded["particles"] == 8_000_000 and t["devices"] == 1
    assert t["steps"] == recorded["window"]["traced_steps"] == 4
    want = 1e3 * t["phase_s_max"]["turbulence"] / t["steps"]
    assert read(recorded) == pytest.approx(want)
    assert want == pytest.approx(
        recorded["result"]["metrics"]["stirring_ms_step"]["value"])
    # the stirring is a per cent of the step, as the issue's arithmetic said
    step = recorded["result"]["metrics"]["steady_step_ms"]["value"]
    assert 0.001 < want / step < 0.02
