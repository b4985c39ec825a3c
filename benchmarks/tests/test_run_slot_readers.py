"""The four readers PR 34 brought for the exchanges' run axis
(``halo_run_slots`` / ``halo_run_fill`` and the ``grav_`` pair), on hand-made
records where the answer is known by inspection, and on PR 29's recorded chip
run of evrard-ve-4m-x4.steady, whose program had no such field: every reader
finds nothing there and does not raise, which is what the driver asks of a
metric new in a PR when it runs the parent."""

import json
import os

import pytest

import run

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "evrard_ve_4m_x4_steady.run.json")
NAMES = ("halo_run_slots", "halo_run_fill", "grav_halo_run_slots",
         "grav_halo_run_fill")


def read(name, rec):
    return run.load_reader("layers", name)(rec)


def exchange(stage, slots=None, live=None):
    e = {"kind": "exchange", "it": 8, "steps": 4, "mode": "sparse",
         "shipped_rows": 400, "rows": [90] * 4, "occ": [0.5] * 4,
         "bytes_per_step": 8000, "trips": 0, "stage": stage}
    if slots is not None:
        e.update(run_slots=slots, live_runs_max=live)
    return e


def test_by_hand():
    rec = {"trace": None,
           "events": [exchange("sph", 48, 30), exchange("sph", 48, 33),
                      exchange("sph", 48, 31),
                      exchange("gravity", 168, 96),
                      exchange("gravity", 168, 84),
                      {"kind": "window", "it": 8}]}
    assert read("halo_run_slots", rec) == 48
    assert read("grav_halo_run_slots", rec) == 168
    # the fullest window's fill, each stage its own
    assert read("halo_run_fill", rec) == pytest.approx(33 / 48)
    assert read("grav_halo_run_fill", rec) == pytest.approx(96 / 168)


def test_a_resize_inside_the_window():
    # a trip re-sized the slots between two windows: the median slots, the
    # fullest fill against the slots it was held to
    rec = {"events": [exchange("sph", 24, 23), exchange("sph", 40, 26),
                      exchange("sph", 40, 25)]}
    assert read("halo_run_slots", rec) == 40
    assert read("halo_run_fill", rec) == pytest.approx(23 / 24)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(name):
    # a one-chip cell's events; a program from before the field (the
    # parent of PR 34); a full-width run, which reports no slots
    assert read(name, {"trace": None, "events": []}) is None
    old = {"events": [exchange("sph"), exchange("gravity"),
                      {"kind": "window", "it": 4}]}
    assert read(name, old) is None


@pytest.mark.parametrize("name", NAMES)
def test_recorded_parent_run_reads_nothing(name):
    with open(FIXTURE) as f:
        recorded = json.load(f)
    assert any(e["kind"] == "exchange" for e in recorded["events"])
    assert read(name, recorded) is None


def test_declared_in_the_benchmark():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    x4 = ["sedov-std-8m-x4.steady", "evrard-ve-4m-x4.steady"]
    for name in NAMES:
        m = by_name[name]
        assert (m["source"], m["layer"], m["moves"]) == (
            "program_counter", "multi-chip", "updates_per_s_chip")
        assert m["workloads"] == (x4[1:] if name.startswith("grav_") else x4)
