"""``stage_times.py`` and the fifteen readers PR 33 brought, on a hand-made
capture where the answer is known by inspection and on cuts of PR 33's own
traced chip runs (fixtures/*.stages.json: the (phase, stage) table and the
window's events of calls c33a and c33b)."""

import json
import os
import re

import pytest

import run
import stage_times
import trace_reduce

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
J = "jit(step)/"
MAC = J + "sphexa/gravity-mac/while/body/"
GX = J + "sphexa/gravity-exchange/"
HX = J + "sphexa/halo-exchange/"
NEW = ("grav_prepass_ms_step", "grav_compact_ms_step", "grav_m2p_ms_step",
       "grav_p2p_kernel_ms_step", "halo_wire_ms_step", "halo_cover_ms_step",
       "halo_pack_ms_step", "halo_localize_ms_step",
       "grav_exchange_wire_ms_step", "grav_exchange_pack_ms_step",
       "grav_slab_imbalance", "cell_ranges_ms_step")
FILLS = ("grav_cand_fill", "grav_m2p_fill", "grav_p2p_fill")


def read(name, rec):
    return run.load_reader("layers", name)(rec)


def device(scale=1.0):
    """One device's ops, ns; ``scale`` stretches the tree solve's."""
    ev, t = [], [0.0]

    def op(name, dur, scope, inside=False):
        ev.append([name, t[0], dur, scope])
        if not inside:
            t[0] += dur

    g = scale
    op("fusion.1", 100e6, J + "sphexa/density/mul")
    # the block loop: a while under gravity-mac that encloses its body's ops
    op("while.1", 1000e6 * g, J + "sphexa/gravity-mac/while", inside=True)
    op("compact.1", 200e6 * g,
       MAC + "sphexa/gravity-mac/sphexa/gravity-mac~prepass/pallas_call")
    op("compact.2", 150e6 * g,
       MAC + "sphexa/gravity-mac/sphexa/gravity-mac~compact/pallas_call")
    op("fusion.2", 300e6 * g, MAC + "sphexa/gravity-m2p/dot_general")
    op("copy.1", 60e6 * g, "")  # no scope: the loop's (gravity-mac, gravity-mac)
    op("fusion.3", 40e6 * g, MAC + "sphexa/gravity-p2p/"
       "sphexa/gravity-p2p~leaf-ranges/gather")
    op("gravity-p2p.1", 250e6 * g,
       MAC + "sphexa/gravity-p2p/sphexa/gravity-p2p~kernel/pallas_call")
    # the near field's serve: halo-exchange functions under gravity-exchange
    op("all-reduce.1", 30e6, GX + "sphexa/gravity-exchange~psum/psum")
    op("fusion.4", 80e6, GX + "sphexa/halo-exchange/"
       "sphexa/halo-exchange~localize/sort")
    op("fusion.5", 20e6, GX + "sphexa/halo-exchange/"
       "sphexa/halo-exchange~pack/gather")
    op("collective-permute-start.1", 5e6, GX + "sphexa/halo-exchange/"
       "sphexa/halo-exchange~wire/ppermute")
    op("collective-permute-done.1", 45e6, GX + "sphexa/halo-exchange/"
       "sphexa/halo-exchange~wire/ppermute")
    op("fusion.6", 10e6, GX + "sphexa/gravity-exchange~jbuf/concatenate")
    # the SPH halo: the same functions under their own phase
    op("fusion.7", 16e6, HX + "sphexa/halo-exchange~table/scatter-add")
    op("fusion.8", 24e6, HX + "sphexa/halo-exchange~cover/scatter-add")
    op("fusion.9", 30e6, HX + "sphexa/halo-exchange~localize/sort")
    op("fusion.10", 12e6, HX + "sphexa/halo-exchange~pack/gather")
    op("all-gather.1", 7e6, HX + "sphexa/halo-exchange~wire/all_gather")
    op("fusion.11", 8e6, HX + "sphexa/halo-exchange~jbuf/concatenate")
    op("fusion.12", 3e6, HX + "mul")  # unstaged rest of the phase
    # group_cell_ranges inside the halo stage and inside neighbors
    op("fusion.13", 18e6, HX + "sphexa/neighbors~cell-ranges/gather")
    op("fusion.14", 22e6, J + "sphexa/neighbors/"
       "sphexa/neighbors~cell-ranges/gather")
    op("fusion.15", 9e6, J + "sphexa/neighbors/sphexa/neighbors~windows/min")
    op("copy.2", 4e6, "")  # at top level with no scope: in no row
    return ev, t[0]


@pytest.fixture()
def hand():
    ev0, end = device()
    ev1, _ = device(scale=1.25)
    cap = {"devices": {"0": ev0, "1": ev1},
           "annotations": [[trace_reduce.TRACED, 0.0, end * 1.25]]}
    stage_times.TABLES["hand"] = stage_times.table_of_capture(cap, steps=2)
    yield cap, {"cell": "hand", "trace": {"steps": 2}, "events": [
        {"kind": "window", "cand_fill": 0.30, "m2p_fill": 0.40,
         "p2p_fill": 0.10},
        {"kind": "window", "cand_fill": 0.32, "m2p_fill": 0.44,
         "p2p_fill": 0.12},
        {"kind": "step", "cand_fill": 0.31, "m2p_fill": 0.41,
         "p2p_fill": 0.14},
        {"kind": "exchange", "cand_fill": 9.0}]}
    stage_times.TABLES.clear()


def test_table_by_inspection(hand):
    cap, _ = hand
    rows = stage_times.TABLES["hand"]["devices"]["0"]["rows"]
    ms = {k: v * 1e-6 for k, v in rows.items()}
    # the loop body's ops keep the loop's first phase and their own last token
    assert ms["gravity-mac", "gravity-mac~prepass"] == 200
    assert ms["gravity-mac", "gravity-m2p"] == 300
    assert ms["gravity-mac", "gravity-p2p~kernel"] == 250
    # the while's own self time (1000 - 1000 of children = 0) plus the
    # scopeless copy, which inherits the enclosing while's path
    assert ms["gravity-mac", "gravity-mac"] == pytest.approx(60)
    # a halo-exchange function called under gravity-exchange
    assert ms["gravity-exchange", "halo-exchange~wire"] == 50
    assert ms["halo-exchange", "halo-exchange~wire"] == 7
    assert ms["halo-exchange", "neighbors~cell-ranges"] == 18
    assert ms["neighbors", "neighbors~cell-ranges"] == 22
    assert ms["halo-exchange", "halo-exchange"] == 3
    # the top-level scopeless copy is in no row, but counted
    assert stage_times.TABLES["hand"]["devices"]["0"]["unscoped_ns"] == 4e6
    assert sum(ms.values()) == pytest.approx(
        sum(e[2] for e in cap["devices"]["0"]) * 1e-6 - 1000 - 4)


def test_rows_sum_to_trace_reduce_phases(hand):
    cap, _ = hand
    fixture = trace_reduce.read_capture(os.path.join(
        FIXTURES, "sedov_std_4m_steady.capture.json.gz"))
    for capture in (cap, fixture):
        table = stage_times.table_of_capture(capture, steps=2)
        summary = trace_reduce.reduce_capture(capture, steps=2)
        assert table["window_s"] == pytest.approx(summary["window_s"])
        for phase, s in summary["phase_s_max"].items():
            mine = max(sum(ns for (f, _), ns in d["rows"].items()
                           if f == phase)
                       for d in table["devices"].values())
            assert mine * 1e-9 == pytest.approx(s, rel=1e-9)
        for d in table["devices"].values():
            for phase, ns in d["phase_ns"].items():
                assert ns == pytest.approx(sum(
                    v for (f, _), v in d["rows"].items() if f == phase))


def test_readers_by_inspection(hand):
    _, rec = hand
    # slowest device (1: its tree solve is 1.25 x), over 2 traced steps
    assert read("grav_prepass_ms_step", rec) == pytest.approx(125.0)
    assert read("grav_compact_ms_step", rec) == pytest.approx(93.75)
    assert read("grav_m2p_ms_step", rec) == pytest.approx(187.5)
    assert read("grav_p2p_kernel_ms_step", rec) == pytest.approx(156.25)
    assert read("halo_wire_ms_step", rec) == pytest.approx(3.5)
    assert read("halo_cover_ms_step", rec) == pytest.approx(20.0)  # + table
    assert read("halo_pack_ms_step", rec) == pytest.approx(10.0)   # + jbuf
    assert read("halo_localize_ms_step", rec) == pytest.approx(15.0)
    # wire + psum; everything else of the phase
    assert read("grav_exchange_wire_ms_step", rec) == pytest.approx(40.0)
    assert read("grav_exchange_pack_ms_step", rec) == pytest.approx(55.0)
    # any first phase: the halo stage's 18 + neighbors' 22
    assert read("cell_ranges_ms_step", rec) == pytest.approx(20.0)
    assert read("grav_slab_imbalance", rec) == pytest.approx(1.25)
    assert read("grav_cand_fill", rec) == pytest.approx(0.31)
    assert read("grav_m2p_fill", rec) == pytest.approx(0.41)
    assert read("grav_p2p_fill", rec) == pytest.approx(0.12)


def test_nothing_to_read(hand):
    cap, rec = hand
    untraced = {"cell": "hand", "trace": None, "events": []}
    for name in NEW + FILLS:
        assert read(name, untraced) is None
    # a program from before the stages: the same ops, the stage scopes
    # stripped from every path. The phases read as they did; every new
    # device reader finds nothing; the fills are the program's own
    strip = lambda p: re.sub(r"sphexa/[A-Za-z0-9_.:+-]+~[A-Za-z0-9_.:+-]+/",
                             "", p)
    old = {"devices": {k: [e[:3] + [strip(e[3])] for e in ev]
                       for k, ev in cap["devices"].items()},
           "annotations": cap["annotations"]}
    assert trace_reduce.reduce_capture(old, 2)["phase_s_max"] == \
        pytest.approx(trace_reduce.reduce_capture(cap, 2)["phase_s_max"])
    stage_times.TABLES["hand"] = stage_times.table_of_capture(old, steps=2)
    for name in NEW:
        if name in ("grav_m2p_ms_step", "grav_slab_imbalance"):
            continue  # read a phase, which the old program has too
        assert read(name, rec) is None, name
    assert read("grav_m2p_ms_step", rec) == pytest.approx(187.5)
    assert read("grav_cand_fill", rec) == pytest.approx(0.31)
    # one device: no imbalance to speak of
    one = dict(cap, devices={"0": cap["devices"]["0"]})
    stage_times.TABLES["hand"] = stage_times.table_of_capture(one, steps=2)
    assert read("grav_slab_imbalance", rec) is None


def test_capture_is_parsed_once(hand, monkeypatch):
    _, rec = hand
    monkeypatch.setattr(trace_reduce, "load_capture", lambda d: 1 / 0)
    for name in NEW:
        read(name, rec)  # the cached table; no second parse


RECORDED = sorted(f for f in os.listdir(FIXTURES)
                  if f.endswith(".stages.json"))


@pytest.mark.parametrize("name", RECORDED)
def test_recorded(name):
    """Each new reader on a cut of PR 33's traced chip runs: the table as
    ``table_of_capture`` made it there, the window's fill events, and the
    numbers the run's result line printed."""
    with open(os.path.join(FIXTURES, name)) as f:
        cut = json.load(f)
    table = {"steps": cut["steps"], "window_s": cut["window_s"], "devices": {
        k: {"rows": {tuple(key.split("|")): ns
                     for key, ns in d["rows"].items()},
            "phase_ns": d["phase_ns"], "unscoped_ns": 0.0}
        for k, d in cut["devices"].items()}}
    stage_times.TABLES[cut["cell"]] = table
    try:
        rec = {"cell": cut["cell"], "trace": {"steps": cut["steps"]},
               "events": cut["events"]}
        bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
        listed = [m["name"] for m in run.metrics_of(bench, "per_layer",
                                                    cut["cell"])
                  if m["name"] in NEW + FILLS]
        assert sorted(listed) == sorted(cut["printed"])
        for metric in listed:
            assert read(metric, rec) == pytest.approx(
                cut["printed"][metric], rel=1e-6), metric
        # rows of a first phase = trace_reduce's phase time, per device max
        for phase, s in cut["phase_s_max"].items():
            mine = max(sum(ns for (f, _), ns in d["rows"].items()
                           if f == phase) for d in table["devices"].values())
            assert mine * 1e-9 == pytest.approx(s, rel=1e-3)
    finally:
        stage_times.TABLES.clear()
