"""The two readers PR 39 brought for the compaction kernel's live chunks
(``grav_prepass_chunk_live`` / ``grav_compact_chunk_live``), on hand-made
events where the answer is known by inspection, and on the window events of
PR 33's recorded chip run of evrard-ve-1m.steady, whose program had the three
fills and no such field: both find nothing there and do not raise, which is what the driver asks of a metric new
in a PR when it runs the parent."""

import json
import os

import pytest

import run

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "evrard_ve_1m_steady.stages.json")
NAMES = ("grav_prepass_chunk_live", "grav_compact_chunk_live")


def read(name, rec):
    return run.load_reader("layers", name)(rec)


def test_by_hand():
    fills = dict(cand_fill=0.4, m2p_fill=0.5, p2p_fill=0.3)
    rec = {"trace": None, "events": [
        {"kind": "window", **fills, "prepass_chunk_live": 0.131,
         "compact_chunk_live": 0.89},
        {"kind": "window", **fills, "prepass_chunk_live": 0.135,
         "compact_chunk_live": 0.91},
        {"kind": "step", **fills, "prepass_chunk_live": 0.133,
         "compact_chunk_live": 0.90},
        # another kind's field of the same name is not the solve's
        {"kind": "exchange", "prepass_chunk_live": 9.0}]}
    assert read("grav_prepass_chunk_live", rec) == pytest.approx(0.133)
    assert read("grav_compact_chunk_live", rec) == pytest.approx(0.90)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(name):
    # no event; a gravity-free cell's window; the parent's window, which
    # carries the three fills and no share
    assert read(name, {"events": []}) is None
    assert read(name, {"events": [{"kind": "window", "it": 8}]}) is None
    with open(FIXTURE) as f:
        parent = json.load(f)
    assert any("cand_fill" in e for e in parent["events"])
    assert read(name, parent) is None
