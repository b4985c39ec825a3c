"""``list_run_fill`` (PR 40) on recorded ``rebuild_lists`` events where the
answer is known by inspection, and on events of a program from before schema
v18, where the reader finds nothing and does not raise: what the driver asks
of a metric new in a PR when it runs the parent."""

import pytest

import run


def read(rec):
    return run.load_reader("layers", "list_run_fill")(rec)


def rebuild(it, reason="proactive", **v18):
    # a v12 event as noh-std-1m.steady's window holds twelve of (PR 28's
    # recorded run), with the v18 fields where given
    e = {"kind": "rebuild_lists", "it": it, "reason": reason,
         "age_steps": 7, "slack": 0.31, "slot_need": 231, "slot_cap": 296,
         "slots_live": 600216, "slots_cap": 2375680, "attempts": 1,
         "rate": 0.09, "cover_steps": 8}
    e.update(v18)
    return e


def test_the_newest_rebuild_of_the_window():
    rec = {"trace": None, "events": [
        rebuild(12, chunks_live=500000, runs_live=200000, run_rows=4),
        {"kind": "window", "it": 16, "steps": 4},
        rebuild(19, chunks_live=545000, runs_live=210000, run_rows=4),
        {"kind": "window", "it": 20, "steps": 4}]}
    assert read(rec) == pytest.approx(545000 / (210000 * 4))


def test_the_parents_shape_reads_low():
    # thirteen rows a run for 3.2 chunks kept: a quarter of the rows read
    rec = {"events": [rebuild(8, chunks_live=2148480, runs_live=673280,
                              run_rows=13)]}
    assert read(rec) == pytest.approx(0.2455, abs=1e-4)


@pytest.mark.parametrize("events", [
    [],                                            # no list cell
    [{"kind": "window", "it": 8, "steps": 4}],      # no rebuild in the window
    [rebuild(8)],                                   # a program before v18
    [{"kind": "rebuild_lists", "it": 8}],           # ... and before v10
], ids=["empty", "no-rebuild", "v12", "v9"])
def test_nothing_to_read(events):
    assert read({"trace": None, "events": events}) is None
