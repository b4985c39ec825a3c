"""The four list-lifecycle readers on hand-made events: numbers with the
v10 fields, ``None`` on a program without them (RECOVERIES.md)."""

import run

READERS = ("list_rebuilds_step", "list_age_steps", "replayed_steps_share",
           "rebuild_lists_ms")


def span(name, dur_s, it=0, id_=1):
    return {"kind": "span", "name": name, "id": id_, "parent": None,
            "it": it, "t0_ns": 0, "dur_ns": int(dur_s * 1e9)}


def rebuild(reason, age, **kw):
    return {"kind": "rebuild_lists", "it": 0, "reason": reason,
            "age_steps": age, "slack": None, "slot_need": 10,
            "slot_cap": 16, "attempts": 1, **kw}


def record(events, steps=8, attempted=12):
    return {"events": events,
            "window": {"steps_completed": steps, "attempted": attempted}}


def read(name, rec):
    return run.load_reader("layers", name)(rec)


def test_readers_on_v10_events():
    events = [
        rebuild("proactive", 4), span("sphexa:rebuild-lists", 0.7),
        {"kind": "rollback", "it": 8, "steps": 4, "reason": "list-expiry"},
        rebuild("rollback", 7), span("sphexa:rebuild-lists", 0.9),
        rebuild("first", 0), span("sphexa:rebuild-lists", 0.5),
    ]
    rec = record(events)
    assert read("list_rebuilds_step", rec) == 3 / 8
    assert read("list_age_steps", rec) == 5.5  # the first list replaced none
    assert read("replayed_steps_share", rec) == 100.0 * 4 / 12
    assert abs(read("rebuild_lists_ms", rec) - 700.0) < 1e-6


def test_readers_return_none_without_the_fields():
    events = [
        {"kind": "rebuild_lists", "it": 4}, span("sphexa:rebuild-lists", 0.7),
        {"kind": "rollback", "it": 8, "steps": 4, "reason": "list-expiry"},
    ]
    for name in READERS:
        assert read(name, record(events)) is None
        assert read(name, record([])) is None
