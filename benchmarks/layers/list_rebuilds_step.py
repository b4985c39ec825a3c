"""Pair-list rebuilds per verified step of the window: ``rebuild_lists``
events that built a list (list_lifecycle.py) ÷ steps completed (a count)."""

import list_lifecycle


def read(run):
    built = list_lifecycle.rebuilds(run["events"])
    steps = run["window"]["steps_completed"]
    if not built or not steps:
        return None
    return len(built) / steps
