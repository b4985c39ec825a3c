"""Median host wall of one pair-list rebuild (the program's
``sphexa:rebuild-lists`` span: the jitted sort + mark pass and its
overflow fetch), over the window. ``None`` on a program without the v10
list events."""

import list_lifecycle
import windows


def read(run):
    if not list_lifecycle.rebuilds(run["events"]):
        return None
    m = windows.median(list_lifecycle.rebuild_span_seconds(run["events"]))
    return None if m is None else 1e3 * m
