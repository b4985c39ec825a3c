"""Median number of verified steps a pair list served before the rebuild
that replaced it (``rebuild_lists.age_steps``, list_lifecycle.py)."""

import list_lifecycle
import windows


def read(run):
    return windows.median(list_lifecycle.ages(run["events"]))
