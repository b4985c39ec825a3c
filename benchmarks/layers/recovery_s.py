"""Seconds the driver spent recovering inside the window: the outermost
``sphexa:reconfigure``, ``sphexa:rebuild-lists`` and ``sphexa:rollback``
spans (the time behind the count ``recoveries``)."""

import program_spans


def read(run):
    if not program_spans.spans(run["events"]):
        return None
    return program_spans.seconds(
        program_spans.outermost_recoveries(run["events"]))
