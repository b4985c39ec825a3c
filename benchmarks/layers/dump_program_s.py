"""Median per dump of the recompute program's time: ``_output_fields``
launched until its result is on the host (``sphexa:dump-program``)."""

import program_spans
import windows


def read(run):
    return windows.median(program_spans.per_dump_seconds(
        run["events"], "sphexa:dump-program"))
