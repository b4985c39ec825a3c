"""Median per dump of the device-to-host copies of the dump's fields (the
``sphexa:dump-fetch`` spans of one dump, summed)."""

import program_spans
import windows


def read(run):
    return windows.median(program_spans.per_dump_seconds(
        run["events"], "sphexa:dump-fetch"))
