"""Median per dump of the file write (``sphexa:dump-h5``: open, datasets,
close; summed over the part files of a sharded dump)."""

import program_spans
import windows


def read(run):
    return windows.median(program_spans.per_dump_seconds(
        run["events"], "sphexa:dump-h5"))
