"""Importing jax and bringing the TPU runtime up (``require_tpu``): the
part of set-up that is neither the program's nor the harness's."""

import windows


def read(run):
    return windows.median(
        windows.span_durations(run["setup_spans"], "chip-reach"))
