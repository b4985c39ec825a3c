"""Percent of the harness's ``init-construct`` + ``warm`` spans that the
start-up account names: the seven ``setup_*`` times plus the self time of
``sphexa:construct`` (the coverage of this account, as trace coverage is
of the device's)."""

import startup_spans


def read(run):
    return startup_spans.accounted_share(run)
