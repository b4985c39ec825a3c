"""Device idle share of the traced cycle of a cell that dumps (1 - union of
device-op intervals / traced span, worst device): the device waits while
the host copies and writes. The same reading as device_idle_share, under
the metric it moves there (dump_s)."""


def read(run):
    t = run["trace"]
    return None if not t else 100.0 * t["idle_share_worst"]
