"""1 - union of device-op intervals / traced span, on the worst device."""



def read(run):
    t = run["trace"]
    return None if not t else 100.0 * t["idle_share_worst"]
