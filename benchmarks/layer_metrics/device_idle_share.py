"""1 - union of device-op intervals / traced window (device trace)."""


def read(run):
    trace = run["trace"]
    return None if not trace else 100.0 * trace["idle_share"]
