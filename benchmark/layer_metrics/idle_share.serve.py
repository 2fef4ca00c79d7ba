"""Device, serving cells: 1 - (union of device-op intervals) / slice,
%, over the traced slice of the window."""


def read(r):
    if "trace" not in r or "records" not in r:
        return None
    t = r["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
