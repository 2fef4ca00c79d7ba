"""Device, training cells: 1 - (union of device-op intervals) / slice,
%, over the traced steps."""


def read(r):
    if "trace" not in r or "steps" not in r:
        return None
    t = r["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
