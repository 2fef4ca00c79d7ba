"""Device, training cells: ``memory_stats()["peak_bytes_in_use"]`` after
the window, GB (1e9 bytes), before the reference runs."""


def read(r):
    if "steps" not in r:
        return None
    return r["device"]["memory_peak_bytes"] / 1e9
