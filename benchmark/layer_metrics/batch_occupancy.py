"""Scheduler: mean of the flight recorder's ``occupancy`` (slots in use
over slots) over the decode cycles of the traced slice, %."""


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if c.get("active")]
    if not cycles:
        return None
    return 100.0 * sum(c["occupancy"] for c in cycles) / len(cycles)
