"""Scheduler: median per cycle of cycle_ms - decode_dispatch_ms -
fetch_ms from the flight recorder (host clock): what a cycle spends
outside launching the step and waiting for its tokens."""
from benchmark.lib import stats as S


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if c.get("active")]
    if not cycles:
        return None
    return S.median([c["cycle_ms"] - c["decode_dispatch_ms"] - c["fetch_ms"]
                     for c in cycles])
