"""Engine and steps: getting the executables of the programs built before
the window, s: sum of ``compile_ms`` (the span ``program/compile``:
``lowered.compile()`` — a backend compilation cold, a retrieval from the
persistent cache warm) over ``setup_programs_built``'s events. The log
says which it was: the cache's hits and misses of those compiles, jax's
own events counted around each (a program with Pallas kernels makes more
than one lookup). Nothing on a commit before PR 52."""
from benchmark.lib import harness as H
from benchmark.layer_metrics import setup_programs_built as B


def read(r):
    events = B.built_before(r)
    if events is None:
        return None
    H.log(f"executables of {len(events)} programs: "
          f"{sum(p['cache_hits'] or 0 for p in events)} cache hits, "
          f"{sum(p['cache_misses'] or 0 for p in events)} misses")
    return B.part_s(r, "compile_ms")
