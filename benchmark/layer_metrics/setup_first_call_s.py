"""Engine and steps: the first call of each fresh executable built before
the window, s: sum of ``first_call_ms`` (the span ``program/first_call``:
the call's own wall, no sync added — what the runtime does before it
returns, such as loading the executable onto the device) over
``setup_programs_built``'s events. Nothing on a commit before PR 52."""
from benchmark.layer_metrics import setup_programs_built as B


def read(r):
    return B.part_s(r, "first_call_ms")
