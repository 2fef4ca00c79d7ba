"""Engine and steps: tracing the step programs built before the window,
s: sum of ``trace_ms`` (the span ``program/trace``: ``jitted.trace`` of
one ``(Q, T)`` program, every kernel's Python run once) over
``setup_programs_built``'s events. What a kernel's spelling costs a
program, cold or warm. Nothing on a commit before PR 52."""
from benchmark.layer_metrics import setup_programs_built as B


def read(r):
    return B.part_s(r, "trace_ms")
