"""Engine and steps: lowering the step programs built before the window,
s: sum of ``lower_ms`` (the span ``program/lower``: the traced program to
MLIR, the kernels' Mosaic modules included) over
``setup_programs_built``'s events. Nothing on a commit before PR 52."""
from benchmark.layer_metrics import setup_programs_built as B


def read(r):
    return B.part_s(r, "lower_ms")
