"""Expert layer: own device ms a launch under the identity experts' term
(``moe_experts/zero_experts``: the mask of the choices past the experts
with weights, their weights' sum, the scale of the row), all expert
layers, over the slice's launches matched by ``run_id``
(``lib/launch_trace.py``). ``None`` where the program names no such
section."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.section_ms(r, "zero_experts")
