"""Engine and steps: own device ms a launch under the layer norms and the
final one, all layers, over the slice's launches matched by ``run_id``
(``lib/launch_trace.py``: the section is the op's ``tf_op`` scope path in
the trace's metadata)."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.section_ms(r, "norm")
