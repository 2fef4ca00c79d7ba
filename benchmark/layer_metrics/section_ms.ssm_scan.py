"""State-space mixer: own device ms a launch under the recurrence —
section ``ssm_scan``: the one step a sequence of the decode rows, the
chunked scan of a prompt chunk's rows, the ``D`` skip — all layers, over
the slice's launches matched by ``run_id`` (``lib/launch_trace.py``: the
section is the op's ``tf_op`` scope path in the trace's metadata). None
where the program names no such section."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.section_ms(r, "ssm_scan")
