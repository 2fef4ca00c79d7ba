"""State-space mixer: own device ms a launch under the mixer's products —
section ``ssm_proj``: the in-projection and the muP vector, the gated
norm, the out-projection — all layers, over the slice's launches matched
by ``run_id`` (``lib/launch_trace.py``). None where the program names no
such section."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.section_ms(r, "ssm_proj")
