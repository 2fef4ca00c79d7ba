"""State-space mixer: own device ms a launch under the causal depthwise
convolution and its tail — section ``ssm_conv`` — all layers, over the
slice's launches matched by ``run_id`` (``lib/launch_trace.py``). None
where the program names no such section."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.section_ms(r, "ssm_conv")
