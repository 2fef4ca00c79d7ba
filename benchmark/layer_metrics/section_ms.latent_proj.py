"""Expert layer: own device ms a launch under the two projections of a
routed layer whose experts run in a latent — ``latent_proj``: into the
latent before the grouped products (``hidden -> moe_latent_size``) and
out of it after them — all expert blocks, over the slice's launches
matched by ``run_id`` (``lib/launch_trace.py``: the section is the op's
``tf_op`` scope path in the trace's metadata). None where the program
names no such section."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.section_ms(r, "latent_proj")
