"""Training loop: median length in ms of the ``hapi/train_batch`` span
over the traced steps (the program's span in the profiler's trace): the
host's share of a step, which the device's step time has to stay above
for the host not to be the wall."""
from benchmark.lib import host_spans as HS


def read(r):
    return HS.median_ms(r, "hapi/train_batch")
