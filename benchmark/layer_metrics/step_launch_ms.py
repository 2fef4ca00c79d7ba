"""Engine and steps: median length in ms of the
``serving/decode_dispatch`` span over the slice's cycles (the program's
span in the profiler's trace): building the launch's operands on the
host and handing the step program to the device."""
from benchmark.lib import host_spans as HS


def read(r):
    return HS.median_ms(r, "serving/decode_dispatch")
