"""Scheduler: median length in ms of the ``serving/plan`` span over the
slice's cycles (the program's span in the profiler's trace): the row
plan, block reservation, copy-on-write copies and preemption that come
before the launch is built."""
from benchmark.lib import host_spans as HS


def read(r):
    return HS.median_ms(r, "serving/plan")
