"""Scheduler: median length in ms of the ``serving/emit`` span over the
slice's cycles (the program's span in the profiler's trace): the
per-slot loop after the fetch that advances, emits and retires."""
from benchmark.lib import host_spans as HS


def read(r):
    return HS.median_ms(r, "serving/emit")
