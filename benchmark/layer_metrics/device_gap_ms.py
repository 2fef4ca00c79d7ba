"""Scheduler: median idle ms between one launch's module event on the
device and the next launch's (both found by ``run_id``,
``lib/launch_trace.py``): what the host's turn costs the device once it
no longer hides behind the launch in flight. The log line splits the
slice's gap time by the ``serving/*`` span that covered it."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.device_gap_ms(r)
