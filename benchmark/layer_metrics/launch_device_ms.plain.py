"""Engine and steps: median length in ms of the device's module events
(the ``XLA Modules`` line: one event a program execution) of the slice's
launches that carried decode rows only (``chunk_tokens`` 0), each found by
its ``run_id`` (``lib/launch_trace.py``): a launch's true device time,
with two launches in flight too."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.launch_device_ms(r, chunk=False)
