"""Engine and steps: median length in ms of the device's module events
of the slice's launches that carried a prompt chunk, each found by its
``run_id`` (``lib/launch_trace.py``)."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.launch_device_ms(r, chunk=True)
