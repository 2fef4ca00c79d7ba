"""Device: share (%) of the slice's device idle time that lies under no
span of the scheduler thread (``serving/wait`` between cycles, and the
children of a ``serving/cycle``), by overlap: a stretch the program's
spans do not cover, so what they still cannot explain."""
from benchmark.lib import host_spans as HS


def read(r):
    return HS.unplaced_idle_share(r)
