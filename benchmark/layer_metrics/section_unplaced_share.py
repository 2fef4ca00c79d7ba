"""Device: busy time inside the matched launches that lies under no
section of the program and no known kernel name, % of their busy time
(``lib/launch_trace.py``; the log line names the largest such ops)."""
from benchmark.lib import launch_trace as LT


def read(r):
    return LT.unplaced_share(r)
