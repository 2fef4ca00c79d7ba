"""Scheduler: share of the slice's launches that were dispatched while
the launch before them was still un-fetched (the flight recorder's
``overlapped``), %. Near 100 while two launches are in flight and the
device goes from one to the next with no host in between; every drain of
the pipeline (pool pressure, an idle queue) takes one launch off it.
Nothing from a program whose records do not say (an older commit)."""
from benchmark.lib import host_spans as HS


def read(r):
    launches = [c for c in HS.slice_records(r)
                if c.get("launch_q") and "overlapped" in c]
    if not launches:
        return None
    return 100.0 * sum(1 for c in launches if c["overlapped"]) / len(launches)
