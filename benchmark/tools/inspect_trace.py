#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, event counts and the
first events of each line. Look at one trace by hand before trusting a
reduction of it. ``python3 benchmark/tools/inspect_trace.py <dir or file>``"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import trace_reduce as TR  # noqa: E402


def main(path: str) -> int:
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = TR.latest_xplane(path)
    print(f"file {path} ({os.path.getsize(path):,} bytes)")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for ln in lines:
            events = list(ln.events)
            print(f"  line {ln.name!r}: {len(events)} events")
            for ev in events[:4]:
                print(f"    {ev.name[:70]!r} start_ns {int(ev.start_ns)} "
                      f"dur_ns {int(ev.duration_ns)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
