#!/usr/bin/env python3
"""Lay the program's spans beside the device's busy intervals, cycle by
cycle, from one serving trace: where each span of a cycle starts and how
long it is, when the launch's first device op starts and its last one
ends, and which spans the device's idle time falls under (by overlap, a
gap split between the spans it crosses). Look at this before trusting a
reader. ``python3 benchmark/tools/cycle_timeline.py <trace dir>
[<out.json>]`` writes the spans, the merged busy intervals and the
ragged kernel's own to ``out.json`` too. The sums are
``lib/host_spans.py``'s; this only prints."""
import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import host_spans as HS  # noqa: E402
from benchmark.lib import trace_reduce as TR  # noqa: E402


def main(trace_dir: str, out_json: str = None) -> int:
    path = TR.latest_xplane(trace_dir)
    spans, kernel = HS.read_trace(path)
    reduced = TR.reduce_trace(path, 1.0)
    intervals = reduced["intervals"]
    readings = {"host_spans": spans, "kernel_intervals": kernel,
                "trace": reduced}
    print(f"file {path}: {len(spans)} spans, {len(intervals)} busy intervals")
    starts = [s for s, _ in intervals]
    cycles = HS.by_cycle(readings, HS.CYCLE)
    for n, (lo, hi) in sorted(cycles.items()):
        parts = " ".join(
            f"{name[8:]}@{(s - lo) / 1e6:.2f}+{(e - s) / 1e6:.2f}"
            for s, e, name, a in spans
            if a.get("cycle") == n and name != HS.CYCLE)
        i = bisect.bisect_left(starts, lo)
        j = bisect.bisect_left(starts, hi) - 1
        dev = (f"device {(intervals[i][0] - lo) / 1e6:.2f}.."
               f"{(intervals[j][1] - lo) / 1e6:.2f}" if i <= j else "device -")
        print(f"cycle {n} ({(hi - lo) / 1e6:.2f} ms): {parts} | {dev}")
    gaps = TR.gaps(reduced)
    idle = sum(e - s for s, e in gaps)
    longest = sorted((e - s for s, e in gaps), reverse=True)[:len(cycles) + 2]
    print(f"idle {idle / 1e6:.2f} ms in {len(gaps)} gaps; the longest: "
          f"{[round(g / 1e6, 2) for g in longest]}")
    by_span = HS.idle_by_span(readings) or {}
    for name, ns in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"  idle under {name}: {ns / 1e6:.2f} ms "
              f"({100.0 * ns / sum(by_span.values()):.1f}%)")
    print(f"clock check: {HS.clock_check(readings)}")
    print(f"idle_unplaced_share: {HS.unplaced_idle_share(readings)}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump({"spans": spans, "intervals": intervals,
                       "kernel": kernel}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
