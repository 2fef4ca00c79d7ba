"""Engine and steps: programs built inside the window, by their own time
stamp: the events of ``engine_stats["startup"]["programs"]`` with ``t0 <=
at < t1``. Expected 0; the log names each with the launch that asked for
it, and each build AFTER ``t1`` with how long after (the drain's odd
programs, which ``compiles.serve`` counts because it snapshots a counter
when the benchmark's main thread gets to it). Nothing where the program
keeps no build events (every commit before PR 52)."""
from benchmark.lib import harness as H
from benchmark.layer_metrics import setup_programs_built as B


def _named(p):
    return (f"{p['site'].split('#')[0]} ({p['launch_rows']} rows, "
            f"{p['slots_active']} slots)")


def read(r):
    st = B.startup(r)
    if st is None:
        return None
    t0, t1 = r["t0"], r["t1"]
    inside = [p for p in st["programs"] if t0 <= p["at"] < t1]
    for p in inside:
        H.log(f"built inside the window, {p['at'] - t0:.2f} s in: {_named(p)}")
    for p in st["programs"]:
        if p["at"] >= t1:
            H.log(f"built {p['at'] - t1:.2f} s after the window closed: "
                  f"{_named(p)}")
    return float(len(inside))
