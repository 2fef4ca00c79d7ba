"""Engine and steps, block generation: device ms a launch spends under the
step's last scope (``unmask``: the head on the B rows of every slot's
block, argmax and confidence, the choice of the positions to fix),
averaged over the slice's launches.

The scope's instructions are found in the compiled step programs' text
(``readings["scope_keys"]``, ``lib/scope_ops.py``). A program without the
scope (one token a step) has nothing to read."""
from benchmark.lib import scope_ops as SO
from benchmark.lib import trace_reduce as TR


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "denoise_slots" in c]
    keys = r.get("scope_keys", {}).get("unmask")
    if not cycles or not keys or "slice" not in r:
        return None
    path = TR.latest_xplane(r["slice"]["dir"])
    if path is None:
        return None
    secs = SO.scope_seconds(SO.full_name_events(path), set(keys))
    return 1e3 * secs / len(cycles) if secs > 0 else None
