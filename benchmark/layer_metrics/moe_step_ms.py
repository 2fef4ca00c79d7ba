"""Expert layer: device ms a launch spends under the expert layer's scope
(``moe_experts``: routing, the grouped products, the combine and the
shared expert), all expert layers, averaged over the slice's launches.

The scope's instructions are found in the compiled step programs' text
(``readings["scope_keys"]``, ``lib/scope_ops.py``); the grouped products,
whose TPU kernel loses the scope's name, are added by their own
(``ragged-dot``)."""
from benchmark.lib import scope_ops as SO
from benchmark.lib import trace_reduce as TR


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "moe_pairs" in c]
    keys = r.get("scope_keys", {}).get("moe_experts")
    if not cycles or not keys or "slice" not in r:
        return None
    path = TR.latest_xplane(r["slice"]["dir"])
    if path is None:
        return None
    planes = SO.full_name_events(path)
    named = {k for evs in planes.values() for _, _, k in evs
             if "ragged-dot" in k}
    secs = SO.scope_seconds(planes, set(keys) | named)
    return 1e3 * secs / len(cycles) if secs > 0 else None
