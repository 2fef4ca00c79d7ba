"""Kernels: device ms a launch spends in ``ragged_paged_attention_window``
(the sliding-window layers, all of them), averaged over the slice's
launches that carry the window counters. With the walk started at the
window a decode row costs ``ceil(W / block) + 1`` blocks whatever its
context; started at block 0 this would be several times the global
layers' kernel."""
from benchmark.lib import kernel_costs_mimo as KM


def read(r):
    cycles = [c for c in r.get("trace_cycles", [])
              if "kv_tokens_window" in c]
    if not cycles or "trace" not in r:
        return None
    secs = KM.kernel_seconds(r["trace"]["ops"], True)
    return 1e3 * secs / len(cycles) if secs > 0 else None
