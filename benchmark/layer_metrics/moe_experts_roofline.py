"""The expert layer's grouped products: share of their roofline, %.

The least time of the slice's launches is the larger of: the bytes of the
held experts that got a token (``moe_experts_hit``) plus the rows in and
out of every (row, expert) pair (``moe_pairs``) over the HBM bandwidth,
and the FLOPs of three ``hidden x moe_intermediate`` products a pair over
the bf16 peak (``kernel_costs_axk1``). Time is the device time of the
trace events named ``ragged-dot``: what ``jax.lax.ragged_dot`` is on a
TPU. Rows padded to the product's tile and experts read again by a second
chunk of pairs are the implementation's and lower the share."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import kernel_costs_axk1 as KA
from benchmark.lib import peaks as P


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "moe_pairs" in c]
    if not cycles or "trace" not in r:
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items() if "ragged-dot" in k)
    if secs <= 0:
        return None
    m, peaks = r["model"], P.peaks_for(r["device_kind"])
    size = K.dtype_itemsize(r["serving"]["dtype"])
    by_bytes = sum(KA.moe_bytes(c["moe_experts_hit"], c["moe_pairs"], m, size)
                   for c in cycles) / peaks["hbm_bytes_per_s"]
    by_flops = sum(KA.moe_flops(c["moe_pairs"], m)
                   for c in cycles) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / secs
