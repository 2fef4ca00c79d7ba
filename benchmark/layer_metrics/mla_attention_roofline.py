"""Kernel ``mla_paged_attention``: share of its roofline, %.

The least time of the slice's launches is the larger of two: the bytes of
every context token of every planned sequence once a layer
(``kv_tokens`` x (rank + rope) values, ``kernel_costs_axk1.mla_read_bytes``)
over the HBM bandwidth, and the FLOPs of the absorbed form over the causal
(row, token) pairs (``kv_row_tokens``, ``kernel_costs_axk1.mla_flops``)
over the bf16 peak. Time is the device time of every trace event whose
name holds the kernel's. A chunk's q blocks re-read their context and a
decode row fills 64 of the MXU's rows: both are the kernel's cost and
lower the share."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import kernel_costs_axk1 as KA
from benchmark.lib import peaks as P


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "kv_row_tokens" in c]
    if not cycles or "trace" not in r:
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items()
               if "mla_paged_attention" in k)
    if secs <= 0:
        return None
    m, peaks = r["model"], P.peaks_for(r["device_kind"])
    size = K.dtype_itemsize(r["serving"]["dtype"])
    by_bytes = sum(KA.mla_read_bytes(c["kv_tokens"], m, size)
                   for c in cycles) / peaks["hbm_bytes_per_s"]
    by_flops = sum(KA.mla_flops(c["kv_row_tokens"], m)
                   for c in cycles) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / secs
