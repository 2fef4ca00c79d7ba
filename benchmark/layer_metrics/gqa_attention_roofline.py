"""Kernel ``ragged_paged_attention`` on grouped-query heads: share of its
roofline, %.

The least time of the slice's launches is the larger of two: the bytes of
every context token of every planned sequence once a layer (``kv_tokens``
x K and V of every KV head, ``kernel_costs_sdar.gqa_read_bytes``) over the
HBM bandwidth, and the FLOPs of a score and a value product a (row,
visible token) pair and query head (``kv_row_tokens``, counted for the
block mask; ``kernel_costs_sdar.gqa_flops``) over the bf16 peak. Time is
the device time of every trace event whose name holds the kernel's. A
chunk's q blocks re-read their context, and a block of 4 fills half of a
q block's rows: both are the kernel's cost and lower the share. A model
without ``num_key_value_heads`` has no grouped heads to read."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import kernel_costs_sdar as KS
from benchmark.lib import peaks as P


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "kv_row_tokens" in c]
    m = r.get("model", {})
    if not cycles or "trace" not in r or "num_key_value_heads" not in m \
            or "head_dim" not in m:
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items()
               if "ragged_paged_attention" in k)
    if secs <= 0:
        return None
    peaks = P.peaks_for(r["device_kind"])
    size = K.dtype_itemsize(r["serving"]["dtype"])
    by_bytes = sum(KS.gqa_read_bytes(c["kv_tokens"], m, size)
                   for c in cycles) / peaks["hbm_bytes_per_s"]
    by_flops = sum(KS.gqa_flops(c["kv_row_tokens"], m)
                   for c in cycles) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / secs
