"""Kernel ``ragged_paged_attention`` of a model whose cache only SOME
layers hold: share of its roofline, %.

The least time of the slice's launches is the larger of two
(``lib/kernel_costs_lfm2.py``): the bytes of every context token of every
planned sequence once a CACHE-BEARING layer (``kv_tokens`` x K and V of
every KV head x ``cache_layers`` of the launch's record) over the HBM
bandwidth, and the FLOPs of a score and a value product a (row, visible
token) pair and query head (``kv_row_tokens``) a cache-bearing layer over
the bf16 peak. Time is the device time of every trace event whose name
holds the kernel's. ``gqa_attention_roofline`` multiplies by
``num_hidden_layers`` and would read ``layers / cache_layers`` times over
here (5x on ``lfm2-24b-a2b-pp4``). None where the program stamps no
``cache_layers`` (every layer holds a cache, or the parent)."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import kernel_costs_lfm2 as KL
from benchmark.lib import peaks as P


def read(r):
    cycles = [c for c in r.get("trace_cycles", [])
              if "cache_layers" in c and "kv_row_tokens" in c]
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
    by_bytes = sum(KL.attention_read_bytes(c["kv_tokens"], c["cache_layers"],
                                           m, size)
                   for c in cycles) / peaks["hbm_bytes_per_s"]
    by_flops = sum(KL.attention_flops(c["kv_row_tokens"], c["cache_layers"],
                                      m)
                   for c in cycles) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / secs
