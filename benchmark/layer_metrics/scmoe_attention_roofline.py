"""Kernel ``mla_paged_attention`` under a shortcut-connected layer (two
latent caches a published layer): share of its roofline, %.

As ``mla_attention_roofline.py`` — the larger of the bytes of every
context token of every planned sequence once a cache (``kv_tokens`` x
(rank + rope) values) over the HBM bandwidth and the FLOPs of the
absorbed form over the causal (row, token) pairs (``kv_row_tokens``) over
the bf16 peak, against the device time of every trace event whose name
holds the kernel's — but counted over ``model["attention_layers"]``
caches (``kernel_costs_longcat``): that reader multiplies by
``num_hidden_layers``, which here is the published layers and would
count half the caches. ``None`` for a configuration without the key."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import kernel_costs_longcat as KL
from benchmark.lib import peaks as P


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "kv_row_tokens" in c]
    if not cycles or "trace" not in r \
            or "attention_layers" not in r.get("model", {}):
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items()
               if "mla_paged_attention" in k)
    if secs <= 0:
        return None
    m, peaks = r["model"], P.peaks_for(r["device_kind"])
    size = K.dtype_itemsize(r["serving"]["dtype"])
    by_bytes = sum(KL.mla_read_bytes(c["kv_tokens"], m, size)
                   for c in cycles) / peaks["hbm_bytes_per_s"]
    by_flops = sum(KL.mla_flops(c["kv_row_tokens"], m)
                   for c in cycles) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / secs
