"""Kernels ``flash_attention_fwd/dq/dkv``: share of their compute
roofline, %. Compute-bound at sequence 1,024: causal FLOPs of the six
products the algorithm needs (``kernel_costs.flash_train_flops``) for
the traced steps, over the bf16 peak, over the three kernels' device
time."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import peaks as P


def read(r):
    if "trace" not in r or "steps" not in r:
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items()
               if "flash_attention" in k)
    if secs <= 0:
        return None
    m = r["model"]
    heads = int(m["num_attention_heads"])
    flops = r["trace_steps"] * K.flash_train_flops(
        r["batch"], heads, r["seq"], int(m["hidden_size"]) // heads,
        int(m["num_hidden_layers"]))
    least = flops / P.peaks_for(r["device_kind"])["bf16_flops_per_s"]
    return 100.0 * least / secs
