"""The gated short convolution (a ``conv`` layer's whole operator): share
of its roofline, %.

The least time of the slice's matched launches is the larger of two
(``lib/kernel_costs_lfm2.py``): the operators' weights once a launch and
the real rows in and out (``ssm_rows`` of the launch's record) over the
HBM bandwidth, and two FLOPs a weight a real row over the bf16 peak, all
conv layers. Time is the device time UNDER SECTIONS ``ssm_proj`` +
``ssm_conv`` of the same launches (``lib/launch_trace.py``: an op's
section is its ``tf_op`` scope path) — found by section, never by a
kernel's name, so it reads the same work whatever implements it, and
whatever the implementation does beyond the least (float32 gates, padded
rows, a second read of a product) lowers the share: it cannot pass 100%.
None where the configuration has no ``conv_L_cache``, the program stamps
no ``ssm_rows`` or names no such section."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import kernel_costs_lfm2 as KL
from benchmark.lib import launch_trace as LT
from benchmark.lib import peaks as P


def read(r):
    lt = LT.launch_trace(r)
    m = r.get("model", {})
    if lt is None or "sections" not in lt or "conv_L_cache" not in m:
        return None
    size = K.dtype_itemsize(r["serving"]["dtype"])
    ns, by_bytes, by_flops = 0, 0.0, 0.0
    for n, rec in lt["records"].items():
        if "ssm_rows" not in rec:
            continue
        by = lt["sections"][n]
        ns += by.get("ssm_proj", 0) + by.get("ssm_conv", 0)
        by_bytes += KL.short_conv_bytes(rec["ssm_rows"], m, size)
        by_flops += KL.short_conv_flops(rec["ssm_rows"], m)
    if ns <= 0:
        return None
    peaks = P.peaks_for(r["device_kind"])
    least = max(by_bytes / peaks["hbm_bytes_per_s"],
                by_flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)
