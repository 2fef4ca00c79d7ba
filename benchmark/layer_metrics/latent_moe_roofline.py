"""The grouped products of UNGATED experts that run in a latent (Nemotron-H's
LatentMoE): share of their roofline, %.

The least time of the slice's launches is the larger of: the two ``latent
x moe_intermediate`` matrices of every held expert that got a token
(``moe_experts_hit``) plus a pair's row in and out at ``latent`` lanes
(``moe_pairs``) over the HBM bandwidth, and two such products a pair over
the bf16 peak (``lib/kernel_costs_nemotron_h.py``). Time is found as
``moe_experts_roofline`` finds it — the device time of the trace events
named ``ragged-dot``, what ``jax.lax.ragged_dot`` is on a TPU — so rows
padded to the product's tile and experts read again by a second trip
lower the share. (That reader prices three ``hidden x moe_intermediate``
products a pair: 6x over for this form, so a cell lists one of the two.)
None where the configuration names no latent or the program counts no
pairs."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import kernel_costs_nemotron_h as KN
from benchmark.lib import peaks as P


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if "moe_pairs" in c]
    m = r.get("model", {})
    if not cycles or "trace" not in r or "moe_latent_size" not in m:
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items() if "ragged-dot" in k)
    if secs <= 0:
        return None
    peaks = P.peaks_for(r["device_kind"])
    size = K.dtype_itemsize(r["serving"]["dtype"])
    by_bytes = sum(KN.latent_moe_bytes(c["moe_experts_hit"], c["moe_pairs"],
                                       m, size)
                   for c in cycles) / peaks["hbm_bytes_per_s"]
    by_flops = sum(KN.latent_moe_flops(c["moe_pairs"], m)
                   for c in cycles) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / secs
