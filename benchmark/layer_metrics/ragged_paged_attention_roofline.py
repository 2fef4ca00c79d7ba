"""Kernel ``ragged_paged_attention``: share of its bytes roofline, %.

Bytes-bound: a decode row reads its whole cached context once and does
two small products with it, so the least time is bytes / HBM bandwidth.
Bytes per cycle are the blocks held by live page tables less one block
per active row (``kernel_costs.paged_attention_read_bytes``), counted
ONCE per cycle whatever the kernel re-reads; time is the device time of
every trace event whose name holds the kernel's."""
from benchmark.lib import kernel_costs as K
from benchmark.lib import peaks as P


def read(r):
    cycles = [c for c in r.get("trace_cycles", []) if c.get("active")]
    if not cycles or "trace" not in r:
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items()
               if "ragged_paged_attention" in k)
    if secs <= 0:
        return None
    m, s = r["model"], r["serving"]
    heads = int(m["num_attention_heads"])
    need = sum(K.paged_attention_read_bytes(
        c["blocks_in_use"], c["active"], int(s["block_size"]),
        int(m["num_hidden_layers"]), heads, int(m["hidden_size"]) // heads,
        K.dtype_itemsize(s["dtype"])) for c in cycles)
    least = need / P.peaks_for(r["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / secs
