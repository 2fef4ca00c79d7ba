"""Kernel ``ragged_paged_attention``: GB/s at which it gets through the
context its launches must read: the sum of the cycle records'
``kv_tokens`` (the engine's counter: sum of the planned slots'
``kv_len``) times the K and V bytes a cached token holds over all
layers, over the kernel's device time inside those cycles' launches
(device trace, joined to the records by cycle number). The exact count
beside ``ragged_paged_attention_roofline``'s blocks in use less a block
a row; over the chip's HBM bandwidth it is that share."""
from benchmark.lib import host_spans as HS
from benchmark.lib import kernel_costs as K


def read(r):
    if "model" not in r or "serving" not in r:
        return None
    m, s = r["model"], r["serving"]
    heads = int(m["num_attention_heads"])
    return HS.kv_read_gbs(r, K.kv_bytes_per_token(
        int(m["num_hidden_layers"]), heads, int(m["hidden_size"]) // heads,
        K.dtype_itemsize(s["dtype"])))
