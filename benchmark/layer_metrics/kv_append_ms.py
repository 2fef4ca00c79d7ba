"""Kernel ``kv_append``: device ms a launch spends writing its tokens'
K|V rows into the block pool, all layers: the own time of every trace
event whose name holds the kernel's (``r["trace"]["ops"]``), over the
slice's launches (the cycle records that carry ``kv_write_blocks``, the
engine's counter of the blocks a launch's real rows land in). The log
line gives the rate at which those blocks move: each is read and written
back whole, once a layer.

Nothing where the trace holds no such op or the records no such counter:
a program whose append is still XLA's scatter (named for the pool's
flattened shape, ``fusion bf16[50964480,128]`` at gpt2-large)."""
from benchmark.lib import harness as H
from benchmark.lib import kernel_costs as K


def read(r):
    cycles = [c for c in r.get("trace_cycles", [])
              if c.get("kv_write_blocks")]
    if not cycles or "trace" not in r:
        return None
    secs = sum(v for k, v in r["trace"]["ops"].items() if "kv_append" in k)
    if secs <= 0:
        return None
    if "model" in r and "serving" in r:
        m, s = r["model"], r["serving"]
        heads = int(m["num_attention_heads"])
        block_bytes = int(s["block_size"]) * K.kv_bytes_per_token(
            int(m["num_hidden_layers"]), heads,
            int(m["hidden_size"]) // heads, K.dtype_itemsize(s["dtype"]))
        blocks = sum(c["kv_write_blocks"] for c in cycles)
        H.log(f"kv_append: {blocks / len(cycles):.1f} blocks a launch a "
              f"layer, {2 * blocks * block_bytes / secs / 1e9:.1f} GB/s "
              f"read and written over {len(cycles)} launches")
    return 1e3 * secs / len(cycles)
