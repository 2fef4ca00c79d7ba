"""Expert layer: the share of the router's choices that fell on identity
("zero-computation") experts, %: sum of ``moe_zero_pairs`` over sum of
``moe_rows`` x ``moe_topk`` of the slice's cycle records (the launch's
counters: real rows only, both already summed over the expert layers).
What the router's width buys: such a choice touches no weights. 256 of
768 outputs give 33.3 under even routing. ``None`` where the records lack
the counter (a program before it) or the configuration has no identity
experts."""
from benchmark.lib import host_spans as HS


def read(r):
    top_k = r.get("model", {}).get("moe_topk")
    if not top_k or not r.get("model", {}).get("zero_expert_num"):
        return None
    counted = [c for c in HS.slice_records(r)
               if "moe_zero_pairs" in c and c.get("moe_rows")]
    if not counted:
        return None
    return 100.0 * sum(c["moe_zero_pairs"] for c in counted) \
        / (sum(c["moe_rows"] for c in counted) * int(top_k))
