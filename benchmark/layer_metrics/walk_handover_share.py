"""Kernel ``ragged_paged_attention``: share of the slice's walks that began
on a group the walk before them had started, %: sum of
``kv_walks_handed`` over sum of ``kv_walks`` of the slice's cycle records
(the engine's counters, taken where the launch is built, from the
launch's ``blk_seq`` by the rule the kernel follows: a layer's walks of at
least one block, and those of them whose q blocks follow another such
walk's with no pad block between). A walk that is handed its first group
finds it in flight — started from the last trip of the walk before it,
behind that walk's products; one that is not (a launch's first, one after
a pad block) starts its own and waits out a whole DMA latency with
nothing to hide it. 63 of 64 walks of a plain launch of 64 decode rows;
a layout that put pad blocks between sequences would show here first.
Nothing where no record has ``kv_walks`` (a program from before the
hand-over, or a kernel that has none)."""
from benchmark.lib import host_spans as HS


def read(r):
    counted = [c for c in HS.slice_records(r) if c.get("kv_walks")]
    if not counted:
        return None
    return 100.0 * sum(c.get("kv_walks_handed", 0) for c in counted) \
        / sum(c["kv_walks"] for c in counted)
