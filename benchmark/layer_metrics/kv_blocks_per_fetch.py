"""Kernel ``ragged_paged_attention``: KV blocks a group fetch brings, on
average: sum of ``kv_steps`` over sum of ``kv_fetches`` of the slice's
cycle records (the engine's counters, taken where the launch is built:
``kv_steps`` is q blocks x KV blocks over the planned slots, one DMA of a
whole block each; ``kv_fetches`` is q blocks x ceil(KV blocks / G), the
groups of G blocks the kernel starts together, waits for once and
computes on once). It says how full the groups run: G is 8 at block 16,
so contexts of 128-1,024 tokens read ~7 and a 32-token prompt 2. Nothing
where a record lacks ``kv_fetches`` (a program from before the grouped
walk)."""
from benchmark.lib import host_spans as HS


def read(r):
    counted = [c for c in HS.slice_records(r) if c.get("kv_steps")]
    if not counted or any("kv_fetches" not in c for c in counted):
        return None
    return sum(c["kv_steps"] for c in counted) \
        / sum(c["kv_fetches"] for c in counted)
