"""Kernel ``ragged_paged_attention`` on the GLOBAL layers of a model with
window and global layers: share of its roofline, %.

The least time of the slice's launches is the larger of two: the bytes of
every context token of every planned sequence once a global layer
(``kv_tokens`` x K and V of every KV head, 2,560 B at the published
widths; ``kernel_costs_mimo.attention_read_bytes``) over the HBM
bandwidth, and the FLOPs of a score and a value product a (row, visible
token) pair and query head (``kv_row_tokens``;
``kernel_costs_mimo.attention_flops``) over the bf16 peak. Time is the
device time of the trace events whose name holds
``ragged_paged_attention`` and not the window kernel's. The 64 pad lanes
of a stored row and a chunk's re-reads are the kernel's cost and lower
the share. A program without the window counters (one cache group) has
nothing to read here."""
from benchmark.lib import kernel_costs_mimo as KM


def read(r):
    return KM.roofline_share(r, False)
