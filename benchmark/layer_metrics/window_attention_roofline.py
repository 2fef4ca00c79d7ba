"""Kernel ``ragged_paged_attention_window`` (the sliding-window layers):
share of its roofline, %.

As ``global_attention_roofline``, from what a WINDOW layer must read —
``kv_tokens_window``: a slot's last ``sliding_window - 1`` tokens and the
launch's rows, x K and V of every KV head (5,120 B at the published
widths), once a window layer — and the pairs under the window mask
(``kv_row_tokens_window``), over the device time of the trace events
named ``ragged_paged_attention_window``. A walk that started at block 0
would read this low by the ratio of the context to the window."""
from benchmark.lib import kernel_costs_mimo as KM


def read(r):
    return KM.roofline_share(r, True)
