"""Operations and bytes that the ALGORITHM of grouped-query attention
over a paged cache needs, from shapes and the launch counters — the least
the work requires, as in ``lib/kernel_costs.py``: re-reads, padding to
tiles and rows no sequence owns are the implementation's own cost and
lower its roofline share, so no share can read over 100%.

Everything is counted from the KV heads and ``head_dim`` the
configuration states, never from ``hidden_size // num_attention_heads``
(64 here, half the real head): a cached token is ``num_key_value_heads``
rows of K and of V, whatever the number of query heads reading them.
"""
from __future__ import annotations


def kv_bytes_per_token(model: dict, itemsize: int) -> int:
    """Bytes ONE layer's cache holds a token: K and V of every KV head."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) \
        * int(itemsize)


def gqa_read_bytes(kv_tokens: int, model: dict, itemsize: int) -> float:
    """Bytes the attention kernel must at least read in one launch over
    all layers: every context token of every planned sequence once
    (``kv_tokens`` of the cycle record), whatever the q blocks of a chunk
    re-read."""
    return float(kv_tokens) * kv_bytes_per_token(model, itemsize) \
        * int(model["num_hidden_layers"])


def gqa_flops(kv_row_tokens: int, model: dict) -> float:
    """FLOPs of one launch over all layers: per (query row, visible
    cached token) pair and QUERY head, a score and a value product over
    ``head_dim`` lanes, two FLOPs a multiply-add (``kv_row_tokens`` of the
    cycle record counts the pairs of the block mask exactly)."""
    return float(kv_row_tokens) * int(model["num_attention_heads"]) \
        * 2 * int(model["head_dim"]) * 2.0 * int(model["num_hidden_layers"])
