"""Operations and bytes that the ALGORITHM of LFM2-MoE's two operators
needs in one launch, from shapes and the launch counters — the least the
work requires, as in ``lib/kernel_costs.py``: re-reads, padding to tiles,
rows no sequence owns and float32 intermediates are the implementation's
own cost and lower its roofline share, so no share can read over 100%.

**Attention over a cache that SOME layers hold.** A cached token is
``num_key_value_heads`` rows of K and of V of ``head_dim`` lanes in each
layer that holds a cache — ``cache_layers`` of the launch's record (2 of
this stage's 10), never ``num_hidden_layers``: the ``conv`` layers read
and write no block. The kernel must read every context token of every
planned sequence once such a layer (``kv_tokens``) and do, a (query row,
visible token) pair and QUERY head, a score and a value product over
``head_dim`` lanes (``kv_row_tokens``).

**The gated short convolution.** A ``conv`` layer's operator must read
its weights once a launch (``W_in`` ``E x 3E``, the ``K`` taps, ``W_out``
``E x E``) and each real row in and out (``E`` values each way, the
serving dtype), and do two FLOPs a weight a real row (``ssm_rows`` of the
record counts a launch's real rows once; the gates and the taps are
counted with the weights' 2 FLOP each). The tail (``K - 1`` rows of ``E``
float32 a sequence each way) is counted with the rows it stands for: a
few KB a slot.
"""
from __future__ import annotations

CONV = "conv"


def conv_layers(model: dict) -> int:
    """Served layers whose operator is the gated short convolution."""
    L = int(model["num_hidden_layers"])
    return sum(1 for t in model["layer_types"][:L] if t == CONV)


def kv_bytes_per_token(model: dict, itemsize: int) -> int:
    """Bytes ONE attention layer's cache holds a token: K and V of every
    KV head (2,048 B at the published widths in bf16)."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) \
        * int(itemsize)


def attention_read_bytes(kv_tokens: int, cache_layers: int, model: dict,
                         itemsize: int) -> float:
    """Bytes the attention kernel must at least read in one launch: every
    context token of every planned sequence once a cache-bearing layer."""
    return float(kv_tokens) * kv_bytes_per_token(model, itemsize) \
        * int(cache_layers)


def attention_flops(kv_row_tokens: int, cache_layers: int,
                    model: dict) -> float:
    """FLOPs of one launch's attention: per (query row, visible cached
    token) pair and QUERY head a score and a value product over
    ``head_dim`` lanes, two FLOPs a multiply-add, a cache-bearing layer."""
    return float(kv_row_tokens) * int(model["num_attention_heads"]) \
        * 2 * int(model["head_dim"]) * 2.0 * int(cache_layers)


def conv_operator_params(model: dict) -> int:
    """Parameters of ONE conv operator: 16.78 M at the published widths."""
    E = int(model["hidden_size"])
    return E * 3 * E + int(model["conv_L_cache"]) * E + E * E


def short_conv_bytes(rows: int, model: dict, itemsize: int) -> float:
    """Bytes the conv operators of one launch must at least move, all
    conv layers: the weights once, each real row in and out."""
    return conv_layers(model) * (
        conv_operator_params(model) * int(itemsize)
        + float(rows) * 2 * int(model["hidden_size"]) * int(itemsize))


def short_conv_flops(rows: int, model: dict) -> float:
    """FLOPs of one launch's conv operators, all conv layers."""
    return float(rows) * conv_layers(model) * 2.0 \
        * conv_operator_params(model)
