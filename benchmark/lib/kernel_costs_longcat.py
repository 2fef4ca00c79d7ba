"""Operations and bytes that the ALGORITHM of LongCat-Flash's latent
attention needs, from shapes and the launch counters — the least the work
requires, as in ``lib/kernel_costs.py``: re-reads, padding to tiles and
rows no group owns are the implementation's own cost and lower its
roofline share, so no share can read over 100%.

The kernel is A.X-K1's (``ops/mla_paged_attention.py``) and a token's
bytes and a pair's FLOPs are ``lib/kernel_costs_axk1.py``'s; what differs
is how many caches a launch's counters are a layer OF: every SUB-BLOCK
holds one, two a published layer (``model["attention_layers"]``), where
``kernel_costs_axk1`` multiplies by ``num_hidden_layers`` — here the
published layers, which is what the expert layer's readers need it to be.
The expert layer's costs (``moe_bytes``, ``moe_flops``,
``held_expert_layers``) are ``kernel_costs_axk1``'s as they stand: an
identity expert's pair is in no ``moe_pairs`` and costs nothing.
"""
from __future__ import annotations

from . import kernel_costs_axk1 as KA


def attention_layers(model: dict) -> int:
    """Latent caches a launch reads and writes: one a sub-block."""
    return int(model["attention_layers"])


def _by_cache(model: dict) -> dict:
    """``model`` as ``kernel_costs_axk1``'s latent-attention costs read it:
    its layer count the caches'."""
    return dict(model, num_hidden_layers=attention_layers(model))


def mla_read_bytes(kv_tokens: int, model: dict, itemsize: int) -> float:
    """Bytes the latent kernel must at least read in one launch over all
    sub-blocks: every context token of every planned sequence once a
    cache (``kv_tokens`` of the cycle record), ``kv_lora_rank +
    qk_rope_head_dim`` values each."""
    return KA.mla_read_bytes(kv_tokens, _by_cache(model), itemsize)


def mla_flops(kv_row_tokens: int, model: dict) -> float:
    """FLOPs of the absorbed form in one launch over all sub-blocks: per
    (query row, visible cached token) pair and head, a score over ``rank
    + rope`` lanes and a value over ``rank`` lanes, two FLOPs a
    multiply-add."""
    return KA.mla_flops(kv_row_tokens, _by_cache(model))
