"""Operations and bytes that the ALGORITHM of each of A.X-K1's two new
kernel-sized pieces needs, from shapes and the launch counters — the
least the work requires, as in ``lib/kernel_costs.py``: re-reads, padding
to tiles and rows no group owns are the implementation's own cost and
lower its roofline share, so no share can read over 100%.
"""
from __future__ import annotations


def latent_bytes_per_token(model: dict, itemsize: int) -> int:
    """Bytes ONE layer's latent cache holds a token: ``kv_lora_rank +
    qk_rope_head_dim`` values (the pad lanes of the stored row are the
    layout's, not the algorithm's)."""
    return (int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])) \
        * int(itemsize)


def mla_read_bytes(kv_tokens: int, model: dict, itemsize: int) -> float:
    """Bytes the latent kernel must at least read in one launch over all
    layers: every context token of every planned sequence once
    (``kv_tokens`` of the cycle record), whatever the q blocks of a chunk
    re-read."""
    return float(kv_tokens) * latent_bytes_per_token(model, itemsize) \
        * int(model["num_hidden_layers"])


def mla_flops(kv_row_tokens: int, model: dict) -> float:
    """FLOPs of the absorbed form in one launch over all layers: per
    (query row, visible cached token) pair and head, a score over ``rank
    + rope`` lanes and a value over ``rank`` lanes, two FLOPs a
    multiply-add (``kv_row_tokens`` of the cycle record counts the
    pairs of the causal mask exactly)."""
    rank, rope = int(model["kv_lora_rank"]), int(model["qk_rope_head_dim"])
    return float(kv_row_tokens) * int(model["num_attention_heads"]) \
        * (rank + rope + rank) * 2.0 * int(model["num_hidden_layers"])


def expert_params(model: dict) -> int:
    """Parameters of ONE routed expert (gate, up, down)."""
    return 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def moe_bytes(experts_hit: int, pairs: int, model: dict,
              itemsize: int) -> float:
    """Bytes the grouped products of one launch must at least move, all
    expert layers: the three matrices of every held expert that got a
    token (``moe_experts_hit``, summed over the layers), once; and per
    (row, expert) pair the row read and its output written."""
    return float(experts_hit) * expert_params(model) * itemsize \
        + float(pairs) * 2 * int(model["hidden_size"]) * itemsize


def moe_flops(pairs: int, model: dict) -> float:
    """FLOPs of the grouped products of one launch, all expert layers:
    three ``hidden x moe_intermediate`` products a (row, expert) pair."""
    return float(pairs) * 2.0 * expert_params(model)


def held_expert_layers(model: dict) -> tuple:
    """(experts held a layer, expert layers)."""
    lo, hi = model.get("experts_held", (0, int(model["n_routed_experts"])))
    return int(hi) - int(lo), \
        int(model["num_hidden_layers"]) - int(model["first_k_dense_replace"])
