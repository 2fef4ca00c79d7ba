"""Operations and bytes that each kernel's ALGORITHM needs, from shapes.

These are the least the work requires, not what an implementation
happens to do: re-reads, recomputation and padding are the kernel's own
cost and lower its roofline share. A share above 100% therefore means
the count here is too high or the measured time left out part of the
work.
"""
from __future__ import annotations


def gpt_param_count(vocab: int, hidden: int, layers: int, inter: int,
                    positions: int) -> int:
    """Parameters of a GPT-2 with a tied head and biased projections."""
    per_layer = (4 * hidden * hidden + 4 * hidden      # q, k, v, out
                 + 2 * hidden * inter + inter + hidden  # mlp
                 + 4 * hidden)                          # two LayerNorms
    return vocab * hidden + positions * hidden + layers * per_layer \
        + 2 * hidden


def train_flops_per_token(n_params: int) -> float:
    """6·N: forward and backward matmuls per token; recomputation and
    attention's sequence term are not counted (model FLOPs)."""
    return 6.0 * n_params


def causal_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                           matmuls: int) -> float:
    """FLOPs of ``matmuls`` [seq, seq, head_dim] products under a causal
    mask (half of the square), for one layer."""
    return matmuls * 2.0 * batch * heads * seq * seq * head_dim / 2.0


FLASH_FWD_MATMULS = 2        # Q·K^T, P·V
FLASH_BWD_MATMULS = 4        # dV, dP, dQ, dK (recomputing S is the kernel's)


def flash_train_flops(batch: int, heads: int, seq: int, head_dim: int,
                      layers: int) -> float:
    """Causal attention FLOPs of one training step over all layers."""
    return layers * causal_attention_flops(
        batch, heads, seq, head_dim, FLASH_FWD_MATMULS + FLASH_BWD_MATMULS)


def kv_bytes_per_token(layers: int, heads: int, head_dim: int,
                       itemsize: int) -> int:
    """Bytes of K and V one cached token holds over all layers."""
    return layers * 2 * heads * head_dim * itemsize


def paged_attention_read_bytes(live_blocks: int, active_rows: int,
                               block_tokens: int, layers: int, heads: int,
                               head_dim: int, itemsize: int) -> float:
    """Bytes a decode/chunk launch must at least read from the KV pool:
    every block held by a live page table once, less one whole block per
    active row for its partly filled tail (an under-count, so the share
    it feeds cannot be flattered)."""
    blocks = max(0, int(live_blocks) - int(active_rows))
    return float(blocks) * block_tokens * kv_bytes_per_token(
        layers, heads, head_dim, itemsize)


def dtype_itemsize(name: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[name]
