"""Operations and bytes that the ALGORITHM of Nemotron-H's two
kernel-sized pieces needs in one launch, from shapes and the launch
counters — the least the work requires, as in ``lib/kernel_costs.py``:
re-reads, padding to tiles, rows no group or sequence owns, a second read
of the state and a chunk's quadratic form are the implementation's own
cost and lower its roofline share, so no share can read over 100%.

**The grouped products of UNGATED experts in a latent.** An expert is two
matrices ``latent x moe_intermediate`` (``W2 relu(W1 v)^2``), and reads
and writes rows of ``moe_latent_size`` lanes, not of the hidden width:
the launch must read the two matrices of every held expert that got a
token once (``moe_experts_hit``, summed over the expert blocks) and, a
(row, expert) pair (``moe_pairs``), the row in and its output out at
``latent`` lanes; and do two products a pair. ``kernel_costs_axk1`` prices
three ``hidden x moe_intermediate`` products a pair: 6x these.

**The recurrence of a Mamba-2 mixer as a block's whole content.** As
``kernel_costs_falcon_h1``, under this family's key names and a layer
count that is the launch record's ``state_layers`` (the ``M`` blocks: 5 of
this stage's 11), never ``num_hidden_layers``: a block with state must
read and write the recurrent state of every sequence the launch advances
once (``state_slots``: ``heads x P x N`` values of the state's dtype each
way) and read each real row's ``x`` and ``y`` (``heads x P``), ``B`` and
``C`` (``G x N``) and ``dt`` (``heads``) in float32; and do, a real row
and head, the update and the read of a ``P x N`` state — a decay, an outer
product's multiply-add and the contraction with ``C``: 5 FLOP a state
element (``ssm_rows``).
"""
from __future__ import annotations

import numpy as np


def expert_params(model: dict) -> int:
    """Parameters of ONE routed expert: ``W1 [latent, I]`` and ``W2 [I,
    latent]`` (5.505 M at the published widths)."""
    return 2 * int(model["moe_latent_size"]) \
        * int(model["moe_intermediate_size"])


def latent_moe_bytes(experts_hit: int, pairs: int, model: dict,
                     itemsize: int) -> float:
    """Bytes the grouped products of one launch must at least move, all
    expert blocks."""
    return float(experts_hit) * expert_params(model) * itemsize \
        + float(pairs) * 2 * int(model["moe_latent_size"]) * itemsize


def latent_moe_flops(pairs: int, model: dict) -> float:
    """FLOPs of the grouped products of one launch, all expert blocks: two
    ``latent x moe_intermediate`` products a (row, expert) pair."""
    return float(pairs) * 2.0 * expert_params(model)


def _ssm(model: dict) -> tuple:
    return (int(model["mamba_num_heads"]), int(model["mamba_head_dim"]),
            int(model["ssm_state_size"]), int(model["n_groups"]))


def state_bytes(model: dict) -> int:
    """Bytes of ONE sequence's recurrent state in one ``M`` block
    (4,194,304 at the published widths in float32)."""
    h, p, n, _ = _ssm(model)
    return h * p * n * np.dtype(model.get("state_dtype", "float32")).itemsize


def scan_bytes(state_slots: int, rows: int, state_layers: int,
               model: dict) -> float:
    """Bytes the scan must at least move in one launch, all ``M`` blocks."""
    h, p, n, g = _ssm(model)
    row = (2 * h * p + 2 * g * n + h) * 4
    return (float(state_slots) * 2 * state_bytes(model) + float(rows) * row) \
        * int(state_layers)


def scan_flops(rows: int, state_layers: int, model: dict) -> float:
    """FLOPs of one launch's scan, all ``M`` blocks."""
    h, p, n, _ = _ssm(model)
    return float(rows) * h * p * n * 5.0 * int(state_layers)
