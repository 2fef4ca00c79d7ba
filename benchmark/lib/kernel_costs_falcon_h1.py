"""Operations and bytes that the ALGORITHM of a Mamba-2 recurrence over a
state a slot needs, from shapes and the launch counters — the least the
work requires, as in ``lib/kernel_costs.py``: a second read of the state,
the padded rows of a launch, a chunk's quadratic form in place of a step
a row are the implementation's own cost and lower its roofline share, so
no share can read over 100%.

A launch's scan must, a layer with state, read and write the recurrent
state of every sequence it advances ONCE (``state_slots`` of the cycle
record: ``heads x P x N`` values of the state's dtype each way) and read
each real row's ``x`` and ``y`` (``heads x P``), ``B`` and ``C`` (``G x
N``) and ``dt`` (``heads``) in float32; and it must do, a real row and
head, the update and the read of a ``P x N`` state: a decay, an outer
product's multiply-add and the contraction with ``C`` — 5 FLOP a state
element (``ssm_rows`` of the record).
"""
from __future__ import annotations

import numpy as np


def _dims(model: dict) -> tuple:
    return (int(model["mamba_n_heads"]), int(model["mamba_d_head"]),
            int(model["mamba_d_state"]), int(model["mamba_n_groups"]))


def state_bytes(model: dict) -> int:
    """Bytes of ONE sequence's recurrent state in one layer."""
    h, p, n, _ = _dims(model)
    return h * p * n * np.dtype(model.get("state_dtype", "float32")).itemsize


def scan_bytes(state_slots: int, rows: int, model: dict) -> float:
    """Bytes the scan must at least move in one launch over all layers."""
    h, p, n, g = _dims(model)
    row = (2 * h * p + 2 * g * n + h) * 4
    return (float(state_slots) * 2 * state_bytes(model) + float(rows) * row) \
        * int(model["num_hidden_layers"])


def scan_flops(rows: int, model: dict) -> float:
    """FLOPs of one launch's scan over all layers."""
    h, p, n, _ = _dims(model)
    return float(rows) * h * p * n * 5.0 * int(model["num_hidden_layers"])
