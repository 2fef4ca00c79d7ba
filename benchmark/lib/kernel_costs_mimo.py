"""Operations and bytes that the ALGORITHM of attention over a cache of
window and global layers needs, from shapes and the launch counters — the
least the work requires, as in ``lib/kernel_costs.py``: re-reads, the pad
lanes of a stored row (320 values are stored 384 wide), padding to tiles
and rows no sequence owns are the implementation's own cost and lower its
roofline share, so no share can read over 100%.

A cached token of a layer is its KV heads' K (``head_dim`` lanes) and V
(``v_head_dim`` lanes); a GLOBAL layer must read every context token of
every planned sequence (``kv_tokens`` of the cycle record), a WINDOW layer
the last ``sliding_window - 1`` and the launch's rows
(``kv_tokens_window``); the pairs under each mask are ``kv_row_tokens``
and ``kv_row_tokens_window``.
"""
from __future__ import annotations

from . import kernel_costs as K
from . import peaks as P

# the window layers' kernel carries its own name in a device trace
WINDOW_KERNEL = "ragged_paged_attention_window"


def layers_of(model: dict, window: bool) -> int:
    """Served layers of a kind."""
    L = int(model["num_hidden_layers"])
    return sum(1 for v in model["hybrid_layer_pattern"][:L]
               if bool(int(v)) == bool(window))


def _dims(model: dict, window: bool) -> tuple:
    p = "swa_" if window else ""
    return (int(model[p + "num_attention_heads"]),
            int(model[p + "num_key_value_heads"]),
            int(model[p + "head_dim"]), int(model[p + "v_head_dim"]))


def kv_bytes_per_token(model: dict, window: bool, itemsize: int) -> int:
    """Bytes ONE layer of a kind holds a token: K and V of every KV head
    (2,560 B global, 5,120 B window at the published widths in bf16)."""
    _, hkv, dk, dv = _dims(model, window)
    return hkv * (dk + dv) * int(itemsize)


def attention_read_bytes(kv_tokens: int, model: dict, window: bool,
                         itemsize: int) -> float:
    """Bytes the kernel of a kind must at least read in one launch over
    its layers: the tokens it must read (``kv_tokens`` /
    ``kv_tokens_window``) once a layer."""
    return float(kv_tokens) * kv_bytes_per_token(model, window, itemsize) \
        * layers_of(model, window)


def attention_flops(kv_row_tokens: int, model: dict, window: bool) -> float:
    """FLOPs of one launch over a kind's layers: per (query row, visible
    token) pair and QUERY head a score over ``head_dim`` lanes and a value
    over ``v_head_dim``, two FLOPs a multiply-add."""
    h, _, dk, dv = _dims(model, window)
    return float(kv_row_tokens) * h * (dk + dv) * 2.0 \
        * layers_of(model, window)


def kernel_seconds(ops: dict, window: bool) -> float:
    """Device seconds of a kind's attention kernel among a slice's trace
    events (``readings["trace"]["ops"]``): the names that hold
    ``ragged_paged_attention`` and, or and not, the window kernel's."""
    return sum(v for k, v in ops.items() if "ragged_paged_attention" in k
               and (WINDOW_KERNEL in k) == bool(window))


def roofline_share(r: dict, window: bool):
    """A kind's kernel's share of its roofline over the traced slice, %:
    the larger of its bytes over the HBM bandwidth and its FLOPs over the
    bf16 peak, over the kernel's device time. ``None`` where the program
    stamps no window counters (one cache group), the configuration has no
    layer pattern, or the kernel is not in the trace."""
    tokens, pairs = ("kv_tokens_window", "kv_row_tokens_window") if window \
        else ("kv_tokens", "kv_row_tokens")
    cycles = [c for c in r.get("trace_cycles", [])
              if "kv_tokens_window" in c]
    m = r.get("model", {})
    if not cycles or "trace" not in r or "hybrid_layer_pattern" not in m:
        return None
    secs = kernel_seconds(r["trace"]["ops"], window)
    if secs <= 0:
        return None
    peaks = P.peaks_for(r["device_kind"])
    size = K.dtype_itemsize(r["serving"]["dtype"])
    by_bytes = sum(attention_read_bytes(c[tokens], m, window, size)
                   for c in cycles) / peaks["hbm_bytes_per_s"]
    by_flops = sum(attention_flops(c[pairs], m, window)
                   for c in cycles) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / secs
