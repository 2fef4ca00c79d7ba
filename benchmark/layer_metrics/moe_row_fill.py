"""Expert layer: real (row, expert) pairs over the rows the grouped
products were handed, %: sum of ``moe_pairs`` over sum of
``moe_rows_walked`` of the slice's cycle records (the launch's counters,
summed over the expert layers on the device and read with its tokens).
``routed_experts`` starts every held expert's rows on a tile of the
product and pads its group to whole tiles with zero rows, so that no tile
holds two experts' rows: this is what the alignment costs in rows — the
gather in, the two f32 products' outputs, ``silu * up`` and the way back
all run on the rows walked. Nothing where the program keeps no such
counter (pairs back to back, nothing to pad: every commit before PR 44)."""
from benchmark.lib import host_spans as HS


def read(r):
    counted = [c for c in HS.slice_records(r) if c.get("moe_rows_walked")]
    if not counted:
        return None
    return 100.0 * sum(c["moe_pairs"] for c in counted) \
        / sum(c["moe_rows_walked"] for c in counted)
