"""``moe_row_fill`` on recorded readings: the cycle records of a slice with
the rows the grouped products walked, with the counter missing from one,
and without it (what the parent's program writes)."""
import pytest

from benchmark import run as R
from benchmark.lib import host_spans as HS


def read(readings):
    return R.load_module("layer_metrics", "moe_row_fill").read(readings)


def readings(cycles):
    """Cycles 1-3 lie whole in the slice; cycle 4's span is not in it."""
    spans = [(1000 * n, 1000 * n + 990, HS.CYCLE, {"cycle": n})
             for n in (1, 2, 3)]
    return {"host_spans": spans, "cycles": cycles}


RECORDS = [
    # 8 layers of 4,608 pairs, 64 experts' groups padded to tiles of 128
    {"cycle": 1, "moe_pairs": 36864, "moe_experts_hit": 512,
     "moe_rows": 9216, "moe_rows_walked": 67584},
    # a launch of decode rows: thin groups, mostly pad
    {"cycle": 2, "moe_pairs": 4096, "moe_experts_hit": 512,
     "moe_rows": 1024, "moe_rows_walked": 16384},
    # a cycle that launched nothing counts nothing
    {"cycle": 3},
    # outside the slice
    {"cycle": 4, "moe_pairs": 10, "moe_experts_hit": 1, "moe_rows": 10,
     "moe_rows_walked": 10},
]


@pytest.mark.parametrize("cycles,want", [
    (RECORDS, 100.0 * 40960 / 83968),
    (RECORDS[:1], 100.0 * 36864 / 67584),
    # a record without the counter (no pair on a held expert walks no
    # row: 0 is left out as an absent key is) adds nothing to either sum
    ([RECORDS[0], {"cycle": 2, "moe_pairs": 0, "moe_experts_hit": 0,
                   "moe_rows": 1024, "moe_rows_walked": 0}],
     100.0 * 36864 / 67584),
    ([RECORDS[0], {"cycle": 2, "moe_pairs": 4096, "moe_rows": 1024}],
     100.0 * 36864 / 67584),
    # the parent's records: three counters, nothing to read (not 100)
    ([{k: v for k, v in c.items() if k != "moe_rows_walked"}
      for c in RECORDS], None),
    (RECORDS[2:], None),                       # no launch in the slice
    ([], None),
], ids=["chunk-and-decode", "one-launch", "no-pair-held", "key-missing",
        "parent", "no-launch", "empty"])
def test_fill_over_the_cycles_of_the_slice(cycles, want):
    got = read(readings(cycles))
    assert got is None if want is None else got == pytest.approx(want)


def test_without_records_or_spans_reads_nothing():
    assert read({}) is None
    assert read({"cycles": RECORDS}) is None   # no cycle span in the slice


def test_the_metric_is_declared_for_the_four_routed_cells():
    bench = R.H.load_json("BENCHMARK.json")
    entry = [m for m in bench["per_layer"] if m["name"] == "moe_row_fill"]
    roofline = next(m for m in bench["per_layer"]
                    if m["name"] == "moe_experts_roofline")
    assert len(entry) == 1
    assert entry[0]["workloads"] == roofline["workloads"]
    assert (entry[0]["layer"], entry[0]["moves"], entry[0]["source"]) == (
        "expert layer", "serve_tok_s", "program_counter")
