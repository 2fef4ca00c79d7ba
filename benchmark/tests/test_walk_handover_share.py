"""``walk_handover_share`` on recorded readings: the cycle records of a
slice with the hand-over's counters, with one of them missing, and
without them (what the parent's program writes)."""
import pytest

from benchmark import run as R
from benchmark.lib import host_spans as HS


def read(readings):
    return R.load_module("layer_metrics", "walk_handover_share").read(readings)


def readings(cycles):
    """Cycles 1-3 lie whole in the slice; cycle 4's span is not in it."""
    spans = [(1000 * n, 1000 * n + 990, HS.CYCLE, {"cycle": n})
             for n in (1, 2, 3)]
    return {"host_spans": spans, "cycles": cycles}


RECORDS = [
    # a plain launch of 64 decode rows: all but the call's first walk
    {"cycle": 1, "kv_steps": 1721, "kv_walks": 64, "kv_walks_handed": 63},
    # 60 decode rows, a pad step, then a chunk of 17 walks
    {"cycle": 2, "kv_steps": 2300, "kv_walks": 77, "kv_walks_handed": 75},
    # a cycle that launched nothing counts nothing
    {"cycle": 3},
    # outside the slice
    {"cycle": 4, "kv_steps": 10, "kv_walks": 4, "kv_walks_handed": 0},
]


@pytest.mark.parametrize("cycles,want", [
    (RECORDS, 100.0 * 138 / 141),
    (RECORDS[:1], 100.0 * 63 / 64),
    # a record that lacks the handed count adds its walks and no handed one
    ([RECORDS[0], {"cycle": 2, "kv_walks": 77}], 100.0 * 63 / 141),
    # the parent's records: no such keys, nothing to read (not 0)
    ([{k: v for k, v in c.items() if not k.startswith("kv_walks")}
      for c in RECORDS], None),
    (RECORDS[2:], None),                       # no launch in the slice
    ([], None),
], ids=["plain-and-chunk", "plain", "handed-key-missing", "parent",
        "no-launch", "empty"])
def test_handed_share_over_the_cycles_of_the_slice(cycles, want):
    got = read(readings(cycles))
    assert got is None if want is None else got == pytest.approx(want)


def test_without_records_or_spans_reads_nothing():
    assert read({}) is None
    assert read({"cycles": RECORDS}) is None   # no cycle span in the slice
