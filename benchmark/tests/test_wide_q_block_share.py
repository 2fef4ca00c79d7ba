"""``wide_q_block_share`` on recorded readings: the cycle records of a
slice with the wide step's counters, with one of them missing, and
without them (what the parent's program writes)."""
import pytest

from benchmark import run as R
from benchmark.lib import host_spans as HS


def read(readings):
    return R.load_module("layer_metrics", "wide_q_block_share").read(readings)


def readings(cycles):
    """Cycles 1-3 lie whole in the slice; cycle 4's span is not in it."""
    spans = [(1000 * n, 1000 * n + 990, HS.CYCLE, {"cycle": n})
             for n in (1, 2, 3)]
    return {"host_spans": spans, "cycles": cycles}


RECORDS = [
    # a 1,024-row chunk beside 120 decode rows: 124 of 248 q blocks wide
    {"cycle": 1, "kv_steps": 9000, "q_blocks": 248, "q_blocks_wide": 124},
    # decode rows only: no wide step
    {"cycle": 2, "kv_steps": 4400, "q_blocks": 120, "q_blocks_wide": 0},
    # a cycle that launched nothing counts nothing
    {"cycle": 3},
    # outside the slice
    {"cycle": 4, "kv_steps": 10, "q_blocks": 4, "q_blocks_wide": 4},
]


@pytest.mark.parametrize("cycles,want", [
    (RECORDS, 100.0 * 124 / 368),
    (RECORDS[1:], 0.0),                        # decode only: 0, not nothing
    # a record that lacks the wide count adds its q blocks and no wide one
    ([RECORDS[0], {"cycle": 2, "q_blocks": 120}], 100.0 * 124 / 368),
    # the parent's records: no such keys, nothing to read (not 0)
    ([{k: v for k, v in c.items() if not k.startswith("q_blocks")}
      for c in RECORDS], None),
    (RECORDS[2:], None),                       # no launch in the slice
    ([], None),
], ids=["chunk-and-decode", "decode-only", "wide-key-missing", "parent",
        "no-launch", "empty"])
def test_wide_share_over_the_cycles_of_the_slice(cycles, want):
    got = read(readings(cycles))
    assert got is None if want is None else got == pytest.approx(want)


def test_without_records_or_spans_reads_nothing():
    assert read({}) is None
    assert read({"cycles": RECORDS}) is None   # no cycle span in the slice
