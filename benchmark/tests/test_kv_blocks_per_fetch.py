"""``kv_blocks_per_fetch`` on recorded readings: the cycle records of a
slice with the grouped walk's counter, and the same records without it
(what the parent's program writes)."""
import pytest

from benchmark import run as R
from benchmark.lib import host_spans as HS


def read(readings):
    return R.load_module("layer_metrics", "kv_blocks_per_fetch").read(readings)


def readings(cycles):
    """Cycles 1-3 lie whole in the slice; cycle 4's span is not in it."""
    spans = [(1000 * n, 1000 * n + 990, HS.CYCLE, {"cycle": n})
             for n in (1, 2, 3)]
    return {"host_spans": spans, "cycles": cycles}


RECORDS = [
    # 64 decode rows, 1,344 KV blocks between them, 192 groups of 8
    {"cycle": 1, "kv_steps": 1344, "kv_fetches": 192},
    # the same with a 3-q-block chunk over 5 blocks (one group) beside it
    {"cycle": 2, "kv_steps": 1359, "kv_fetches": 195},
    # a cycle that launched nothing counts nothing
    {"cycle": 3},
    # outside the slice
    {"cycle": 4, "kv_steps": 10, "kv_fetches": 10},
]


def test_blocks_a_fetch_over_the_cycles_of_the_slice():
    assert read(readings(RECORDS)) == \
        pytest.approx((1344 + 1359) / (192 + 195))


def test_a_program_without_the_counter_reads_nothing():
    parent = [{k: v for k, v in c.items() if k != "kv_fetches"}
              for c in RECORDS]
    assert read(readings(parent)) is None
    assert read(readings([])) is None
    assert read({}) is None
