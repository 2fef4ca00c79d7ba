"""The reduction from a profiler trace to busy time, per-op time and
gaps, on a small trace recorded on a v5e
(``benchmark/tools/record_trace.py``: three steps of one fused matmul,
10 ms of sleep between them) and on hand-made events."""
import os

import pytest

from benchmark.lib import trace_reduce as TR

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
WINDOW_S = 0.033966          # printed by the recording


def test_the_recorded_trace_reduces_to_what_its_events_add_up_to():
    planes = TR.device_planes(SMALL)
    assert list(planes) == ["/device:TPU:0"]
    events = sorted(planes["/device:TPU:0"])
    assert len(events) == 9                       # 3 steps x 3 ops
    assert {n for _, _, n in events} == \
        {"copy-start", "copy-done", "fusion bf16[1024,1024]"}
    by_hand_ns = sum(e - s for s, e, _ in events)  # no op overlaps another
    assert by_hand_ns == 44163
    red = TR.reduce_trace(SMALL, WINDOW_S)
    assert red["busy_s"] == pytest.approx(44163e-9)
    assert red["window_s"] == WINDOW_S and red["planes"] == 1
    assert red["ops"]["fusion bf16[1024,1024]"] == pytest.approx(37818e-9)
    assert TR.op_seconds(red, "fusion") == pytest.approx(37818e-9)
    assert TR.top_ops(red, 1)[0][0] == "fusion bf16[1024,1024]"
    # the two long gaps are the sleeps between the steps
    long_gaps = [e - s for s, e in TR.gaps(red) if e - s > 1_000_000]
    assert len(long_gaps) == 2 and all(9e6 < g < 14e6 for g in long_gaps)
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert 0.99 < idle < 1.0


def test_busy_time_is_the_union_and_own_time_excludes_nested_ops():
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "a"),
              (200, 250, "c")]
    red = TR.reduce_events({"/device:TPU:0": events}, 1e-6)
    assert red["busy_s"] == pytest.approx(150e-9)
    ops = {k: round(v * 1e9) for k, v in red["ops"].items()}
    assert ops == {"while": 30, "a": 30, "b": 40, "c": 50}
    assert TR.gaps(red) == [(100, 200)]


def test_busy_time_is_averaged_over_the_chips_used():
    red = TR.reduce_events({"/device:TPU:0": [(0, 100, "x")],
                            "/device:TPU:1": [(0, 50, "x")]}, 1e-6)
    assert red["busy_s"] == pytest.approx(75e-9)
    assert red["ops"]["x"] == pytest.approx(75e-9)


def test_a_trace_with_no_device_op_is_refused():
    with pytest.raises(ValueError):
        TR.reduce_events({}, 1.0)


def test_gaps_are_labelled_by_the_span_that_holds_their_midpoint():
    gaps = [(0, 10), (100, 140), (500, 600)]
    spans = [(90, 150, "cycle: dispatch"), (0, 5, "cycle: fetch")]
    out = dict(TR.label_gaps(gaps, spans, other="between cycles"))
    assert out == {"cycle: dispatch": 40e-9,
                   "between cycles": pytest.approx(110e-9)}


def test_short_names():
    assert TR.short_name("%flash_attention_dkv.35 = (bf16[96,1024,64]{2,1,0}, "
                         "bf16[96,1024,64]) custom-call(...)") == \
        "flash_attention_dkv"
    assert TR.short_name("%fusion.2551 = (f32[8,128]{1,0}, f32[8,128,50304]) "
                         "fusion(...)") == "fusion f32[8,128]"
    assert TR.short_name("ragged_paged_attention") == "ragged_paged_attention"
