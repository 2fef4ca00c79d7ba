"""The readers of the program's own spans: on a small trace recorded on
the CPU (``benchmark/tools/record_host_spans.py``: five cycles of the
tiny fused engine, two of them with a prompt chunk, then two train
steps; the CPU has no device plane) and on hand-made readings for what
joins the spans to the device's intervals."""
import json
import os
import shutil

import pytest

from benchmark import run as R
from benchmark.lib import host_spans as HS

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "host-spans.xplane.pb")
CHILDREN = ["serving/sweep", "serving/admit", "serving/plan",
            "serving/decode_dispatch", "serving/host_fetch", "serving/emit",
            "serving/record"]
SERVING = ["sched_plan_ms", "sched_emit_ms", "step_launch_ms",
           "decode_step_ms", "chunk_step_ms", "q_row_fill",
           "q_row_fill.chunk", "idle_unplaced_share", "kv_read_gbs",
           "launch_programs"]
MODEL = {"model": {"num_attention_heads": 2, "hidden_size": 8,
                   "num_hidden_layers": 3},
         "serving": {"dtype": "bfloat16"}}


def read(metric, readings):
    return R.load_module("layer_metrics", metric).read(readings)


def recorded_readings(tmp_path, **more):
    """Readings that point at the recorded trace the way a serving driver
    does: a slice directory with the profiler's layout under it."""
    run_dir = tmp_path / "plugins" / "profile" / "recorded"
    run_dir.mkdir(parents=True)
    shutil.copy(RECORDED, run_dir / "host.xplane.pb")
    with open(os.path.join(DATA, "host-spans-cycles.json")) as f:
        cycles = json.load(f)
    return {"slice": {"dir": str(tmp_path)}, "cycles": cycles, **more}


def test_the_recorded_trace_holds_each_cycle_with_its_children_in_order():
    spans = HS.read_trace(RECORDED)[0]
    cycles = {a["cycle"]: (s, e) for s, e, n, a in spans if n == HS.CYCLE}
    assert sorted(cycles) == [6, 7, 8, 9, 10]
    for number, (lo, hi) in cycles.items():
        inside = [(s, e, n) for s, e, n, a in spans
                  if a.get("cycle") == number
                  and n not in (HS.CYCLE, "serving/wait")]
        assert [n for _, _, n in inside] == CHILDREN
        assert lo <= inside[0][0] and inside[-1][1] <= hi
        for (_, end, _), (start, _, _) in zip(inside, inside[1:]):
            assert end <= start                      # no overlap
    # between two cycles the scheduler is inside a span too (the first
    # cycle's began before the trace did)
    waits = {a["cycle"]: e for _, e, n, a in spans if n == "serving/wait"}
    assert all(waits[number] <= cycles[number][0] for number in waits)
    assert sorted(waits) == [7, 8, 9, 10]
    steps = [a["step"] for _, _, n, a in spans if n == "hapi/train_batch"]
    assert steps == [3, 4]


def test_span_medians_and_the_row_fill_read_the_recorded_trace(tmp_path):
    r = recorded_readings(tmp_path)
    spans = HS.host_spans(r)
    assert r["host_spans"] is spans and HS.host_spans(r) is spans  # read once
    for metric, name in (("sched_plan_ms", "serving/plan"),
                         ("sched_emit_ms", "serving/emit"),
                         ("step_launch_ms", "serving/decode_dispatch"),
                         ("train_dispatch_ms", "hapi/train_batch")):
        lengths = sorted((e - s) / 1e6 for s, e, n, _ in spans if n == name)
        assert read(metric, r) == pytest.approx(lengths[len(lengths) // 2]
                                                if len(lengths) % 2 else
                                                sum(lengths[0:2]) / 2)
        assert 0 < read(metric, r) < 1000
    # 32 + 28 real rows in programs of 32 + 64 on the two chunk cycles,
    # 3 + 3 + 3 in 32 + 32 + 32 on the plain ones
    assert [c["launch_rows"] for c in r["cycles"]] == [32, 28, 3, 3, 3]
    assert [c["chunk_tokens"] > 0 for c in r["cycles"]] == \
        [True, True, False, False, False]
    # a 32-token chunk: 4 q blocks of 8 rows, each against 4 KV blocks
    assert [c["kv_steps"] for c in r["cycles"]] == [16, 13, 10, 10, 10]
    assert read("q_row_fill", r) == pytest.approx(100.0 * 9 / 96)
    assert read("q_row_fill.chunk", r) == pytest.approx(100.0 * 60 / 96)
    # programs [q32,t8] and [q64,t8]; only the window's launches count
    assert read("launch_programs", r) == 2
    assert read("launch_programs", dict(r, t0=r["cycles"][2]["t"],
                                        t1=r["cycles"][4]["t"])) == 1
    # the CPU run has no device plane: nothing to join the spans to
    assert read("decode_step_ms", r) is None
    assert read("chunk_step_ms", r) is None
    assert read("idle_unplaced_share", r) is None
    assert read("kv_read_gbs", dict(r, **MODEL)) is None


def test_the_training_driver_s_trace_is_found_where_it_puts_it(monkeypatch,
                                                               tmp_path):
    run_dir = tmp_path / "trace-train" / "plugins" / "profile" / "recorded"
    run_dir.mkdir(parents=True)
    shutil.copy(RECORDED, run_dir / "host.xplane.pb")
    monkeypatch.setattr(HS.H, "OUT_DIR", str(tmp_path))
    assert read("train_dispatch_ms", {"trace_steps": 2}) > 0
    # a run that did not trace reads no file at all
    assert read("train_dispatch_ms", {"steps": 7}) is None


@pytest.mark.parametrize("metric", SERVING + ["train_dispatch_ms"])
def test_a_program_that_marks_no_span_reads_as_nothing(metric, tmp_path):
    """The parent commit: a trace with device ops and cycle records, no
    ``serving/`` or ``hapi/`` event, no launch counter."""
    run_dir = tmp_path / "plugins" / "profile" / "parent"
    run_dir.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "small.xplane.pb"),
                run_dir / "small.xplane.pb")
    r = {"slice": {"dir": str(tmp_path)}, "trace_steps": 2,
         "trace": {"intervals": [(0, 10), (20, 30)]},
         "cycles": [{"cycle": 1, "active": 2, "chunk_tokens": 0}], **MODEL}
    assert read(metric, r) is None
    assert read(metric, {"slice": {"dir": str(tmp_path / "absent")}}) is None


def hand_made():
    """Three cycles of 1,000 ns on one clock with the device: cycle 1 and
    3 decode only, cycle 2 with a chunk. The device runs 300 ns in cycle
    1 (in two pieces), 600 in 2, 320 in 3."""
    spans = []
    for i, number in enumerate((1, 2, 3)):
        t = 1000 * i
        cyc = {"cycle": number}
        spans += [(t, t + 990, HS.CYCLE, cyc),
                  (t, t + 100, "serving/plan", cyc),
                  (t + 100, t + 200, "serving/decode_dispatch", cyc),
                  (t + 200, t + 800, "serving/host_fetch", cyc),
                  (t + 800, t + 950, "serving/emit", cyc)]
    return {"host_spans": spans,
            "trace": {"intervals": [(150, 250), (300, 500), (1150, 1750),
                                    (2140, 2460)]},
            "cycles": [{"cycle": 1, "chunk_tokens": 0, "kv_tokens": 100,
                        "kv_steps": 20},
                       {"cycle": 2, "chunk_tokens": 512, "kv_tokens": 700,
                        "kv_steps": 90},
                       {"cycle": 3, "kv_steps": 30}], **MODEL}


def test_device_time_of_a_launch_is_split_by_what_the_cycle_carried():
    r = hand_made()
    assert read("decode_step_ms", r) == pytest.approx((300 + 320) / 2 / 1e6)
    # what the chunk launch took above the plain one, by the kernel
    # steps it walked above it: ms per 1,000 steps of 2 heads x 3 layers
    assert read("chunk_step_ms", r) == \
        pytest.approx(1e3 * (600 - 310) / 1e6 / ((90 - 25) * 6))
    # device time outside the launch's stretch of the cycle is not its own
    r["trace"]["intervals"] = [(50, 250), (300, 900)]
    r["cycles"] = r["cycles"][:2]
    assert read("decode_step_ms", r) == pytest.approx((150 + 500) / 1e6)


def test_a_slice_with_no_chunk_cycle_has_no_chunk_step():
    r = hand_made()
    r["cycles"][1]["chunk_tokens"] = 0
    assert read("chunk_step_ms", r) is None
    assert read("decode_step_ms", r) == pytest.approx(320 / 1e6)
    # a cycle whose record the poll missed is in neither
    r["cycles"] = r["cycles"][:1]
    assert read("decode_step_ms", r) == pytest.approx(300 / 1e6)


def test_idle_time_under_no_span_is_unplaced():
    r = hand_made()
    # gaps: 250-300 under host_fetch; 500-1150 and 1750-2140 each cross
    # the 50 ns between an emit span's end and the next cycle's plan
    assert read("idle_unplaced_share", r) == \
        pytest.approx(100.0 * (50 + 50) / (50 + 650 + 390))
    assert HS.idle_by_span(r) == {
        "serving/plan": 100 + 100, "serving/decode_dispatch": 50 + 40,
        "serving/host_fetch": 50 + 300 + 50, "serving/emit": 150 + 150,
        HS.NO_SPAN: 100}
    r["trace"]["intervals"] = [(150, 250), (300, 940), (1010, 1750)]
    assert read("idle_unplaced_share", r) == \
        pytest.approx(100.0 * 50 / (50 + 70))
    # a span between the cycles (the scheduler's wait for work) covers it
    r["host_spans"].append((950, 1000, "serving/wait", {"cycle": 2}))
    assert read("idle_unplaced_share", r) == 0.0
    # idle time before the first recorded span or after the last says
    # nothing about the spans (one that began before the trace is not in
    # it): the gap 2460-3500 counts as far as the last emit span's end
    r["trace"]["intervals"] = [(-400, -300), (2140, 2460), (3500, 3600)]
    assert HS.idle_by_span(r)[HS.NO_SPAN] == 50      # 1950-2000
    assert sum(HS.idle_by_span(r).values()) == 2140 + (2950 - 2460)


def test_the_kernel_s_read_rate_counts_its_time_inside_the_launches():
    r = hand_made()
    per_token = 3 * 2 * 2 * 4 * 2               # layers, K and V, heads, Dh
    # the kernel runs 200 of cycle 1's launch, 500 of cycle 2's; cycle 3
    # has no counter, and the 30 ns before cycle 1's dispatch span belong
    # to a launch the slice does not hold
    r["kernel_intervals"] = [(50, 80), (300, 500), (1200, 1700),
                             (2200, 2400)]
    assert read("kv_read_gbs", r) == \
        pytest.approx((100 + 700) * per_token / (200 + 500))
    r["kernel_intervals"] = []
    assert read("kv_read_gbs", r) is None


def test_the_clock_check_counts_a_launch_outside_its_spans():
    r = hand_made()
    ok = HS.clock_check(r)
    assert (ok["cycles"], ok["stretches"], ok["violations"]) == (3, 2, 0)
    assert ok["launch_lead_ms"] == pytest.approx(50 / 1e6)
    assert ok["fetch_lag_ms"] == pytest.approx(300 / 1e6)
    # the device's clock 200 ns late: cycle 1's launch now ends after its
    # fetch span and reaches into the host-only stretch before cycle 2
    r["trace"]["intervals"] = [(s + 700, e + 700)
                               for s, e in r["trace"]["intervals"]]
    assert HS.clock_check(r)["violations"] == 2
    assert HS.clock_check({"host_spans": []}) is None
