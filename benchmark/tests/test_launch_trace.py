"""``lib/launch_trace.py``: a launch found on the device by its ``run_id``
and its device time by section — on hand-made events for the rules, and
(below) on a trace recorded on a v5e by
``benchmark/tools/record_launch_trace.py``."""
import json
import os
import shutil

import pytest

from benchmark import run as R
from benchmark.lib import host_spans as HS
from benchmark.lib import launch_trace as LT
from benchmark.lib import trace_reduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "launch-trace.xplane.pb")
NEW = ["launch_device_ms.plain", "launch_device_ms.chunk", "device_gap_ms",
       "section_ms.norm", "section_ms.qkv", "section_ms.o_proj",
       "section_ms.head", "section_ms.mlp", "section_ms.router",
       "section_unplaced_share"]


def read(metric, readings):
    return R.load_module("layer_metrics", metric).read(readings)


# -- the rules, on hand-made events ----------------------------------------
PLAIN, CHUNK = "jit_fused_step_q8_t1(11)", "jit_fused_step_q16_t1(22)"
MODULES = [(100, 200, PLAIN, 4), (210, 300, PLAIN, 5), (320, 500, CHUNK, 6),
           (520, 600, "jit_copy_blocks(33)", 7), (610, 700, PLAIN, 8)]
# the runtime's chain: a linkage event (start, flow id) on the dispatching
# thread, the execution it names {flow id: start}, then the enqueue (start,
# run_id) — span 2's and span 4's AFTER the span has ended
DISPATCH = {1: (40, 60), 2: (140, 160), 3: (240, 270), 4: (440, 460),
            5: (540, 560)}
DEVICE = {"modules": MODULES,
          "links": [(45, "a"), (145, "b"), (245, "c"), (255, "d"),
                    (445, "e"), (545, "f")],
          "executes": {"a": 46, "b": 146, "c": 246, "d": 256, "e": 446,
                       "f": 546},
          "enqueues": [(50, 4), (162, 5), (250, 6), (260, 7), (465, 8)]}


def record(n, program, **more):
    return {"cycle": n, "launch_q": 8, "launch_t": 1,
            "launch_program": program, **more}


RECORDS = {1: record(1, "fused_step_q8_t1"), 2: record(2, "fused_step_q8_t1"),
           3: record(3, "fused_step_q16_t1", chunk_tokens=9),
           4: record(4, "fused_step_q8_t1"),
           5: record(5, "fused_step_q8_t1")}


def test_a_launch_is_the_module_event_its_dispatch_span_executed():
    matched, why = LT.join_launches(DISPATCH, RECORDS, DEVICE)
    # span 3 executed two programs: the record's program name picks the step
    assert matched == {1: MODULES[0][:3], 2: MODULES[1][:3],
                       3: MODULES[2][:3], 4: MODULES[4][:3]}
    # span 5's execution was never enqueued in the trace: counted, not guessed
    assert why == {"no program executed inside the dispatch span has a "
                   "module event": 1}


def test_an_enqueue_belongs_to_the_execution_before_it_and_to_no_other():
    # execution "b" lost its enqueue (the trace ended): the next
    # execution's enqueue is not taken for it
    device = dict(DEVICE, enqueues=[e for e in DEVICE["enqueues"]
                                    if e[1] != 5])
    matched, why = LT.join_launches(DISPATCH, RECORDS, device)
    assert sorted(matched) == [1, 3, 4] and sum(why.values()) == 2
    # a span with no linkage event inside it (dispatched before the trace
    # began) matches nothing, though its enqueue and module event are there
    device = dict(DEVICE, links=DEVICE["links"][1:])
    matched, _ = LT.join_launches(DISPATCH, RECORDS, device)
    assert sorted(matched) == [2, 3, 4]


def test_the_parents_records_name_no_program_and_two_candidates_match_none():
    old = {n: {k: v for k, v in r.items() if k != "launch_program"}
           for n, r in RECORDS.items()}
    matched, why = LT.join_launches(DISPATCH, old, DEVICE)
    assert sorted(matched) == [1, 2, 4]
    assert why["several candidates"] == 1
    # a span whose record the poll missed, or that launched nothing
    matched, why = LT.join_launches(DISPATCH, {1: RECORDS[1], 2: {"cycle": 2}},
                                    DEVICE)
    assert sorted(matched) == [1] and why["no launch record"] == 4


def test_an_op_name_shared_by_two_programs_goes_by_the_launchs_program():
    table = {"%a": [("norm", 11), ("qkv", 22)], "%b": [("mlp", 11)],
             "%k": [("attention", None)], "%c": [(None, 11)]}
    assert LT.place(table["%a"], 11) == "norm"
    assert LT.place(table["%a"], 22) == "qkv"
    assert LT.place(table["%a"], 33) is None       # neither: unplaced
    assert LT.place(table["%b"], 22) == "mlp"      # one section: no doubt
    assert LT.place(table["%k"], 22) == "attention"
    assert LT.place(table["%c"], 11) is None and LT.place(None, 11) is None


def test_sections_and_unplaced_time_are_the_busy_time_of_the_launches():
    launches = {1: MODULES[0][:3], 3: MODULES[2][:3]}
    table = {"%a": [("norm", 11), ("qkv", 22)], "%w": [("moe_experts", 22)],
             "%r = custom-call": [("moe_experts", None)],
             "%c": [(None, 22)]}
    ops = [(100, 130, "%a"), (130, 150, "%x"),            # launch 1
           (205, 209, "%a"),                              # between launches
           (320, 350, "%a"), (360, 460, "%w"),            # launch 3: a while
           (370, 400, "%r = custom-call"), (400, 420, "%c"),   # ... its body
           (470, 500, "%c")]
    by_cycle, busy, unplaced = LT.section_times(ops, launches, table)
    assert by_cycle[1] == {"norm": 30, LT.UNPLACED: 20}
    assert by_cycle[3] == {"qkv": 30, "moe_experts": 50 + 30,
                           LT.UNPLACED: 20 + 30}
    assert busy == {1: 50, 3: 30 + 100 + 30}
    for n in launches:
        assert sum(by_cycle[n].values()) == busy[n]
    assert unplaced == {"x": 20, "c": 50}


# -- nothing to read: None, never a raise -----------------------------------
def slice_readings(tmp_path, trace, cycles):
    """Readings that point at a recorded trace the way a serving driver
    does: a slice directory with the profiler's layout under it."""
    run_dir = tmp_path / "plugins" / "profile" / "recorded"
    run_dir.mkdir(parents=True)
    shutil.copy(trace, run_dir / "host.xplane.pb")
    with open(os.path.join(DATA, cycles)) as f:
        return {"slice": {"dir": str(tmp_path)}, "cycles": json.load(f)}


@pytest.mark.parametrize("metric", NEW)
def test_a_trace_with_no_device_plane_reads_nothing(tmp_path, metric):
    """The CPU's trace has dispatch spans and no ``XLA Modules`` line."""
    r = slice_readings(tmp_path, os.path.join(DATA, "host-spans.xplane.pb"),
                       "host-spans-cycles.json")
    assert read(metric, r) is None
    assert r["launch_trace"] is None           # looked once, kept
    assert read(metric, {}) is None            # an untraced run's readings
    assert read(metric, {"slice": {"dir": str(tmp_path / "none")}}) is None


# -- a program without sections (an older commit): launches yes, sections no -
def test_small_step_launches_are_joined_and_no_section_is_read(tmp_path):
    """``small.xplane.pb`` (a v5e, three ``small_step`` calls, ``run_id`` 4,
    5, 6) under hand-made dispatch spans around its calls (enqueued at
    45.012, 56.103 and 68.039 ms): the three module events are found, their
    lengths and the idle between them read, and no section metric."""
    r = slice_readings(tmp_path, os.path.join(DATA, "small.xplane.pb"),
                       "host-spans-cycles.json")
    ms = 1_000_000
    r["host_spans"] = [(44 * ms, 46 * ms, LT.DISPATCH, {"cycle": 1}),
                       (55 * ms, 57 * ms, LT.DISPATCH, {"cycle": 2}),
                       (67 * ms, 69 * ms, LT.DISPATCH, {"cycle": 3}),
                       (70 * ms, 71 * ms, LT.DISPATCH, {"cycle": 4})]
    r["cycles"] = [{"cycle": n, "launch_q": 8, "chunk_tokens": 8 * (n == 2)}
                   for n in (1, 2, 3, 4)]
    lt = LT.launch_trace(r)
    assert {n: m[2].split("(")[0] for n, m in lt["launches"].items()} \
        == {1: "jit_small_step", 2: "jit_small_step", 3: "jit_small_step"}
    assert "sections" not in lt
    assert read("launch_device_ms.plain", r) == pytest.approx(
        (12.642 + 15.776) / 2 / 1e3, rel=1e-3)
    assert read("launch_device_ms.chunk", r) == pytest.approx(15.763 / 1e3,
                                                              rel=1e-3)
    # 43.570 -> 54.677 and 54.692 -> 66.642 ms on the device's clock
    assert read("device_gap_ms", r) == pytest.approx(
        (11.1067 + 11.9493) / 2, rel=1e-3)
    for metric in NEW:
        if metric.startswith("section_"):
            assert read(metric, r) is None


# -- a dozen launches of a small engine, recorded on a v5e -------------------
def test_recorded_launches_are_joined_and_their_time_summed_by_section(
        tmp_path, capsys):
    """``launch-trace.xplane.pb`` (``tools/record_launch_trace.py`` on a
    v5e: two launches in flight, a turn of ~4 ms around ~0.04 ms of device
    work): the trace starts while launch 169 is dispatched, so the first
    of its twelve module events has no dispatch span and stays out; the
    eleven spans 170-180 each find theirs — every enqueue but one lies
    AFTER its span's end, the chain ties them — 170's with the chunk."""
    r = slice_readings(tmp_path, RECORDED, "launch-trace-cycles.json")
    assert all(c["overlapped"] for c in r["cycles"])
    lt = LT.launch_trace(r)
    log = capsys.readouterr().out
    assert "11 of 11 dispatch spans matched" in log
    assert "1 of 12 module events belong to no dispatch span" in log
    assert {n: m[2].split("(")[0] for n, m in lt["launches"].items()} == {
        170: "jit_fused_step_q64_t32",
        **{n: "jit_fused_step_q16_t32" for n in range(171, 178)},
        **{n: "jit_fused_step_q8_t32" for n in (178, 179, 180)}}
    device = LT.read_device(TR.latest_xplane(str(tmp_path)))
    first = device["modules"][0]
    assert first[3] == 354 and first[:3] not in lt["launches"].values()
    run_of = {m[:3]: m[3] for m in device["modules"]}
    enqueued = {run_id: s for s, run_id in device["enqueues"]}
    inside = [n for n, (lo, hi) in HS.by_cycle(r, LT.DISPATCH).items()
              if lo <= enqueued[run_of[lt["launches"][n]]] < hi]
    assert inside == [176, 179]      # what the join by time alone found
    # the launch's record says which kind it was
    assert lt["records"][170]["chunk_tokens"] == 40
    assert read("launch_device_ms.chunk", r) == pytest.approx(0.055063)
    assert read("launch_device_ms.plain", r) == pytest.approx(0.041193,
                                                              rel=1e-4)
    assert read("device_gap_ms", r) == pytest.approx(4.1293, rel=1e-4)
    # every section of a dense step, and with the unplaced time they ARE
    # the launch's busy time
    for n, sections in lt["sections"].items():
        assert set(sections) == {"embed", "norm", "qkv", "cache_write",
                                 "attention", "o_proj", "mlp", "head",
                                 "sample", LT.UNPLACED}
        assert sum(sections.values()) == lt["busy"][n]
        assert lt["busy"][n] <= lt["launches"][n][1] - lt["launches"][n][0]
    assert read("section_unplaced_share", r) == pytest.approx(1.2027,
                                                              rel=1e-3)
    per_launch = {m: read(m, r) for m in NEW if m.startswith("section_ms")}
    assert per_launch.pop("section_ms.router") is None    # a dense model
    assert all(0.001 < v < 0.02 for v in per_launch.values())
    assert per_launch["section_ms.head"] == pytest.approx(
        sum(s["head"] + s["sample"] for s in lt["sections"].values())
        / 11 / 1e6)
