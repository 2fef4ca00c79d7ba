"""The nine readers of ``setup_s``'s inside (PR 52) on hand-written
readings: build events on both sides of ``t0`` and of ``t1``, a site on
the fallback path, a program that keeps no ``startup`` (every commit
before PR 52), a door that saw no request."""
import copy

import pytest

from benchmark import run as R
from benchmark.lib import harness as H

SERVING_CELLS = [
    "gpt2-large.decode", "axk1-ep16.decode", "sdar-30b-a3b-pp8.decode",
    "mimo-v2-flash-ep16.decode", "falcon-h1-34b-pp12.decode",
    "lfm2-24b-a2b-pp4.decode", "longcat-flash-ep32.decode",
    "nemotron3-super-ep4.decode"]
SETUP = ["setup_before_engine_s", "setup_engine_build_s", "setup_trace_s",
         "setup_lower_s", "setup_executable_s", "setup_first_call_s",
         "setup_programs_built", "setup_ramp_s"]
NAMES = SETUP + ["builds_in_window"]


def read(name, readings):
    return R.load_module("layer_metrics", name).read(readings)


def event(at, site, trace=1000.0, lower=500.0, compile_=2000.0, first=250.0,
          hits=1, misses=0, rows=64, slots=64):
    return {"site": site + "#1", "at": at, "trace_ms": trace,
            "lower_ms": lower, "compile_ms": compile_, "cache_hits": hits,
            "cache_misses": misses, "first_call_ms": first, "eqns": 900,
            "launch_rows": rows, "slots_active": slots}


# the process started at 1000.0 on the clock of t0; the window is
# [1080, 1120)
READINGS = {
    "t0": 1080.0, "t1": 1120.0, "setup_s": 80.0,
    "engine_stats": {"startup": {
        "t_build": 1029.5, "build_ms": 6000.0,
        "phases_ms": {"params": 10.0, "pallas_smoke": 900.0, "pool": 90.0,
                      "plan_gate": 4900.0, "scheduler": 1.0},
        "programs": [
            event(1036.0, "serving/fused[q64,t32]"),
            event(1041.0, "serving/fused[q1024,t32]", trace=1500.0,
                  hits=2, rows=1024),
            # a site that fell back to plain jit: one wall, no parts
            {"site": "serving/copy#1", "at": 1050.0, "trace_ms": None,
             "lower_ms": None, "compile_ms": None, "cache_hits": None,
             "cache_misses": None, "first_call_ms": None, "eqns": None,
             "fallback": True, "wall_ms": 700.0, "launch_rows": None,
             "slots_active": 3},
            # built by the ramp, 1.5 s before the window opened
            event(1078.5, "serving/fused[q128,t32]", hits=0, misses=1,
                  compile_=9000.0, rows=70),
            # inside the window
            event(1100.25, "serving/fused[q8,t512]", rows=5, slots=5),
            # the drain's: after t1
            event(1137.5, "serving/fused[q64,t64]", rows=9, slots=9),
        ]}},
    "door_stats": {"served": 90, "first_request_t": 1072.25},
}

WANT = {"setup_before_engine_s": 29.5, "setup_engine_build_s": 6.0,
        "setup_trace_s": 3.5, "setup_lower_s": 1.5,
        "setup_executable_s": 13.0, "setup_first_call_s": 0.75,
        "setup_programs_built": 4.0, "setup_ramp_s": 7.75,
        "builds_in_window": 1.0}


@pytest.fixture
def logged(monkeypatch):
    lines = []
    monkeypatch.setattr(H, "log", lines.append)
    return lines


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_on_events_around_the_windows_edges(name, logged):
    assert read(name, copy.deepcopy(READINGS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_record_reads_nothing(name, logged):
    """The parent commit: no ``startup`` in the engine's stats, no
    ``first_request_t`` in the door's — ``None``, not 0, and no raise."""
    parent = copy.deepcopy(READINGS)
    del parent["engine_stats"]["startup"]
    del parent["door_stats"]["first_request_t"]
    assert read(name, parent) is None
    assert read(name, {"t0": 1.0, "t1": 2.0, "setup_s": 1.0}) is None
    assert logged == []


def test_a_door_that_saw_no_request_has_no_ramp(logged):
    quiet = copy.deepcopy(READINGS)
    quiet["door_stats"]["first_request_t"] = None
    assert read("setup_ramp_s", quiet) is None
    # the summary then names neither the ramp nor a remainder
    assert read("setup_programs_built", quiet) == 4.0
    assert "ramp" not in logged[-1] and "waves running" not in logged[-1]


def test_the_parts_and_the_remainder_add_up_to_setup_s(logged):
    r = copy.deepcopy(READINGS)
    parts = [read(n, r) for n in SETUP if n != "setup_programs_built"]
    assert sum(parts) == pytest.approx(29.5 + 6.0 + 3.5 + 1.5 + 13.0 + 0.75
                                       + 7.75)
    logged.clear()
    read("setup_programs_built", r)
    line, = logged
    # the program the ramp built (10.75 s of parts) lies inside the ramp:
    # the remainder counts it once
    assert f"waves running {80.0 - sum(parts) + 10.75:.2f}" in line
    assert "4 programs (1 of them built inside the ramp)" in line
    assert "serving/fused[q128,t32] (70 rows, 64 slots)" in line
    assert "serving/copy (None rows, 3 slots)" in line
    assert "q8,t512" not in line             # after t0: not the set-up's


def test_the_executables_line_says_hits_and_misses(logged):
    read("setup_executable_s", copy.deepcopy(READINGS))
    assert logged == ["executables of 4 programs: 3 cache hits, 1 misses"]


def test_a_build_inside_the_window_and_one_after_it_are_named(logged):
    assert read("builds_in_window", copy.deepcopy(READINGS)) == 1.0
    assert logged == [
        "built inside the window, 20.25 s in: serving/fused[q8,t512] "
        "(5 rows, 5 slots)",
        "built 17.50 s after the window closed: serving/fused[q64,t64] "
        "(9 rows, 9 slots)"]
    # a drain-time build alone: what compiles.serve counts as 1 reads 0
    drained = copy.deepcopy(READINGS)
    del drained["engine_stats"]["startup"]["programs"][4]
    logged.clear()
    assert read("builds_in_window", drained) == 0.0
    assert len(logged) == 1 and "after the window closed" in logged[0]


@pytest.mark.parametrize("name", NAMES)
def test_each_is_listed_for_the_eight_serving_cells(name):
    bench = H.load_json("BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == SERVING_CELLS
    assert entry["moves"] == ("serve_tok_s" if name == "builds_in_window"
                              else "setup_s")
    assert entry["better"] == "lower"
    assert entry["unit"] == ("count" if name in ("setup_programs_built",
                                                 "builds_in_window")
                             else "s")
    train, = [w for w in bench["workloads"] if "train" in w["name"]]
    assert train["name"] not in entry["workloads"]
