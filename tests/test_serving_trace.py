"""Serving SLO observability: request traces + the flight recorder.

Deterministic mock-device scheduler tests (no real model, tiny pools)
for the ISSUE-6 measurement layer:

* event ordering — submit <= admitted <= first_token <= terminal, with
  TTFT/TPOT derived from the per-token stamps;
* preemption replay shows up in the trace (preempt mark + second
  admission) and the request still completes with the right length;
* the flight recorder's rings hold their bounds under sustained load;
* a step failure auto-dumps the recorder to a JSON postmortem file;
* per-engine latency isolation — two schedulers' stats come from their
  OWN retired traces, not a shared process-global histogram;
* chrome-trace export carries request lanes and thread-name metadata.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.framework import monitor
from paddle_tpu.serving.flight_recorder import FlightRecorder
from paddle_tpu.serving.scheduler import GenerationRequest, Scheduler
from paddle_tpu.serving.tracing import TERMINAL_EVENTS

from _mock_serving import MockDevice, mock_pool


def _submit(sched, prompt_len=4, max_new=3, **kw):
    return sched.submit(GenerationRequest(
        np.ones(prompt_len, np.int32), max_new, **kw))


class TestRequestTrace:
    def test_event_ordering_and_derived_metrics(self):
        pool = mock_pool(slots=2)
        dev = MockDevice(pool, decode_delay=0.002)
        sched = dev.scheduler()
        handles = [_submit(sched, prompt_len=4 + i, max_new=4)
                   for i in range(3)]
        for h in handles:
            h.result(timeout=60)
        sched.close()
        for h in handles:
            tr = h.trace
            assert tr.completed
            assert tr.t("submit") <= tr.t("admitted") \
                <= tr.t("first_token") <= tr.finished_at
            assert tr.t("prefill_start") <= tr.t("prefill_end")
            # 4 tokens emitted -> 4 stamps, TTFT and a real TPOT (the
            # decode_delay makes the cadence strictly positive)
            assert len(tr.token_times) == 4
            assert tr.ttft_ms is not None and tr.ttft_ms >= 0
            assert tr.tpot_ms is not None and tr.tpot_ms > 0
            assert len(tr.decode_intervals_ms) == 3
            assert sum(1 for n, _, _ in tr.events
                       if n in TERMINAL_EVENTS) == 1
            # timeline is JSON-friendly and time-ordered
            tl = tr.timeline()
            assert [e["t_ms"] for e in tl] == \
                sorted(e["t_ms"] for e in tl)
            json.dumps(tl)

    def test_terminal_event_names_cancel_and_deadline(self):
        pool = mock_pool(slots=1)
        dev = MockDevice(pool, decode_delay=0.01)
        sched = dev.scheduler()
        a = _submit(sched, max_new=50)
        b = _submit(sched, max_new=50, timeout=0.05)
        time.sleep(0.03)
        a.cancel()
        for h in (a, b):
            with pytest.raises(Exception):
                h.result(timeout=60)
        sched.close()
        assert a.trace.t("cancelled") is not None
        assert b.trace.t("deadline") is not None

    def test_tpot_none_for_single_token_request(self):
        pool = mock_pool()
        dev = MockDevice(pool)
        sched = dev.scheduler()
        h = _submit(sched, max_new=1)
        h.result(timeout=60)
        sched.close()
        assert len(h.trace.token_times) == 1
        assert h.trace.ttft_ms is not None
        assert h.trace.tpot_ms is None

    def test_tpot_histogram_live(self):
        monitor.stat_reset("serving/tpot_ms")
        pool = mock_pool()
        dev = MockDevice(pool, decode_delay=0.001)
        sched = dev.scheduler()
        _submit(sched, max_new=5).result(timeout=60)
        sched.close()
        h = monitor.stat_histogram("serving/tpot_ms")
        # 5 tokens -> 4 inter-token samples
        assert h is not None and h["count"] >= 4 and h["p50"] > 0


class TestPreemptionReplayTrace:
    def test_preempt_and_readmission_appear_in_trace(self):
        # 4 usable blocks of 8, two requests that each want 3 blocks:
        # growth exhausts the pool mid-decode, the youngest (B) is
        # preempted, feeds again after re-admission, and still finishes
        # with the full token budget
        pool = mock_pool(slots=2, max_len=32, num_blocks=4)
        dev = MockDevice(pool)
        sched = dev.scheduler()
        a = _submit(sched, prompt_len=8, max_new=12)
        b = _submit(sched, prompt_len=8, max_new=12)
        ra = a.result(timeout=60)
        rb = b.result(timeout=60)
        sched.close()
        assert ra.size == 20 and rb.size == 20
        assert sched.preempts >= 1
        pre = a if a.trace.count("preempt") else b
        assert pre.trace.count("preempt") >= 1
        # the victim was re-admitted AFTER the preemption...
        admits = [t for n, t, _ in pre.trace.events if n == "admitted"]
        assert len(admits) == pre.trace.count("preempt") + 1
        assert admits[-1] > pre.trace.t("preempt")
        # ...and the preempt made it into the flight recorder's events
        evs = sched.recorder.snapshot()["events"]
        assert any(e["event"] == "preempt" for e in evs)


class TestFlightRecorder:
    def test_ring_buffer_bounds_hold(self):
        rec = FlightRecorder(max_cycles=4, max_events=10)
        pool = mock_pool(slots=2)
        dev = MockDevice(pool)
        sched = dev.scheduler(recorder=rec)
        for _ in range(8):
            _submit(sched, max_new=4).result(timeout=60)
        sched.close()
        snap = rec.snapshot()
        assert len(snap["cycles"]) <= 4
        assert len(snap["events"]) <= 10
        # the monotonic counters kept counting past the ring bounds
        assert snap["cycles_recorded"] > 4
        assert snap["events_recorded"] > 10
        assert snap["requests_retired"] == 8

    def test_cycle_records_breakdown(self):
        pool = mock_pool(slots=2)
        dev = MockDevice(pool, prefill_delay=0.002, decode_delay=0.002)
        sched = dev.scheduler()
        _submit(sched, max_new=3).result(timeout=60)
        sched.close()
        cycles = sched.recorder.snapshot()["cycles"]
        assert cycles, "no cycle records captured"
        for c in cycles:
            for k in ("cycle", "sweep_ms", "admit_ms", "prefill_ms",
                      "decode_dispatch_ms", "fetch_ms", "cycle_ms",
                      "occupancy", "queue_depth", "emitted"):
                assert k in c, f"cycle record missing {k}: {c}"
        assert any(c["prefill_ms"] > 0 for c in cycles)
        assert any(c["decode_dispatch_ms"] > 0 for c in cycles)
        assert sum(c["emitted"] for c in cycles) >= 2  # decode tokens
        json.dumps(cycles)
        # occupancy histogram fed by the decode cycles
        assert monitor.stat_histogram("serving/batch_occupancy") \
            is not None
        assert monitor.stat_histogram("serving/cycle_ms") is not None

    def test_step_failure_auto_dumps(self):
        pool = mock_pool(slots=2)
        dev = MockDevice(pool)
        boom = {"armed": False}

        def bad_step(slot_requests, plan, prev=None):
            boom["armed"] = True
            raise RuntimeError("injected device failure")

        sched = Scheduler(pool, dev.do_prefill, bad_step)
        h = _submit(sched, max_new=4)
        with pytest.raises(RuntimeError):
            h.result(timeout=60)
        sched.close()
        assert boom["armed"]
        path = sched.recorder.last_dump_path
        assert path is not None and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert "injected device failure" in doc["reason"]
        assert doc["cycles"] and doc["events"]
        assert h.trace.t("error") is not None
        os.unlink(path)

    def test_per_engine_latency_isolation(self):
        # two schedulers in one process: each recorder's percentiles
        # come from its own retired traces only
        fast_pool, slow_pool = mock_pool(), mock_pool()
        fast = MockDevice(fast_pool).scheduler()
        slow = MockDevice(slow_pool, decode_delay=0.02).scheduler()
        for s in (fast, slow):
            for _ in range(3):
                _submit(s, max_new=4).result(timeout=60)
        fast.close(), slow.close()
        lf = fast.recorder.latency_summary()
        ls = slow.recorder.latency_summary()
        # one TTFT and one (mean) TPOT sample banked per retired request
        assert lf["ttft_ms"]["count"] == ls["ttft_ms"]["count"] == 3
        assert lf["tpot_ms"]["count"] == ls["tpot_ms"]["count"] == 3
        # the slow engine's decode cadence (>= 20ms) must not leak into
        # the fast engine's per-engine percentiles
        assert ls["tpot_ms"]["p50"] >= 15.0
        assert lf["tpot_ms"]["p50"] < ls["tpot_ms"]["p50"]


class TestChromeTraceExport:
    def test_request_lanes_and_thread_names(self, tmp_path):
        pool = mock_pool(slots=2)
        dev = MockDevice(pool, decode_delay=0.001)
        with profiler.profile() as sess:
            sched = dev.scheduler()
            hs = [_submit(sched, max_new=3) for _ in range(2)]
            # consume on a separate thread so the submitter and the
            # stream-consumer labels land on distinct lanes
            toks = [[] for _ in hs]

            def consume(i, h):
                toks[i] = list(h.stream())

            ts = [threading.Thread(target=consume, args=(i, h))
                  for i, h in enumerate(hs)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            sched.close()
        assert all(len(t) == 3 for t in toks)
        path = sess.export_chrome_trace(str(tmp_path / "serve.json"))
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        names = {e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert "serving scheduler" in names
        assert any(n.startswith("submitter") for n in names)
        assert any(n.startswith("stream consumer") for n in names)
        assert any(n.startswith("request ") for n in names)
        # request lanes: one whole-lifetime span per request with its
        # phase children, on the synthetic per-request tid
        lanes = [e for e in evs if e.get("ph") == "X"
                 and e["cat"] == "serving/request"]
        whole = [e for e in lanes if e["name"].startswith("request ")]
        assert len(whole) == 2
        assert {e["name"] for e in lanes} >= {"queued", "prefill",
                                              "decode"}
        # cycle spans with the phase breakdown children
        cats = {e["name"] for e in evs if e.get("ph") == "X"
                and e["cat"] == "serving"}
        assert {"serving/cycle", "serving/sweep", "serving/admit",
                "serving/decode_dispatch",
                "serving/host_fetch"} <= cats


class TestARecordIsALaunch:
    """Two launches in flight: launch n's plan and dispatch lie in one
    turn of the loop, its fetch and emit in the next. Record n and every
    span with ``cycle=n`` still describe launch n alone."""

    def _run(self, **sched_kw):
        pool = mock_pool(slots=3, max_len=64)
        dev = MockDevice(pool, decode_delay=0.002, chain=True)
        sides = []      # per launch: what its plan says each side will see

        def step(slot_requests, plan, prev=None):
            feeding = {s for s, r in slot_requests.items() if r.pending_feed}
            sides.append({
                "rows": sum(plan.values()),
                "chunk_tokens": sum(plan[s] for s in feeding),
                "emitted": sum(
                    1 for s, r in slot_requests.items() if s not in feeding
                    or len(r.pending_feed) == plan[s])})
            # the dispatch-side keys, as the engine's operand builder
            # stamps them
            sched.note_launch(rows=sides[-1]["rows"], q=8 * len(plan), t=1,
                              program=f"fused_step_q{8 * len(plan)}_t1",
                              kv_tokens=0, kv_steps=0, kv_fetches=0)
            return dev.do_step(slot_requests, plan, prev)

        with profiler.profile() as sess:
            sched = Scheduler(pool, dev.do_prefill, step, prefill_budget=6,
                              **sched_kw)
            hs = [_submit(sched, prompt_len=n, max_new=m)
                  for n, m in ((10, 4), (3, 6), (5, 3))]
            for h in hs:
                h.result(timeout=60)
            sched.close()
        spans = {}
        for e in sess.events():
            if e["name"].startswith("serving/") \
                    and "cycle" in (e["args"] or {}):
                spans.setdefault(e["name"], {}).setdefault(
                    e["args"]["cycle"], []).append(
                        (e["ts"], e["ts"] + e["dur"]))
        launches = [c for c in sched.recorder.snapshot()["cycles"]
                    if c["decode_dispatch_ms"] > 0]
        return sched, sides, launches, spans

    def test_both_sides_of_a_record_belong_to_one_launch(self):
        sched, sides, launches, _ = self._run()
        assert len(launches) == len(sides) >= 6
        assert [c["cycle"] for c in launches] == \
            sorted(c["cycle"] for c in launches)
        for c, side in zip(launches, sides):
            # written at dispatch ... and at emit, a turn later
            assert c["launch_rows"] == side["rows"], (c, side)
            assert c.get("chunk_tokens", 0) == side["chunk_tokens"], (c, side)
            assert c["emitted"] == side["emitted"], (c, side)
            assert c["fetch_ms"] > 0 and c["late_rows"] == 0
        # the mix differs from launch to launch, so a record that joined
        # its neighbour's emit side would have been caught
        assert len({(s["rows"], s["chunk_tokens"], s["emitted"])
                    for s in sides}) >= 3

    def test_spans_carry_their_launchs_number(self):
        _, _, launches, spans = self._run()
        for name in ("serving/plan", "serving/decode_dispatch",
                     "serving/host_fetch", "serving/emit"):
            for c in launches:
                assert len(spans[name][c["cycle"]]) == 1, (name, c["cycle"])
        turn = {n: iv[0] for n, iv in spans["serving/cycle"].items()}
        for c in launches:
            n = c["cycle"]
            dispatch = spans["serving/decode_dispatch"][n][0]
            fetch = spans["serving/host_fetch"][n][0]
            emit = spans["serving/emit"][n][0]
            assert dispatch[1] <= fetch[0] <= fetch[1] <= emit[0]
            # the turn is numbered by the launch it DISPATCHES
            assert turn[n][0] <= dispatch[0] and dispatch[1] <= turn[n][1]
            if c["overlapped"]:
                # ... and fetches the launch before it after that
                before = spans["serving/host_fetch"][n - 1][0]
                assert dispatch[1] <= before[0] and before[1] <= turn[n][1]
        assert sum(c["overlapped"] for c in launches) >= 4

    def test_overlapped_from_the_third_launch_of_a_busy_stretch_on(self):
        """The launch that opens a busy stretch (the pool was empty)
        lands in its own turn, so that the burst behind the first
        arrival is in the queue when the next launch is planned; that
        next launch finds nothing in flight; from the third on, two
        are."""
        monitor.stat_reset("serving/launch_overlapped")
        sched, _, launches, _ = self._run()
        assert [c["overlapped"] for c in launches] == \
            [False, False] + [True] * (len(launches) - 2)
        assert monitor.stat_get("serving/launch_overlapped") == \
            len(launches) - 2
        # a second stretch starts with an empty pipeline again
        pool = mock_pool(slots=1)
        sched = MockDevice(pool).scheduler()
        for _ in range(2):
            _submit(sched, max_new=3).result(timeout=60)
            time.sleep(0.05)
        sched.close()
        flags = [c["overlapped"] for c in sched.recorder.snapshot()["cycles"]
                 if c["decode_dispatch_ms"] > 0]
        assert flags == [False, False, True] * 2

    def test_speculative_mode_never_overlaps(self):
        """The accepted count decides the next positions: a verify
        launch is fetched in the turn that dispatched it."""
        S, K = 3, 2

        def spec_step(slot_requests, plan, spec):
            # every draft rejected: one corrected token a slot a launch
            out = np.zeros(2 * S + S * K + 1, np.int32)
            out[S:2 * S] = 5
            return out

        sched, sides, launches, spans = self._run(
            do_spec_step=spec_step, spec_k=K)
        assert launches and not any(c["overlapped"] for c in launches)
        assert sched.late_rows == 0
        for c in launches:
            n = c["cycle"]
            turn = spans["serving/cycle"][n][0]
            fetch = spans["serving/host_fetch"][n][0]
            assert turn[0] <= fetch[0] and fetch[1] <= turn[1]
