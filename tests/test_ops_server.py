"""PR-16 SLO plane acceptance: OpsServer + SLOTracker + tail sampling.

* scrape equivalence — every counter/gauge parsed back from a live
  ``GET /metrics`` equals ``registry.snapshot()`` taken at the same
  instant (a controlled private registry, no concurrent writers), and
  the SLO attainment recomputed from the scraped histogram buckets
  brackets the exact in-process value within one bucket of resolution;
* burn rates — multi-window deltas against the sampler ring (fast
  window sees only post-baseline errors, slow window falls back to
  process lifetime while the ring is young), zero burn on zero traffic;
* poisoned-replica ops surface — a fleet with one dead replica answers
  503 on ``/healthz`` naming the poisoned replica, 200 on ``/readyz``
  (degraded but serving), and ``/statusz`` still renders every section
  with the replica marked DOWN — none of it raises;
* endpoint coverage — /, /varz, /tracez, /timeline, 404s, post-close
  behavior;
* flight-recorder tail sampling — slowest-N eviction order, violation
  capture, windowed goodput, and the ``FLAGS_flight_dump_dir``
  auto-dump override.
"""
import json
import math
import os
import urllib.error
import urllib.request

import pytest

from paddle_tpu.framework import metrics as M
from paddle_tpu.serving import (EngineFleet, FlightRecorder, OpsServer,
                                SLOObjective, SLOTracker,
                                attainment_from_buckets)


def _get(url, timeout=30):
    """(status, decoded body) — 4xx/5xx answers come back as data, not
    exceptions, because error bodies are part of the surface under test."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class _Trace:
    """Minimal retired-trace stand-in for hooks/observe_trace."""

    def __init__(self, ttft_ms, tpot_ms=None, request_id=0):
        self.request_id = request_id
        self.ttft_ms = ttft_ms
        self.tpot_ms = tpot_ms

    def snapshot(self):
        return {"request": self.request_id, "ttft_ms": self.ttft_ms,
                "tpot_ms": self.tpot_ms}


class _StubRecorder:
    def latency_samples(self):
        return {"ttft_ms": [], "tpot_ms": []}


class _StubEngine:
    """Enough of GenerationEngine for EngineFleet aggregation; poisoned
    when ``fail_stats`` (stats() raising == scheduler thread dead)."""

    def __init__(self, fail_stats=False):
        self._fail_stats = fail_stats
        self.flight_recorder = _StubRecorder()

    def stats(self):
        if self._fail_stats:
            raise RuntimeError("scheduler thread is dead")
        return {"queue_depth": 0, "active_requests": 0, "num_slots": 4,
                "slots_in_use": 1, "slot_utilization": 0.25,
                "preempts": 0, "requests_retired": 3,
                "nonfinite_cycles": 0, "kv_pool_capacity_bytes": 1000,
                "kv_bytes_in_use": 100}

    def close(self, cancel_pending=False):
        pass


# ---------------------------------------------------------------------------
# scrape equivalence (the acceptance gate)
# ---------------------------------------------------------------------------

class TestScrapeEquivalence:
    def test_http_scrape_equals_snapshot(self):
        reg = M.MetricsRegistry(include_monitor=False)
        reg.inc("ops_requests_total", 3, route="a")
        reg.inc("ops_requests_total", 5, route="b")
        reg.set_gauge("ops_pool_free", 7.5, pool="kv")
        reg.set_gauge("ops_up", 1.0)
        for v in (1.0, 4.0, 12.0, 88.0, 310.0):
            reg.observe("ops_lat_ms", v, leg="x")
        with OpsServer(registry=reg) as srv:
            status, body = _get(srv.url + "/metrics")
            snap = reg.snapshot()          # same instant: no writers
        assert status == 200
        parsed = M.parse_prometheus(body)
        # every native counter/gauge series round-trips exactly
        for kind, ptype in (("counters", "counter"), ("gauges", "gauge")):
            for name, series in snap[kind].items():
                assert parsed["types"][name] == ptype
                for entry in series:
                    key = (name, tuple(sorted(entry["labels"].items())))
                    assert parsed["samples"][key] == entry["value"], key
        # the histogram family round-trips bucket-exact
        hist = snap["histograms"]["ops_lat_ms"][0]
        assert parsed["types"]["ops_lat_ms"] == "histogram"
        for le, cum in hist["buckets"]:
            le_val = math.inf if le == "+Inf" else float(le)
            le_lab = "+Inf" if le == "+Inf" else (
                str(int(le_val)) if float(le_val).is_integer()
                else f"{le_val:.17g}")
            key = ("ops_lat_ms_bucket", (("le", le_lab), ("leg", "x")))
            assert parsed["samples"][key] == cum, key
        key = ("ops_lat_ms_count", (("leg", "x"),))
        assert parsed["samples"][key] == hist["count"]

    def test_scraped_buckets_bracket_exact_attainment(self):
        reg = M.MetricsRegistry(include_monitor=False)
        slo = SLOTracker(registry=reg, name="equiv")
        slo.add_objective("ttft", metric="ttft_ms", target_ms=250.0,
                          goal=0.9)
        lat = [3.0, 12.0, 48.0, 90.0, 180.0, 240.0, 260.0, 420.0,
               900.0, 2400.0, 55.0, 70.0]
        for i, v in enumerate(lat):
            slo.observe_trace(_Trace(v, request_id=i))
        exact = slo.report()["objectives"]["ttft"]["attainment"]
        assert exact == sum(v <= 250.0 for v in lat) / len(lat)
        with OpsServer(registry=reg, slo=slo) as srv:
            status, body = _get(srv.url + "/metrics")
        assert status == 200
        parsed = M.parse_prometheus(body)
        pairs = []
        for (name, labels), value in parsed["samples"].items():
            if name != "slo_latency_ms_bucket":
                continue
            lab = dict(labels)
            if lab.get("objective") != "ttft":
                continue
            le = lab["le"]
            pairs.append((math.inf if le == "+Inf" else float(le),
                          value))
        lo, hi = attainment_from_buckets(pairs, 250.0)
        # the exact per-event attainment lies inside the one-bucket
        # bracket recomputed purely from the HTTP-scraped exposition
        assert lo is not None and lo <= exact <= hi, (lo, exact, hi)
        assert hi - lo < 1.0    # a real bracket, not [0, 1]
        # and the published gauge IS the exact value
        key = ("slo_attainment", (("objective", "ttft"),))
        assert parsed["samples"][key] == pytest.approx(exact)
        slo.close()


# ---------------------------------------------------------------------------
# burn rates over the sampler ring
# ---------------------------------------------------------------------------

class TestBurnRates:
    def test_fast_window_deltas_against_aged_baseline(self):
        reg = M.MetricsRegistry(include_monitor=False)
        slo = SLOTracker(registry=reg, name="burn", fast_window_s=60.0,
                         slow_window_s=1800.0)
        slo.add_objective("ttft", target_ms=100.0, goal=0.9)
        for _ in range(10):
            slo.observe_trace(_Trace(10.0))     # 10 good
        reg.sample_now()
        # age the baseline entry past the fast window but not the slow
        reg._ring[-1]["t"] -= 120.0
        for _ in range(5):
            slo.observe_trace(_Trace(500.0))    # then 5 violations
        rates = slo.burn_rates()["ttft"]
        # fast window: 5 bad / 5 total post-baseline, budget 0.1 -> 10x
        assert rates["1m"] == pytest.approx(10.0)
        # slow window: ring younger than 30m -> lifetime 5/15 over 0.1
        assert rates["30m"] == pytest.approx((5 / 15) / 0.1)
        slo.close()

    def test_zero_traffic_burns_zero(self):
        reg = M.MetricsRegistry(include_monitor=False)
        with SLOTracker(registry=reg, name="idle") as slo:
            slo.add_objective("ttft", target_ms=100.0, goal=0.99)
            assert slo.burn_rates()["ttft"] == {"1m": 0.0, "30m": 0.0}
            rep = slo.report()["objectives"]["ttft"]
            assert rep["total"] == 0 and rep["attainment"] is None

    def test_objective_validation(self):
        with pytest.raises(ValueError):
            SLOObjective("x", "latency_ms", 100.0, 0.9)   # bad metric
        with pytest.raises(ValueError):
            SLOObjective("x", "ttft_ms", 100.0, 1.0)      # zero budget


# ---------------------------------------------------------------------------
# poisoned-replica ops surface (satellite 3)
# ---------------------------------------------------------------------------

class TestPoisonedReplica:
    def test_healthz_flips_readyz_holds_statusz_renders(self):
        fleet = EngineFleet([_StubEngine(), _StubEngine(fail_stats=True)],
                            name="opsfleet")
        srv = OpsServer(target=fleet).start()
        try:
            code, body = _get(srv.url + "/healthz")
            assert code == 503
            doc = json.loads(body)
            assert doc["ok"] is False
            assert doc["replicas_healthy"] == 1
            assert doc["unhealthy"] == [1]
            # degraded-but-serving: one healthy replica keeps readiness
            code, body = _get(srv.url + "/readyz")
            assert code == 200 and json.loads(body)["ready"] is True
            # the console still renders end to end — no section raises,
            # the poisoned replica is flagged, the healthy one isn't
            code, body = _get(srv.url + "/statusz")
            assert code == 200
            assert "DOWN" in body and "[0] ok" in body
            assert "scheduler thread is dead" in body
            # and the in-process console agrees (same renderer)
            text = M.statusz()
            assert "DOWN" in text
            code, body = _get(srv.url + "/varz")
            assert code == 200 and json.loads(body)["counters"] is not None
        finally:
            srv.close()
            fleet.close()

    def test_closed_target_unhealthy_and_unready(self):
        fleet = EngineFleet([_StubEngine()], name="closing")
        srv = OpsServer(target=fleet).start()
        try:
            assert _get(srv.url + "/healthz")[0] == 200
            fleet.close()
            code, body = _get(srv.url + "/healthz")
            assert code == 503
            assert json.loads(body)["reason"] == "target closed"
            assert _get(srv.url + "/readyz")[0] == 503
        finally:
            srv.close()

    def test_stats_raising_target_is_unhealthy_not_a_500(self):
        srv = OpsServer(target=_StubEngine(fail_stats=True)).start()
        try:
            code, body = _get(srv.url + "/healthz")
            assert code == 503
            assert "scheduler thread is dead" in json.loads(body)["reason"]
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# endpoint coverage
# ---------------------------------------------------------------------------

class TestEndpoints:
    def test_index_unknown_and_targetless_health(self):
        with OpsServer() as srv:
            code, body = _get(srv.url + "/")
            assert code == 200
            assert "/metrics" in json.loads(body)["endpoints"]
            code, body = _get(srv.url + "/nope")
            assert code == 404 and "see" in json.loads(body)
            # no target: the process-level surface is trivially healthy
            assert _get(srv.url + "/healthz")[0] == 200
            assert _get(srv.url + "/readyz")[0] == 200
            assert json.loads(_get(srv.url + "/tracez")[1]) == \
                {"engines": {}}

    def test_a_burst_of_clients_connects_without_a_dropped_syn(self):
        """The stdlib's listen backlog of 5 drops the SYNs of a burst
        (each comes back whole seconds later): 64 clients connect
        before the accept loop gets one turn, and none waits."""
        import socket
        import time
        with OpsServer() as srv:
            assert srv._httpd.request_queue_size >= 256
            host, port = srv._httpd.server_address[:2]
            srv._httpd.shutdown()           # nobody accepts: the queue holds
            t0 = time.perf_counter()
            socks = [socket.create_connection((host, port), timeout=10)
                     for _ in range(64)]
            took = time.perf_counter() - t0
            for s in socks:
                s.close()
        assert took < 0.9, f"{took:.2f} s: a SYN was retransmitted"

    def test_timeline_serves_trace_doc(self):
        with OpsServer() as srv:
            code, body = _get(srv.url + "/timeline")
        assert code == 200
        assert "traceEvents" in json.loads(body)

    def test_tracez_carries_tails_and_slo_report(self):
        reg = M.MetricsRegistry(include_monitor=False)
        slo = SLOTracker(registry=reg, name="tz")
        slo.add_objective("ttft", target_ms=100.0, goal=0.9)
        eng = _StubEngine()
        rec = FlightRecorder(tail_keep=2)
        eng.flight_recorder = rec
        slo.attach_engine(eng, replica="r0")
        for i, v in enumerate((10.0, 500.0, 20.0, 900.0)):
            rec.retire(_Trace(v, request_id=i))
        with OpsServer(target=eng, registry=reg, slo=slo) as srv:
            doc = json.loads(_get(srv.url + "/tracez")[1])
        tail = doc["engines"]["0"]
        assert tail["tail_slo_ms"] == 100.0
        assert tail["slo_violations_total"] == 2
        assert [s["ttft_ms"] for s in tail["slowest"]] == [900.0, 500.0]
        assert len(tail["recent"]) == 4
        assert doc["slo"]["objectives"]["ttft"]["total"] == 4
        assert doc["slo"]["objectives"]["ttft"]["attainment"] == 0.5
        assert doc["slo"]["goodput_rps"]["r0"] > 0
        slo.close()

    def test_close_is_idempotent_and_url_clears(self):
        srv = OpsServer().start()
        url = srv.url
        assert url is not None and srv.port is not None
        srv.close()
        srv.close()
        assert srv.url is None and srv.port is None
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(url + "/healthz", timeout=2)


# ---------------------------------------------------------------------------
# flight-recorder tail sampling + goodput + dump-dir override
# ---------------------------------------------------------------------------

class TestTailSampling:
    def test_slowest_n_keeps_the_slowest(self):
        rec = FlightRecorder(tail_keep=3)
        for i, v in enumerate((50.0, 900.0, 10.0, 300.0, 700.0, 20.0)):
            rec.retire(_Trace(v, request_id=i))
        tails = rec.tail_traces()
        assert [s["ttft_ms"] for s in tails["slowest"]] == \
            [900.0, 700.0, 300.0]
        assert all(s["tail"] == "slowest" for s in tails["slowest"])
        assert tails["slo_violations_total"] == 0    # no SLO armed

    def test_violations_and_goodput_follow_the_armed_slo(self):
        rec = FlightRecorder()
        rec.set_tail_slo(100.0)
        for i, v in enumerate((10.0, 500.0, 30.0, 40.0)):
            rec.retire(_Trace(v, request_id=i))
        assert rec.slo_violations == 1
        tails = rec.tail_traces()
        assert [v["ttft_ms"] for v in tails["slo_violations"]] == [500.0]
        g = rec.goodput(window_s=60.0)
        assert g["total"] == 4 and g["good"] == 3
        assert g["goodput_rps"] > 0

    def test_retire_hook_fires_outside_lock_and_never_kills(self):
        rec = FlightRecorder()
        seen = []
        rec.add_retire_hook(lambda t: seen.append(t.ttft_ms))
        rec.add_retire_hook(lambda t: 1 / 0)     # hostile hook
        rec.retire(_Trace(42.0))
        assert seen == [42.0]
        assert rec.retired == 1

    def test_auto_dump_honors_env_dir_override(self, tmp_path,
                                               monkeypatch):
        target = tmp_path / "postmortems" / "nested"   # must be created
        monkeypatch.setenv("FLAGS_flight_dump_dir", str(target))
        rec = FlightRecorder()
        rec.record_cycle({"cycle_ms": 1.0})
        rec.retire(_Trace(12.0))
        path = rec.auto_dump("unit test")
        assert path is not None
        assert os.path.dirname(path) == str(target)
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "unit test"
        assert doc["tail_traces"]["recent"][0]["ttft_ms"] == 12.0

    def test_auto_dump_falls_back_to_tempdir(self, monkeypatch):
        monkeypatch.setenv("FLAGS_flight_dump_dir", "")
        rec = FlightRecorder()
        path = rec.auto_dump("fallback")
        assert path is not None and os.path.exists(path)
        os.unlink(path)
