"""The HTTP inference front door (PR 19).

Three layers, all deterministic:

* **wire protocol** — golden request/response JSON over real sockets
  against a stub engine (no model, no compiles): the non-streaming
  completion document, exact SSE framing (per-token ``data:`` chunks,
  finish chunk, ``[DONE]``), and every error body — 400 malformed/
  oversized/invalid, 401 unknown key, 404 unknown path, 429 over-budget
  with Retry-After, 503 queue-full with the scheduler's own estimate —
  with the server thread surviving each one;
* **weighted-fair admission** — mock-device Scheduler: a single
  admission class preserves FCFS byte-for-byte, and under a batch-lane
  backlog the interactive lane's 4x weight admits it ahead of most of
  the earlier-queued batch work;
* **shed metadata** — QueueFullError/DeadlineExceeded carry queue depth
  and the EWMA-derived wait estimate at raise time (None before the
  scheduler has admission evidence).
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.serving.frontdoor import LANES, FrontDoor, TokenBucket
from paddle_tpu.serving.scheduler import (DeadlineExceeded,
                                          GenerationRequest,
                                          QueueFullError, RequestCancelled)

from _mock_serving import MockDevice, mock_pool


# ---------------------------------------------------------------------------
# stub engine: the submit/stream contract without a model
# ---------------------------------------------------------------------------

class _StubHandle:
    def __init__(self, rid, toks, eos=None, error=None):
        self.id = rid
        self.tokens = []
        self.eos_token_id = eos
        self._toks = list(toks)
        self._error = error
        self.cancelled = False

    def stream(self):
        for t in self._toks:
            self.tokens.append(t)
            yield t
        if self._error is not None:
            raise self._error

    def cancel(self):
        self.cancelled = True


class _StubEngine:
    """Deterministic engine: token i of a request is ``100 + i``."""

    def __init__(self, eos=None, error=None, raises=None):
        self.eos = eos
        self.error = error
        self.raises = raises
        self.submits = []

    def submit(self, prompt, max_new_tokens, **kw):
        if self.raises is not None:
            raise self.raises
        self.submits.append((list(prompt), int(max_new_tokens), kw))
        toks = [100 + i for i in range(int(max_new_tokens))]
        if self.eos is not None:
            toks[-1] = self.eos
        return _StubHandle(len(self.submits), toks, eos=self.eos,
                           error=self.error)

    def stats(self):
        return {"queue_depth": 0, "active_requests": 0}


def _post(url, doc, headers=None, raw=None):
    req = urllib.request.Request(
        url, data=raw if raw is not None else json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


@pytest.fixture()
def door():
    eng = _StubEngine()
    d = FrontDoor(eng, tenant_limits={"starved": (5.0, 12.0)},
                  max_body_bytes=4096)
    srv = d.start()
    yield d, eng, srv.url + "/v1/completions", srv.url
    d.close()


# ---------------------------------------------------------------------------
# wire protocol: golden documents
# ---------------------------------------------------------------------------

class TestWireProtocol:
    def test_completion_golden(self, door):
        _d, eng, url, _base = door
        st, doc, _ = _post(url, {"prompt": [5, 6, 7], "max_tokens": 3},
                           headers={"X-Tenant": "acme"})
        assert st == 200
        assert doc == {
            "id": "cmpl-1",
            "object": "text_completion",
            "model": "paddle-tpu",
            "choices": [{"index": 0,
                         "text": "100 101 102",
                         "token_ids": [100, 101, 102],
                         "finish_reason": "length"}],
            "usage": {"prompt_tokens": 3, "completion_tokens": 3,
                      "total_tokens": 6}}
        # identity + lane landed on the engine call
        prompt, max_new, kw = eng.submits[0]
        assert (prompt, max_new) == ([5, 6, 7], 3)
        assert kw["tenant"] == "acme" and kw["lane"] == "interactive"

    def test_finish_reason_stop_on_eos(self):
        eng = _StubEngine(eos=9)
        d = FrontDoor(eng)
        srv = d.start()
        try:
            st, doc, _ = _post(srv.url + "/v1/completions",
                               {"prompt": [1], "max_tokens": 4})
            assert st == 200
            assert doc["choices"][0]["finish_reason"] == "stop"
            assert doc["choices"][0]["token_ids"][-1] == 9
        finally:
            d.close()

    def test_sse_stream_golden(self, door):
        _d, _eng, url, _base = door
        req = urllib.request.Request(
            url, data=json.dumps({"prompt": [5], "max_tokens": 2,
                                  "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers["Content-Type"] == "text/event-stream"
            frames = r.read().decode().strip().split("\n\n")
        assert all(f.startswith("data: ") for f in frames)
        payloads = [f[len("data: "):] for f in frames]
        assert payloads[-1] == "[DONE]"
        assert json.loads(payloads[0]) == {
            "id": "cmpl-1", "object": "text_completion.chunk",
            "model": "paddle-tpu",
            "choices": [{"index": 0, "token_id": 100, "text": "100 ",
                         "finish_reason": None}]}
        final = json.loads(payloads[-2])
        assert final["choices"][0]["finish_reason"] == "length"
        assert final["usage"] == {"prompt_tokens": 1,
                                  "completion_tokens": 2,
                                  "total_tokens": 3}
        # exactly: 2 token chunks + finish chunk + DONE
        assert len(payloads) == 4

    def test_deadline_mid_request_reported_not_erred(self):
        eng = _StubEngine(error=DeadlineExceeded("too slow"))
        d = FrontDoor(eng)
        srv = d.start()
        try:
            st, doc, _ = _post(srv.url + "/v1/completions",
                               {"prompt": [1], "max_tokens": 3})
            assert st == 200   # tokens produced before the deadline ship
            assert doc["choices"][0]["finish_reason"] == "deadline"
            assert doc["choices"][0]["token_ids"] == [100, 101, 102]
            # streaming: the terminal chunk carries the same reason
            req = urllib.request.Request(
                srv.url + "/v1/completions",
                data=json.dumps({"prompt": [1], "max_tokens": 1,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                frames = r.read().decode().strip().split("\n\n")
            final = json.loads(frames[-2][len("data: "):])
            assert final["choices"][0]["finish_reason"] == "deadline"
        finally:
            d.close()

    def test_models_endpoint_and_ops_share_port(self, door):
        _d, _eng, _url, base = door
        with urllib.request.urlopen(base + "/v1/models", timeout=30) as r:
            doc = json.loads(r.read())
        assert doc["data"][0]["id"] == "paddle-tpu"
        # the ops surface lives on the SAME server: one process, one port
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.status == 200
        with urllib.request.urlopen(base, timeout=30) as r:
            endpoints = json.loads(r.read())["endpoints"]
        assert "/v1/completions" in endpoints
        assert "/metrics" in endpoints


class TestWireErrors:
    def test_malformed_json_400_and_thread_survives(self, door):
        _d, _eng, url, _base = door
        st, doc, _ = _post(url, None, raw=b"{nope")
        assert st == 400
        assert doc["error"]["type"] == "invalid_request_error"
        assert "malformed JSON" in doc["error"]["message"]
        # the server thread survived: the next request is served
        st, _doc, _ = _post(url, {"prompt": [1], "max_tokens": 1})
        assert st == 200

    def test_oversized_body_400(self, door):
        _d, _eng, url, _base = door
        st, doc, _ = _post(url, {"prompt": [1] * 5000})
        assert st == 400
        assert "byte limit" in doc["error"]["message"]

    def test_prompt_validation_400(self, door):
        _d, _eng, url, _base = door
        for bad in ({"prompt": "text"}, {"prompt": []},
                    {"prompt": [1.5]}, {"max_tokens": 4},
                    {"prompt": [True, False]}):
            st, doc, _ = _post(url, bad)
            assert st == 400, bad
            assert doc["error"]["type"] == "invalid_request_error"

    def test_bad_lane_400(self, door):
        _d, _eng, url, _base = door
        st, doc, _ = _post(url, {"prompt": [1], "lane": "vip"})
        assert st == 400
        assert "lane" in doc["error"]["message"]

    def test_unknown_api_key_401(self):
        eng = _StubEngine()
        d = FrontDoor(eng, api_keys={"sk-good": "acme"})
        srv = d.start()
        try:
            url = srv.url + "/v1/completions"
            st, doc, _ = _post(url, {"prompt": [1]},
                               headers={"Authorization": "Bearer sk-bad"})
            assert st == 401
            assert doc["error"]["type"] == "invalid_api_key"
            st, _doc, _ = _post(url, {"prompt": [1]},
                                headers={"Authorization":
                                         "Bearer sk-good"})
            assert st == 200
            assert eng.submits[0][2]["tenant"] == "acme"
        finally:
            d.close()

    def test_unknown_path_404(self, door):
        _d, _eng, _url, base = door
        st, doc, _ = _post(base + "/v1/chat", {"prompt": [1]})
        assert st == 404
        assert "no such endpoint" in doc["error"]
        assert doc["see"] == "/"

    def test_rate_limit_429_with_retry_after(self, door):
        d, _eng, url, _base = door
        # burst 12: one 12-token-cost request drains it, the next sheds
        st1, _doc, _ = _post(url, {"prompt": [1] * 3, "max_tokens": 9},
                             headers={"X-Tenant": "starved"})
        st2, doc, hdrs = _post(url, {"prompt": [1] * 3, "max_tokens": 9},
                               headers={"X-Tenant": "starved"})
        assert (st1, st2) == (200, 429)
        assert doc["error"]["type"] == "rate_limit_exceeded"
        assert doc["error"]["tenant"] == "starved"
        assert doc["error"]["retry_after_s"] > 0
        assert int(hdrs["Retry-After"]) >= 1
        assert d.stats()["shed"] == {"starved": 1}

    def test_queue_full_503_with_scheduler_estimate(self):
        eng = _StubEngine(raises=QueueFullError(
            "admission queue is full", queue_depth=7, est_wait_s=2.5))
        d = FrontDoor(eng)
        srv = d.start()
        try:
            st, doc, hdrs = _post(srv.url + "/v1/completions",
                                  {"prompt": [1]})
            assert st == 503
            assert doc["error"]["type"] == "overloaded"
            assert doc["error"]["queue_depth"] == 7
            assert doc["error"]["est_wait_s"] == 2.5
            assert hdrs["Retry-After"] == "3"   # ceil(2.5)
        finally:
            d.close()

    def test_closed_engine_503(self):
        eng = _StubEngine(raises=RuntimeError("GenerationEngine is "
                                              "closed"))
        d = FrontDoor(eng)
        srv = d.start()
        try:
            st, doc, _ = _post(srv.url + "/v1/completions",
                               {"prompt": [1]})
            assert st == 503 and doc["error"]["type"] == "overloaded"
        finally:
            d.close()

    def test_static_sampling_mismatch_400(self):
        eng = _StubEngine(raises=ValueError(
            "per-request top_k=5 differs from the engine's static "
            "top_k"))
        d = FrontDoor(eng)
        srv = d.start()
        try:
            st, doc, _ = _post(srv.url + "/v1/completions",
                               {"prompt": [1], "top_k": 5})
            assert st == 400 and "top_k" in doc["error"]["message"]
        finally:
            d.close()


class TestTokenBucket:
    def test_admit_then_shed_then_refill(self):
        b = TokenBucket(rate=100.0, burst=10.0)
        assert b.try_take(10) == 0.0
        wait = b.try_take(5)
        assert wait > 0
        time.sleep(wait + 0.01)
        assert b.try_take(5) == 0.0

    def test_cost_above_burst_never_admits(self):
        b = TokenBucket(rate=1000.0, burst=4.0)
        assert b.try_take(100) > 0

    def test_rejects_nonpositive_config(self):
        with pytest.raises(ValueError):
            TokenBucket(rate=0, burst=4)
        with pytest.raises(ValueError):
            TokenBucket(rate=1, burst=-1)


# ---------------------------------------------------------------------------
# weighted-fair admission (mock-device scheduler)
# ---------------------------------------------------------------------------

class _GatedDevice(MockDevice):
    """First admission blocks on ``gate`` so a test can stage the queue
    before any admission decisions happen; admission order is then read
    back from ``order``."""

    def __init__(self, pool, gate=None):
        super().__init__(pool)
        self.gate = gate
        self.entered = threading.Event()   # first admission reached
        self._first = True
        self.order = []

    def do_prefill(self, req, slot):
        if self._first and self.gate is not None:
            self._first = False
            self.entered.set()
            self.gate.wait(timeout=30)
        self.order.append(req.id)
        super().do_prefill(req, slot)


def _req(prompt_len, max_new=1, **kw):
    return GenerationRequest(np.ones(prompt_len, np.int32), max_new, **kw)


class TestWeightedFairAdmission:
    def test_single_class_is_fcfs(self):
        gate = threading.Event()
        pool = mock_pool(slots=1)
        dev = _GatedDevice(pool, gate)
        sched = dev.scheduler()
        reqs = [sched.submit(_req(4)) for _ in range(6)]
        gate.set()
        for r in reqs:
            r.result(timeout=30)
        sched.close()
        assert dev.order == [r.id for r in reqs]

    def test_interactive_lane_outranks_batch_backlog(self):
        """6 batch requests queued FIRST, then 2 interactive: with the
        default 4:1 lane weights and 24-token feeds against the
        32-token quantum, the interactive pair admits right behind the
        first batch request instead of waiting out the backlog."""
        gate = threading.Event()
        pool = mock_pool(slots=1)
        dev = _GatedDevice(pool, gate)
        sched = dev.scheduler()
        head = sched.submit(_req(4))            # occupies the one slot
        assert dev.entered.wait(timeout=30)     # head is OUT of the queue
        batch = [sched.submit(_req(24, tenant="bulk", lane="batch"))
                 for _ in range(6)]
        inter = [sched.submit(_req(24, tenant="alice",
                                   lane="interactive"))
                 for _ in range(2)]
        gate.set()
        for r in [head] + batch + inter:
            r.result(timeout=30)
        sched.close()
        order = dev.order[1:]                # drop the gate request
        pos = {rid: i for i, rid in enumerate(order)}
        worst_inter = max(pos[r.id] for r in inter)
        # both interactive requests land in the first three admissions
        # despite six batch requests queued ahead of them
        assert worst_inter <= 2, order
        # nothing starves: every batch request still admitted
        assert sorted(order) == sorted(r.id for r in batch + inter)

    def test_custom_lane_weights_validated(self):
        pool = mock_pool(slots=1)
        with pytest.raises(ValueError):
            MockDevice(pool).scheduler(lane_weights={"batch": 0})
        sched = MockDevice(pool).scheduler(
            lane_weights={"batch": 2.5, "bulk": 1.0})
        assert sched._lane_weights["batch"] == 2.5
        assert sched._lane_weights["interactive"] == 4.0
        sched.close()

    def test_untagged_requests_share_default_class(self):
        r = GenerationRequest(np.ones(3, np.int32), 1)
        assert (r.lane, r.tenant) == ("interactive", "default")
        assert r.trace.tenant == "default"
        assert r.trace.lane == "interactive"


# ---------------------------------------------------------------------------
# shed metadata: queue depth + estimated wait at raise time
# ---------------------------------------------------------------------------

class TestShedMetadata:
    def test_queue_full_carries_depth_and_estimate(self):
        gate = threading.Event()
        pool = mock_pool(slots=1)
        dev = _GatedDevice(pool, gate)
        sched = dev.scheduler(max_queue=2)
        head = sched.submit(_req(4))
        assert dev.entered.wait(timeout=30)     # head is OUT of the queue
        queued = [sched.submit(_req(4)) for _ in range(2)]
        with pytest.raises(QueueFullError) as ei:
            sched.submit(_req(4))
        assert ei.value.queue_depth == 2
        # no admission evidence yet: the estimate honestly declines
        assert ei.value.est_wait_s is None
        gate.set()
        for r in [head] + queued:
            r.result(timeout=30)
        # >= 2 admissions banked the EWMA: estimates now materialize
        assert sched._admit_interval_s is not None
        est = sched._est_wait_s(3)
        assert est == pytest.approx(3 * sched._admit_interval_s)
        sched.close()

    def test_deadline_in_queue_carries_depth(self):
        gate = threading.Event()
        pool = mock_pool(slots=1)
        dev = _GatedDevice(pool, gate)
        sched = dev.scheduler()
        head = sched.submit(_req(4))
        doomed = sched.submit(_req(4, timeout=0.01))
        time.sleep(0.05)
        gate.set()
        head.result(timeout=30)
        with pytest.raises(DeadlineExceeded) as ei:
            doomed.result(timeout=30)
        assert ei.value.queue_depth is not None
        assert isinstance(ei.value.queue_depth, int)
        sched.close()

    def test_exception_attrs_default_none(self):
        e = QueueFullError("full")
        assert e.queue_depth is None and e.est_wait_s is None
        e = DeadlineExceeded("late", queue_depth=4, est_wait_s=0.5)
        assert (e.queue_depth, e.est_wait_s) == (4, 0.5)
        assert isinstance(e, TimeoutError)

    def test_cancelled_stream_finish_reason(self):
        eng = _StubEngine(error=RequestCancelled("cancelled"))
        d = FrontDoor(eng)
        srv = d.start()
        try:
            st, doc, _ = _post(srv.url + "/v1/completions",
                               {"prompt": [1], "max_tokens": 2})
            assert st == 200
            assert doc["choices"][0]["finish_reason"] == "cancelled"
        finally:
            d.close()

    def test_lanes_constant_matches_scheduler_defaults(self):
        pool = mock_pool(slots=1)
        sched = MockDevice(pool).scheduler()
        assert set(LANES) == set(sched._lane_weights)
        sched.close()


# ---------------------------------------------------------------------------
# fleet mount (PR 20): the door serves a real multi-replica fleet
# ---------------------------------------------------------------------------

class TestFleetFrontDoor:
    def test_door_over_two_replica_fleet_aggregates_tenants(self):
        """The submit contract is duck-typed, so an EngineFleet mounts
        behind the door unchanged: requests route round-robin across
        two REAL tiny-GPT replicas, and per-tenant retired counts are
        only true as the fleet-level sum."""
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        from paddle_tpu.serving import EngineFleet, GenerationEngine

        paddle.seed(3)
        model = GPTForPretraining(GPTConfig.tiny())
        model.eval()
        engines = [GenerationEngine(model, num_slots=2, max_len=32,
                                    min_bucket=8) for _ in range(2)]
        fleet = EngineFleet(engines, name="door-fleet")
        d = FrontDoor(fleet)
        srv = d.start()
        try:
            url = srv.url + "/v1/completions"
            for i, tenant in enumerate(("acme", "acme", "zoo", "acme")):
                st, doc, _ = _post(
                    url, {"prompt": [3 + i, 4, 5], "max_tokens": 3},
                    headers={"X-Tenant": tenant})
                assert st == 200
                assert len(doc["choices"][0]["token_ids"]) == 3
            s = fleet.stats()
            assert s["replicas_healthy"] == 2
            assert s["requests_retired"] == 4
            # round-robin actually spread the work over both replicas
            assert all(e.stats()["requests_retired"] >= 1
                       for e in engines)
            # the per-tenant truth only exists as the fleet-level sum
            tens = s["tenants"]
            assert tens["acme"]["retired"] == 3
            assert tens["zoo"]["retired"] == 1
        finally:
            d.close()
            fleet.close()
