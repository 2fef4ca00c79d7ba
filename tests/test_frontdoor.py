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

    def test_the_first_request_is_stamped_once(self, door):
        """``stats()["first_request_t"]``: nothing before a request, the
        first one's arrival after it, and no later request moves it."""
        d, _eng, url, base = door
        assert d.stats()["first_request_t"] is None
        with urllib.request.urlopen(base + "/v1/models", timeout=30):
            pass                         # no completion request: no stamp
        assert d.stats()["first_request_t"] is None
        t0 = time.perf_counter()
        assert _post(url, {"prompt": [5], "max_tokens": 1})[0] == 200
        t1 = time.perf_counter()
        first = d.stats()["first_request_t"]
        assert t0 <= first <= t1
        # a refused request is a request all the same, and moves nothing
        assert _post(url, {"prompt": "text"})[0] == 400
        assert _post(url, {"prompt": [5], "max_tokens": 1})[0] == 200
        assert d.stats()["first_request_t"] == first

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


# ---------------------------------------------------------------------------
# the door's ONE stream writer (PR 43): a launch's tokens for every stream
# arrive in one put and leave through one thread
# ---------------------------------------------------------------------------

import socket  # noqa: E402
import sys  # noqa: E402

from paddle_tpu.framework.monitor import stat_get  # noqa: E402

from _mock_serving import MockEngine  # noqa: E402


def _sse_expected(rid, tokens, n_prompt, finish_reason):
    """The stream's body as the thread-a-connection writer wrote it: a
    ``json.dumps`` of each document, framed."""
    def frame(doc):
        return b"data: " + json.dumps(doc).encode() + b"\n\n"
    out = b"".join(frame(
        {"id": rid, "object": "text_completion.chunk",
         "model": "paddle-tpu",
         "choices": [{"index": 0, "token_id": int(t), "text": f"{int(t)} ",
                      "finish_reason": None}]}) for t in tokens)
    n = len(tokens)
    out += frame(
        {"id": rid, "object": "text_completion.chunk",
         "model": "paddle-tpu",
         "choices": [{"index": 0, "token_id": None, "text": "",
                      "finish_reason": finish_reason}],
         "usage": {"prompt_tokens": n_prompt, "completion_tokens": n,
                   "total_tokens": n_prompt + n}})
    return out + b"data: [DONE]\n\n"


def _open_stream(srv, prompt, max_tokens, rcvbuf=None, **body):
    """A streamed completion on a raw socket: the request is sent,
    nothing is read yet."""
    data = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": True, **body}).encode()
    s = socket.socket()
    if rcvbuf is not None:          # before connect: it sizes the window
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.settimeout(30)
    s.connect(("127.0.0.1", srv.port))
    s.sendall((f"POST /v1/completions HTTP/1.0\r\n"
               f"Content-Type: application/json\r\n"
               f"Content-Length: {len(data)}\r\n\r\n").encode() + data)
    return s


def _read_body(s):
    """Everything up to the close, less status line and headers."""
    raw = b""
    while True:
        part = s.recv(65536)
        if not part:
            break
        raw += part
    s.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.0 200"), head[:80]
    return body


def _handle_of(eng, prompt):
    """The accepted request with this prompt (handler threads race, so
    ``eng.handles`` is in no test's order)."""
    t_end = time.monotonic() + 30
    while time.monotonic() < t_end:
        for h in list(eng.handles):
            if list(h.prompt) == list(prompt):
                return h
        time.sleep(0.005)
    raise AssertionError(f"no request with prompt {prompt} was accepted")


def _frames(body):
    return [json.loads(f[len(b"data: "):])
            for f in body.split(b"\n\n") if f and f != b"data: [DONE]"]


@pytest.fixture()
def mock_door():
    """A door over the REAL scheduler on a chained mock device: a
    request's output follows from its prompt."""
    made = []

    def make(slots=4, max_len=1024, **dev_kw):
        eng = MockEngine(MockDevice(
            mock_pool(slots=slots, max_len=max_len, block_size=16),
            chain=True, **dev_kw), max_queue=256)
        d = FrontDoor(eng)
        made.append((d, eng))
        return d, eng, d.start()
    yield make
    for d, eng in made:
        d.close()
        eng.close()


class TestStreamWriter:
    @pytest.mark.parametrize("eos", [None, "last"])
    def test_sse_bytes_are_json_dumps_of_each_chunk(self, mock_door, eos):
        _d, eng, srv = mock_door()
        prompt, n = [5, 6, 7], 9
        want = MockDevice.expected(prompt, n)
        body = {} if eos is None else {"eos_token_id": want[-1]}
        if eos is not None:         # ends at the first EOS in the output
            want = want[:want.index(want[-1]) + 1]
        got = _read_body(_open_stream(srv, prompt, n, **body))
        assert got == _sse_expected(
            f"cmpl-{eng.handles[0].id}", want, len(prompt),
            "length" if eos is None else "stop")

    def test_stalled_client_delays_nobody_and_loses_nothing(self, mock_door):
        d, eng, srv = mock_door(slots=4)
        # accepted sockets inherit the listener's buffer: a stream that
        # nobody reads stalls after a few KB, not after megabytes
        srv._httpd.socket.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                     4096)
        n = 600                                    # ~90 KB of chunks
        stalled = _open_stream(srv, [3, 4], n, rcvbuf=2048)
        others = [_open_stream(srv, [10 + i, 4], n) for i in range(3)]
        for i, s in enumerate(others):             # whole, while one stalls
            assert _read_body(s) == _sse_expected(
                f"cmpl-{_handle_of(eng, [10 + i, 4]).id}",
                MockDevice.expected([10 + i, 4], n), 2, "length")
        h = _handle_of(eng, [3, 4])
        h.result(timeout=30)                       # generated to its end
        assert d.stats()["stream_writer_deferred"] > 0
        assert stat_get("serving/stream_writer_deferred") > 0
        assert _read_body(stalled) == _sse_expected(
            f"cmpl-{h.id}", MockDevice.expected([3, 4], n), 2, "length")

    def test_disconnect_cancels_the_request(self, mock_door):
        _d, eng, srv = mock_door(decode_delay=0.002)
        s = _open_stream(srv, [1, 2], 900)
        assert s.recv(4096)                         # the stream is live
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     b"\x01\x00\x00\x00\x00\x00\x00\x00")   # RST at close
        s.close()
        h = _handle_of(eng, [1, 2])
        with pytest.raises(RequestCancelled):
            h.result(timeout=30)
        assert 0 < h.emitted < 900

    @pytest.mark.parametrize("how", ["deadline", "cancelled"])
    def test_terminal_errors_pick_the_finish_reason(self, mock_door, how):
        _d, eng, srv = mock_door(decode_delay=0.005)
        body = {"timeout_s": 0.5} if how == "deadline" else {}
        s = _open_stream(srv, [1, 2], 900, **body)
        h = _handle_of(eng, [1, 2])
        if how == "cancelled":
            while not h.emitted:
                time.sleep(0.005)
            h.cancel()
        frames = _frames(_read_body(s))
        assert 0 < len(frames) - 1 < 900
        assert [f["choices"][0]["token_id"] for f in frames[:-1]] \
            == MockDevice.expected([1, 2], len(frames) - 1) == h.tokens
        assert frames[-1]["choices"][0]["finish_reason"] == how
        assert frames[-1]["usage"]["completion_tokens"] == len(frames) - 1

    @pytest.mark.parametrize("path", ["stream", "result", "unary"])
    def test_without_a_sink_the_handle_keeps_its_queue(self, mock_door,
                                                       path):
        _d, eng, srv = mock_door()
        want = MockDevice.expected([5, 6], 7)
        if path == "unary":
            st, doc, _ = _post(srv.url + "/v1/completions",
                               {"prompt": [5, 6], "max_tokens": 7})
            assert st == 200 and eng.handles[0].sink is None
            assert doc["choices"][0]["token_ids"] == want
            assert doc["choices"][0]["finish_reason"] == "length"
            return
        h = eng.submit([5, 6], 7)
        if path == "stream":
            assert list(h.stream()) == want
        else:
            assert list(h.result(timeout=30)) == [5, 6] + want

    def test_a_sink_takes_a_launch_in_one_put(self):
        """The scheduler's half alone: every launch is ONE put for all
        the requests of a sink, a request's end rides with its last
        token, and ``stream()`` of such a handle refuses."""
        class Sink:
            def __init__(self):
                self.batches = []

            def put(self, batch):
                self.batches.append(list(batch))
        sink = Sink()
        dev = MockDevice(mock_pool(slots=4, max_len=64), chain=True)
        eng = MockEngine(dev)
        try:
            hs = [eng.submit([7 + i], 5 + i, sink=sink) for i in range(4)]
            for h in hs:
                h.result(timeout=30)
        finally:
            eng.close()
        assert len(sink.batches) <= len(dev.launches)
        per = {h: [] for h in hs}
        for batch in sink.batches:
            assert len({id(h) for h, it in batch if it is not None}) \
                == sum(it is not None for h, it in batch)   # a token each
            for h, it in batch:
                per[h].append(it)
        for i, h in enumerate(hs):
            assert per[h] == MockDevice.expected([7 + i], 5 + i) + [None]
        with pytest.raises(RuntimeError, match="sink"):
            next(hs[0].stream())

    def test_backlog_wakes_the_writer_a_launch_not_a_token(self, mock_door):
        slots, n = 16, 40
        d, eng, srv = mock_door(slots=slots, decode_delay=0.01)
        chunks0 = stat_get("serving/stream_writer_chunks")
        socks = [_open_stream(srv, [2 + i, 9], n) for i in range(slots)]
        for i, s in enumerate(socks):
            toks = [f["choices"][0]["token_id"]
                    for f in _frames(_read_body(s))[:-1]]
            assert toks == MockDevice.expected([2 + i, 9], n)
        st = d.stats()
        assert st["stream_writer_chunks"] == slots * n
        assert stat_get("serving/stream_writer_chunks") - chunks0 \
            == slots * n
        launches = len(eng.device.launches)
        # a wake a launch (a few more: a put may find the writer awake),
        # and most launches emit for most of the 16 slots
        assert st["stream_writer_wakes"] <= launches + 8
        assert st["stream_writer_chunks"] / st["stream_writer_wakes"] \
            >= slots / 2
        assert st["stream_writer_deferred"] == 0

    def test_the_writers_span_is_no_serving_span(self, mock_door):
        """A span a wake, on the writer's thread — and NOT named
        ``serving/*``: the benchmark's readers take every such name for
        a stretch of the scheduler thread (``lib/host_spans.py``)."""
        from paddle_tpu.profiler import span as P
        _d, _eng, srv = mock_door(decode_delay=0.002)
        with P.profile():
            _read_body(_open_stream(srv, [4, 5], 20))
            evs = P.events()
        sched = {e["tid"] for e in evs if e["name"] == "serving/emit"}
        mine = {e["tid"] for e in evs
                if e["name"] == "frontdoor/stream_write"}
        assert len(sched) == 1 and len(mine) == 1 and mine != sched
        assert not [e["name"] for e in evs if e["tid"] in mine
                    and e["name"].startswith("serving/")]

    def test_many_streams_whole_and_ordered_under_fast_switching(
            self, mock_door):
        """More streams than cores with the interpreter switching
        threads every 10 us: the inbox is shared by the scheduler, 32
        handler threads and the writer — a lost or reordered item breaks
        a stream's token sequence."""
        slots, n = 32, 60
        _d, eng, srv = mock_door(slots=slots)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            socks = [_open_stream(srv, [1 + i, 3], n) for i in range(slots)]
            bodies = [_read_body(s) for s in socks]
        finally:
            sys.setswitchinterval(old)
        for i, body in enumerate(bodies):
            assert body == _sse_expected(
                f"cmpl-{_handle_of(eng, [1 + i, 3]).id}",
                MockDevice.expected([1 + i, 3], n), 2, "length")
