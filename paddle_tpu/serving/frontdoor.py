"""HTTP inference front door: OpenAI-style /v1/completions on the ops port.

The serving stack so far ends at a Python API — ``engine.submit(...)``
returns a handle, ``handle.stream()`` yields tokens. This module puts
that API on a socket with the three things a shared endpoint needs and
a library call does not:

* **wire protocol** — ``POST /v1/completions`` takes OpenAI-style JSON
  (``prompt`` as token ids — the repo has no tokenizer, so text is the
  space-joined id string), answers a completion document, or streams
  Server-Sent Events (``stream: true``): one ``data:`` chunk per token,
  a final chunk carrying ``finish_reason``, then ``data: [DONE]``;
* **admission control** — a per-tenant token bucket (cost = prompt
  tokens + ``max_tokens``) sheds over-budget tenants with 429 and an
  honest ``Retry-After`` BEFORE the request touches the engine, and a
  full scheduler queue answers 503 with a ``Retry-After`` derived from
  the scheduler's own admission-rate EWMA (``QueueFullError.est_wait_s``);
* **identity** — the tenant comes off the wire (``Authorization:
  Bearer <key>`` through the ``api_keys`` map, or the ``X-Tenant``
  header) and rides the request into the scheduler's weighted-fair
  (lane, tenant) admission classes, the flight recorder's per-tenant
  goodput accounting and the shed counters, so one noisy tenant is
  visible and boundable instead of anonymous.

Transport: the stdlib threaded HTTP server shared with
:class:`~.opsserver.OpsServer` — ``FrontDoor.mount(ops)`` registers its
routes in the ops route table so ``/metrics`` and ``/v1/completions``
share one process and one port (``FrontDoor.start()`` builds and owns
an ``OpsServer`` when there is none to mount on). Threaded, not async:
the container bakes in no web framework. A connection's handler thread
parses, admits and submits; a unary response it collects itself from
``handle.stream()``. A STREAMED response's bytes are not its to write:
the door owns ONE stream-writer thread (:class:`_StreamWriter`), the
request is submitted with that writer as its ``sink``, the scheduler
hands a launch's tokens for all the door's streams over in one ``put``,
and the writer formats and sends them, a batch a launch, on non-blocking
sockets — what a slow client's socket does not take waits in that
stream's own buffer for a ``selectors`` poll and holds nobody else up.
The handler thread sends the headers, opens the stream at the writer and
sleeps until its last byte is out: 64 live streams are 64 parked
threads and one that wakes a launch, not 64 that wake a token each to
take the interpreter lock from the turn that feeds the device. The
scheduler's one-fetch-per-cycle device contract is untouched: the front
door never holds a device handle (the ``ops-handler-sync`` self-lint
rule walks this module), it only enqueues work and drains host-side
tokens.

Error surface (all JSON, the server thread survives every one):

=====  ====================================================================
400    malformed JSON, oversized body, missing/invalid ``prompt`` or
       ``lane``, per-request ``top_k``/``top_p`` differing from the
       engine's static sampling structure, over-capacity prompt
401    ``api_keys`` configured and the bearer key is unknown
404    unknown path (the ops server's canonical body)
429    tenant over token-bucket budget; ``Retry-After`` from the refill
       rate, shed counted per tenant (``serving/tenant_shed``)
503    scheduler queue full (``Retry-After`` from the admission EWMA)
       or the engine is closed
=====  ====================================================================
"""
from __future__ import annotations

import collections
import json
import math
import selectors
import socket
import threading
import time
import traceback
from typing import Any, Dict, Iterable, List, Optional, Tuple

from ..framework import metrics as _metrics
from ..framework.monitor import stat_add
from ..profiler import span as _prof
from .scheduler import DeadlineExceeded, QueueFullError, RequestCancelled

__all__ = ["FrontDoor", "TokenBucket", "LANES"]

# the scheduler's admission lanes (weights live on the engine); the wire
# rejects anything else with 400 instead of minting ad-hoc classes
LANES = ("interactive", "batch")

_MODEL_ID = "paddle-tpu"


class TokenBucket:
    """Classic token bucket: ``burst`` capacity, ``rate`` tokens/s refill.

    ``try_take(cost)`` is the whole API: 0.0 means admitted (cost
    debited), a positive return is the seconds until the bucket could
    cover ``cost`` — the honest ``Retry-After``. A cost above ``burst``
    can never be admitted (the level is capped); the returned wait is
    computed as if the bucket were uncapped — always positive, so the
    caller always sheds — and a client that retries on schedule and
    still sees 429 should split the request. Thread-safe; monotonic
    clock."""

    __slots__ = ("rate", "burst", "_level", "_t", "_lock")

    def __init__(self, rate: float, burst: float):
        if rate <= 0 or burst <= 0:
            raise ValueError(
                f"rate and burst must be > 0, got rate={rate} burst={burst}")
        self.rate = float(rate)
        self.burst = float(burst)
        self._level = float(burst)
        self._t = time.monotonic()
        self._lock = threading.Lock()

    def try_take(self, cost: float) -> float:
        cost = float(cost)
        with self._lock:
            now = time.monotonic()
            self._level = min(self.burst,
                              self._level + (now - self._t) * self.rate)
            self._t = now
            if cost <= self._level:
                self._level -= cost
                return 0.0
            return (cost - self._level) / self.rate

    def __repr__(self):
        return f"<TokenBucket rate={self.rate}/s burst={self.burst}>"


class FrontDoor:
    """The OpenAI-style completions surface over one engine (or fleet).

    ``engine`` is anything with the ``submit(prompt_ids, max_new_tokens,
    **kwargs) -> handle`` contract (a ``GenerationEngine`` or an
    ``EngineFleet``). Admission knobs:

    * ``rate_tokens_per_s`` / ``burst_tokens`` — the default per-tenant
      token bucket (None = no rate limit);
    * ``tenant_limits`` — ``{tenant: (rate, burst)}`` overrides;
    * ``api_keys`` — ``{bearer_key: tenant}``; when set, a request with
      an ``Authorization: Bearer`` header MUST present a known key
      (401 otherwise). Requests without one fall back to ``X-Tenant``
      or ``default_tenant`` — key-only deployments should front this
      with their key requirement (this is a paper repro, not a vault).
    * ``max_body_bytes`` — requests with a larger Content-Length are
      refused with 400 before the body is read.

    Mount on an existing ops server (``door.mount(srv)``) or let
    ``door.start()`` build one::

        door = FrontDoor(engine, rate_tokens_per_s=500, burst_tokens=2000)
        srv = door.start()               # owns an OpsServer
        requests.post(srv.url + "/v1/completions", json={...})
        door.close()
    """

    def __init__(self, engine: Any, *,
                 rate_tokens_per_s: Optional[float] = None,
                 burst_tokens: Optional[float] = None,
                 tenant_limits: Optional[Dict[str, Tuple[float, float]]] = None,
                 api_keys: Optional[Dict[str, str]] = None,
                 default_tenant: str = "default",
                 default_max_tokens: int = 16,
                 max_body_bytes: int = 1 << 20,
                 registry: Optional[_metrics.MetricsRegistry] = None):
        self._engine = engine
        self._rate = None if rate_tokens_per_s is None \
            else float(rate_tokens_per_s)
        self._burst = float(burst_tokens) if burst_tokens is not None \
            else (None if self._rate is None else 4.0 * self._rate)
        self._tenant_limits = dict(tenant_limits or {})
        self._api_keys = dict(api_keys or {})
        self._default_tenant = str(default_tenant)
        self._default_max_tokens = int(default_max_tokens)
        self._max_body_bytes = int(max_body_bytes)
        self._registry = registry if registry is not None \
            else _metrics.registry()
        self._buckets: Dict[str, TokenBucket] = {}
        self._lock = threading.Lock()
        self._served = 0
        self._streamed = 0
        # time.perf_counter() when the first completion request came
        # through the door (set once): where the program first sees its
        # clients — a benchmark's ramp starts here
        self._first_request_t: Optional[float] = None
        self._shed: Dict[str, int] = {}
        self._ops: Optional[Any] = None      # owned server, if start()ed
        self._writer: Optional[_StreamWriter] = None    # see mount()

    # -- mounting ------------------------------------------------------------
    def mount(self, ops: Any) -> "FrontDoor":
        """Register this front door's routes in an
        :class:`~.opsserver.OpsServer` route table — completions and
        /metrics then share that server's process and port."""
        ops.add_route("POST", "/v1/completions", self._handle_completions)
        ops.add_route("GET", "/v1/models", self._handle_models)
        self._stream_writer()
        return self

    def _stream_writer(self) -> "_StreamWriter":
        """The door's one stream writer: started when the routes go
        live, stopped by ``close()``."""
        with self._lock:
            if self._writer is None or self._writer.closing:
                self._writer = _StreamWriter()
            return self._writer

    def start(self, host: str = "127.0.0.1", port: int = 0):
        """Build, mount on and start an owned ops server bound to the
        engine (health/tracez reflect it); returns the server — read
        ``srv.url`` for the base address. ``close()`` shuts it down."""
        from .opsserver import OpsServer
        if self._ops is None:
            self._ops = OpsServer(target=self._engine, host=host, port=port,
                                  registry=self._registry)
            self.mount(self._ops)
        return self._ops.start()

    def close(self) -> None:
        ops, self._ops = self._ops, None
        if ops is not None:
            ops.close()
        writer = self._writer       # kept: stats() reads its counters
        if writer is not None:
            writer.close()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- admission -----------------------------------------------------------
    def _bucket(self, tenant: str) -> Optional[TokenBucket]:
        with self._lock:
            b = self._buckets.get(tenant)
            if b is None:
                if tenant in self._tenant_limits:
                    rate, burst = self._tenant_limits[tenant]
                elif self._rate is not None:
                    rate, burst = self._rate, self._burst
                else:
                    return None
                b = self._buckets[tenant] = TokenBucket(rate, burst)
            return b

    def _resolve_tenant(self, h) -> Tuple[Optional[str], Optional[str]]:
        """(tenant, None) or (None, error message) for a 401."""
        auth = h.headers.get("Authorization", "")
        if auth.startswith("Bearer ") and self._api_keys:
            key = auth[len("Bearer "):].strip()
            tenant = self._api_keys.get(key)
            if tenant is None:
                return None, "unknown API key"
            return tenant, None
        tenant = h.headers.get("X-Tenant")
        if tenant:
            return str(tenant).strip(), None
        return self._default_tenant, None

    def _count_shed(self, tenant: str, reason: str) -> None:
        stat_add("serving/tenant_shed")
        with self._lock:
            self._shed[tenant] = self._shed.get(tenant, 0) + 1
        try:
            self._registry.inc("serving_tenant_shed", 1,
                               tenant=tenant, reason=reason)
        except Exception:                                # noqa: BLE001
            pass

    # -- wire helpers --------------------------------------------------------
    @staticmethod
    def _reply(h, code: int, doc: Any,
               headers: Optional[Dict[str, str]] = None) -> None:
        data = json.dumps(doc, default=repr).encode()
        h.send_response(code)
        h.send_header("Content-Type", "application/json")
        h.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            h.send_header(k, str(v))
        h.end_headers()
        h.wfile.write(data)

    @classmethod
    def _reply_error(cls, h, code: int, message: str, etype: str,
                     headers: Optional[Dict[str, str]] = None,
                     **extra) -> None:
        cls._reply(h, code,
                   {"error": {"message": message, "type": etype, **extra}},
                   headers)

    def _read_body(self, h) -> Tuple[Optional[dict], Optional[str]]:
        """(parsed body, None) or (None, error) — the error is the 400
        message; an oversized Content-Length is refused UNREAD so a
        hostile body never buffers."""
        try:
            length = int(h.headers.get("Content-Length") or 0)
        except (TypeError, ValueError):
            return None, "invalid Content-Length"
        if length <= 0:
            return None, "a JSON body is required"
        if length > self._max_body_bytes:
            return None, (f"body of {length} bytes exceeds the "
                          f"{self._max_body_bytes} byte limit")
        raw = h.rfile.read(length)
        try:
            body = json.loads(raw)
        except (ValueError, UnicodeDecodeError) as e:
            return None, f"malformed JSON body: {e}"
        if not isinstance(body, dict):
            return None, "the JSON body must be an object"
        return body, None

    @staticmethod
    def _parse_prompt(body: dict) -> Tuple[Optional[list], Optional[str]]:
        prompt = body.get("prompt", body.get("prompt_ids"))
        if isinstance(prompt, int):
            prompt = [prompt]
        if not isinstance(prompt, list) or not prompt \
                or not all(isinstance(t, int) and not isinstance(t, bool)
                           for t in prompt):
            return None, ("'prompt' must be a non-empty list of token ids "
                          "(ints) — this serving stack is tokenizer-free")
        return prompt, None

    # -- finish-reason / documents -------------------------------------------
    @staticmethod
    def _finish_reason(handle, error: Optional[BaseException]) -> str:
        if isinstance(error, DeadlineExceeded):
            return "deadline"
        if isinstance(error, RequestCancelled):
            return "cancelled"
        if error is not None:
            return "error"
        eos = getattr(handle, "eos_token_id", None)
        toks = getattr(handle, "tokens", ())
        if eos is not None and toks and toks[-1] == eos:
            return "stop"
        return "length"

    @staticmethod
    def _completion_doc(rid: int, tokens: Iterable[int], n_prompt: int,
                        finish_reason: str) -> dict:
        toks = [int(t) for t in tokens]
        return {"id": f"cmpl-{rid}",
                "object": "text_completion",
                "model": _MODEL_ID,
                "choices": [{"index": 0,
                             "text": " ".join(str(t) for t in toks),
                             "token_ids": toks,
                             "finish_reason": finish_reason}],
                "usage": {"prompt_tokens": n_prompt,
                          "completion_tokens": len(toks),
                          "total_tokens": n_prompt + len(toks)}}

    # -- route handlers ------------------------------------------------------
    def _handle_models(self, h) -> None:
        self._reply(h, 200, {"object": "list",
                             "data": [{"id": _MODEL_ID, "object": "model",
                                       "owned_by": "paddle_tpu"}]})

    def _handle_completions(self, h) -> None:
        if self._first_request_t is None:
            t = time.perf_counter()
            with self._lock:            # the first burst arrives together
                if self._first_request_t is None:
                    self._first_request_t = t
        tenant, auth_err = self._resolve_tenant(h)
        if auth_err is not None:
            self._reply_error(h, 401, auth_err, "invalid_api_key")
            return
        body, body_err = self._read_body(h)
        if body_err is not None:
            self._reply_error(h, 400, body_err, "invalid_request_error")
            return
        prompt, prompt_err = self._parse_prompt(body)
        if prompt_err is not None:
            self._reply_error(h, 400, prompt_err, "invalid_request_error")
            return
        lane = str(body.get("lane") or h.headers.get("X-Lane")
                   or "interactive")
        if lane not in LANES:
            self._reply_error(
                h, 400, f"lane must be one of {list(LANES)}, got {lane!r}",
                "invalid_request_error")
            return
        try:
            max_tokens = int(body.get("max_tokens",
                                      self._default_max_tokens))
        except (TypeError, ValueError):
            self._reply_error(h, 400, "'max_tokens' must be an int",
                              "invalid_request_error")
            return
        stream = bool(body.get("stream", False))

        # per-tenant token-bucket admission BEFORE the engine sees the
        # request: cost is the request's whole token footprint
        bucket = self._bucket(tenant)
        if bucket is not None:
            retry_s = bucket.try_take(len(prompt) + max(1, max_tokens))
            if retry_s > 0:
                self._count_shed(tenant, "rate_limit")
                self._reply_error(
                    h, 429,
                    f"tenant {tenant!r} is over its token budget; retry "
                    f"in {retry_s:.2f}s", "rate_limit_exceeded",
                    headers={"Retry-After": max(1, math.ceil(retry_s))},
                    retry_after_s=round(retry_s, 3), tenant=tenant)
                return

        kwargs: Dict[str, Any] = {"tenant": tenant, "lane": lane}
        for wire, kw in (("temperature", "temperature"),
                         ("do_sample", "do_sample"),
                         ("top_k", "top_k"), ("top_p", "top_p"),
                         ("eos_token_id", "eos_token_id"),
                         ("timeout_s", "timeout")):
            if body.get(wire) is not None:
                kwargs[kw] = body[wire]
        if stream:
            # the sink rides the submit: a first token can land before
            # this thread has as much as sent the headers
            writer = kwargs["sink"] = self._stream_writer()
        try:
            handle = self._engine.submit(prompt, max_tokens, **kwargs)
        except QueueFullError as e:
            self._count_shed(tenant, "queue_full")
            retry = getattr(e, "est_wait_s", None)
            self._reply_error(
                h, 503, str(e), "overloaded",
                headers={"Retry-After": max(1, math.ceil(retry))
                         if retry else 1},
                queue_depth=getattr(e, "queue_depth", None),
                est_wait_s=retry, tenant=tenant)
            return
        except (ValueError, TypeError) as e:
            self._reply_error(h, 400, str(e), "invalid_request_error")
            return
        except RuntimeError as e:
            # PoolCapacityError is a RuntimeError too — but capacity is
            # the CLIENT's prompt being too big: that one is a 400
            if type(e).__name__ == "PoolCapacityError":
                self._reply_error(h, 400, str(e), "invalid_request_error")
            else:
                self._reply_error(h, 503, str(e), "overloaded")
            return

        with self._lock:
            self._served += 1
            if stream:
                self._streamed += 1
        if stream:
            self._stream_response(h, handle, len(prompt), writer)
        else:
            self._unary_response(h, handle, len(prompt))

    # -- response bodies -----------------------------------------------------
    def _unary_response(self, h, handle, n_prompt: int) -> None:
        # collect by draining the host-side stream queue — NEVER
        # handle.result(): that returns the padded device row and is
        # exactly the sync shape the ops-handler-sync lint rule bans
        tokens, err = [], None
        try:
            for tok in handle.stream():
                tokens.append(int(tok))
        except (DeadlineExceeded, RequestCancelled) as e:
            err = e
        self._reply(h, 200, self._completion_doc(
            handle.id, tokens, n_prompt, self._finish_reason(handle, err)))

    def _stream_response(self, h, handle, n_prompt: int,
                         writer: "_StreamWriter") -> None:
        """SSE over HTTP/1.0 connection-close framing: one ``data:``
        JSON chunk per token as the scheduler produces it, a final
        chunk with ``finish_reason`` + usage, then ``data: [DONE]`` —
        all written by the door's stream writer; this thread sends the
        headers, leaves the connection there and sleeps until the
        stream's last byte is out."""
        sock = h.connection
        try:
            h.send_response(200)
            h.send_header("Content-Type", "text/event-stream")
            h.send_header("Cache-Control", "no-cache")
            h.send_header("X-Accel-Buffering", "no")
            h.end_headers()
        except OSError:
            # client went away before its headers: stop generating
            handle.cancel()
            sock = None
        done = writer.open(handle, sock, n_prompt)
        if getattr(handle, "sink", None) is not writer:
            # an engine that took no sink answers through the handle's
            # own queue: this thread carries it over, an item a put
            err = None
            try:
                for tok in handle.stream():
                    writer.put([(handle, int(tok))])
            except Exception as e:                       # noqa: BLE001
                err = e
            writer.put([(handle, err)])
        done.wait()

    # -- introspection -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            w = self._writer
            return {"served": self._served,
                    "streamed": self._streamed,
                    "first_request_t": self._first_request_t,
                    "shed": dict(self._shed),
                    "shed_total": sum(self._shed.values()),
                    "tenants_seen": sorted(
                        set(self._buckets) | set(self._shed)),
                    "stream_writer_wakes": w.wakes if w else 0,
                    "stream_writer_chunks": w.chunks if w else 0,
                    "stream_writer_deferred": w.deferred if w else 0}

    def __repr__(self):
        s = self.stats()
        return (f"<FrontDoor served={s['served']} "
                f"shed={s['shed_total']} engine={self._engine!r}>")


def _sse(doc: Any) -> bytes:
    return b"data: " + json.dumps(doc).encode() + b"\n\n"


def _token_chunk(rid: str, tok: int) -> dict:
    return {"id": rid, "object": "text_completion.chunk",
            "model": _MODEL_ID,
            "choices": [{"index": 0, "token_id": tok, "text": f"{tok} ",
                         "finish_reason": None}]}


def _final_chunk(rid: str, finish_reason: str, n_prompt: int,
                 n: int) -> dict:
    return {"id": rid, "object": "text_completion.chunk",
            "model": _MODEL_ID,
            "choices": [{"index": 0, "token_id": None, "text": "",
                         "finish_reason": finish_reason}],
            "usage": {"prompt_tokens": n_prompt, "completion_tokens": n,
                      "total_tokens": n_prompt + n}}


class _Stream:
    """One open SSE stream, the writer thread's alone once opened."""

    __slots__ = ("handle", "sock", "n_prompt", "parts", "n", "buf",
                 "ended", "waiting", "dead", "done")

    def __init__(self, handle, sock, n_prompt: int):
        self.handle, self.sock, self.n_prompt = handle, sock, n_prompt
        # a token's chunk is three constant pieces around its digits,
        # cut from the document json.dumps gives: the same bytes
        mark = 7777777
        self.parts = _sse(_token_chunk(f"cmpl-{handle.id}", mark)) \
            .rsplit(b"%d" % mark, 2)
        self.n = 0                  # token chunks formatted
        self.buf = bytearray()      # formatted, not yet taken by the socket
        self.ended = False          # the terminal item has come
        self.waiting = False        # registered for writability
        self.dead = sock is None    # nobody to write to (any more)
        self.done = threading.Event()   # the last byte is out, or never will


class _StreamWriter:
    """The door's ONE writer of streamed responses (module doc).

    A sink of the scheduler's (``put``: a launch's ``(handle, item)``s,
    any thread) and the place a handler thread leaves its connection
    (``open``). Everything else — the streams, their buffers, every
    ``send`` — belongs to the writer thread, which sleeps in ONE
    ``select`` over a wake socket and the sockets that owe bytes.
    """

    def __init__(self):
        self._inbox: collections.deque = collections.deque()
        self._streams: Dict[Any, _Stream] = {}
        self._early: Dict[Any, list] = {}   # items that beat their open
        self._sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ)
        self._lock = threading.Lock()       # open() against the exit
        self.closing = self._closed = False
        self.wakes = self.chunks = self.deferred = 0
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="paddle-stream-writer")
        self._thread.start()

    # -- any thread ----------------------------------------------------------
    def put(self, batch) -> None:
        """Take ``[(handle, item), ...]`` in emit order: an int is a
        token, ``None`` or an exception ends the stream."""
        self._inbox.append(batch)
        try:
            self._wake_w.send(b"\0")
        except OSError:
            pass        # a full pipe holds wakes enough; closed: dropped

    def open(self, handle, sock, n_prompt: int) -> threading.Event:
        """Hand the writer a connection whose headers are out
        (``sock`` None: they could not be sent, the stream is only
        waited out). The event is set once the stream's last byte is
        written, or as soon as there is nobody to write to."""
        st = _Stream(handle, sock, n_prompt)
        if sock is not None:
            sock.setblocking(False)
        with self._lock:
            if self._closed:
                st.dead = True
            else:
                self.put(st)
        if st.dead:
            handle.cancel()
            st.done.set()
        return st.done

    def close(self) -> None:
        self.closing = True
        self.put(())
        self._thread.join(timeout=5)

    # -- the writer thread ---------------------------------------------------
    def _run(self) -> None:
        _prof.set_thread_name("stream writer")
        while not self.closing:
            ready = self._sel.select()
            try:
                self._turn([key.data for key, _ in ready
                            if key.data is not None])
            except Exception:                            # noqa: BLE001
                traceback.print_exc()     # the door's streams live on
        with self._lock:
            self._closed = True
            left = [e for e in self._inbox if isinstance(e, _Stream)]
        for st in left + list(self._streams.values()):
            if not st.ended:
                st.handle.cancel()
            self._drop(st)                # parked handlers go home
        self._sel.close()
        self._wake_r.close()
        self._wake_w.close()

    def _turn(self, writable: List[_Stream]) -> None:
        try:
            while self._wake_r.recv(4096):
                pass
        except BlockingIOError:
            pass
        # NOT a ``serving/`` name: the benchmark's readers take every
        # ``serving/*`` span for a stretch of the scheduler thread
        with _prof.record("frontdoor/stream_write", "serving"):
            for st in writable:           # owed bytes first, in order
                self._flush(st)
            touched, chunks = {}, 0
            while self._inbox:
                entry = self._inbox.popleft()
                if isinstance(entry, _Stream):
                    self._streams[entry.handle] = entry
                    entry = [(entry.handle, item) for item in
                             self._early.pop(entry.handle, ())]
                for handle, item in entry:
                    st = self._streams.get(handle)
                    if st is None:
                        self._early.setdefault(handle, []).append(item)
                    elif st.dead:
                        if type(item) is not int:     # its end: forgotten
                            del self._streams[handle]
                    else:
                        chunks += self._format(st, item)
                        if not st.waiting:
                            touched[st] = None
            for st in touched:
                self._flush(st)
        if chunks:
            self.wakes += 1
            self.chunks += chunks
            stat_add("serving/stream_writer_wakes")
            stat_add("serving/stream_writer_chunks", chunks)

    def _format(self, st: _Stream, item) -> int:
        """``item``'s bytes onto the stream's buffer; 1 for a token's
        chunk."""
        if type(item) is int:
            digits = b"%d" % item
            head, mid, tail = st.parts
            st.buf += head + digits + mid + digits + tail
            st.n += 1
            return 1
        st.ended = True
        st.buf += _sse(_final_chunk(
            f"cmpl-{st.handle.id}",
            FrontDoor._finish_reason(st.handle, item),
            st.n_prompt, st.n)) + b"data: [DONE]\n\n"
        return 0

    def _flush(self, st: _Stream) -> None:
        """Send what the socket takes now; what it does not stays in the
        stream's buffer until the selector says it is writable."""
        try:
            sent = st.sock.send(st.buf)
        except BlockingIOError:
            sent = 0
        except OSError:
            # client went away mid-stream: stop generating for it
            st.handle.cancel()
            self._drop(st)
            return
        del st.buf[:sent]
        if st.buf:
            if not st.waiting:
                st.waiting = True
                self.deferred += 1
                stat_add("serving/stream_writer_deferred")
                self._sel.register(st.sock, selectors.EVENT_WRITE, st)
        elif st.ended:
            self._drop(st)
        elif st.waiting:
            st.waiting = False
            self._sel.unregister(st.sock)

    def _drop(self, st: _Stream) -> None:
        """The stream is over for its connection and its handler goes
        home. One that ended is forgotten; one whose client left stays,
        dead, until the scheduler has sent its terminal item too."""
        if st.waiting:
            st.waiting = False
            self._sel.unregister(st.sock)
        st.dead = True
        st.buf.clear()
        if st.ended:
            self._streams.pop(st.handle, None)
        st.done.set()
