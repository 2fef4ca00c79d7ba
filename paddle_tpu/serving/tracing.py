"""Per-request lifecycle tracing: the serving SLO measurement substrate.

Every :class:`~.scheduler.GenerationRequest` carries a
:class:`RequestTrace` — an append-only list of timestamped lifecycle
events (submit, admitted, prefill start/end, prefix hit with tokens
saved, each prefill chunk, each preemption, first token, finish/cancel/
deadline/error) plus a per-token decode stamp for every emitted token.
From those stamps the trace DERIVES the two serving latencies that
matter:

* **TTFT** (time to first token) — ``first_token - submit``, the
  queueing + prefill latency a client actually feels;
* **TPOT** (time per output token) — the mean inter-token decode
  interval after the first token, the streaming "smoothness" latency.

Both are per-request and per-engine by construction: the engine's
``stats()`` percentiles come from ITS OWN retired traces (via the
:class:`~.flight_recorder.FlightRecorder`), never from the
process-global monitor histograms two engines would contaminate.

Timestamps are ``time.perf_counter()`` host stamps taken in scheduler /
caller host code only — never inside a traced (jitted) function, where
a host read would either burn a trace-time constant or force a sync
(the ``serving-host-sync`` self-lint rule walks this module like the
rest of the package).

Chrome-trace export: when a :func:`profiler.span.profile` session is
armed, a finished trace exports itself as a REQUEST LANE — a synthetic
tid per request carrying queued/prefill/decode phase spans — next to
the scheduler thread's per-cycle spans, so one trace file shows both
views of the same stall (``export_spans``).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

from ..profiler import span as _prof

__all__ = ["RequestTrace", "TERMINAL_EVENTS", "REQUEST_LANE_BASE"]

# lifecycle events that end a request (exactly one per trace)
TERMINAL_EVENTS = ("finish", "cancelled", "deadline", "error")

# chrome-trace lane offset: request lanes use tid = BASE + request id so
# they sort together below the real (python thread ident) lanes
REQUEST_LANE_BASE = 1_000_000_000


class RequestTrace:
    """Timestamped lifecycle of one generation request.

    Owned by the scheduler thread for writes (``mark`` /
    ``stamp_token``); callers read it freely AFTER ``handle.result()``
    returns — the terminal mark happens-before ``_done`` is set.
    """

    __slots__ = ("request_id", "events", "token_times", "token_ids",
                 "token_passes", "tenant", "lane")

    def __init__(self, request_id: int, t_submit: Optional[float] = None,
                 tenant: Optional[str] = None, lane: Optional[str] = None):
        self.request_id = int(request_id)
        # multi-tenancy identity (the front door's admission class):
        # carried on the trace so tail samples, /tracez and the per-
        # tenant goodput accounting can attribute a retired request
        # without the live GenerationRequest object
        self.tenant = tenant
        self.lane = lane
        self.events: List[Tuple[str, float, Optional[dict]]] = [
            ("submit", t_submit if t_submit is not None
             else time.perf_counter(), None)]
        self.token_times: List[float] = []   # one host stamp per token
        # generation by diffusion over blocks: per emitted token its id
        # and the pass of its block in which it was fixed — the ORDER a
        # block was filled in, which the token stamps (a block's tokens
        # are emitted together, in position order) do not show. Empty
        # for a model that yields one token a step
        self.token_ids: List[int] = []
        self.token_passes: List[int] = []

    # -- writers (scheduler thread) ----------------------------------------
    def mark(self, name: str, t: Optional[float] = None, **meta) -> None:
        self.events.append((name, t if t is not None
                            else time.perf_counter(), meta or None))

    def stamp_token(self, t: float, token: Optional[int] = None,
                    fixed_pass: Optional[int] = None) -> None:
        self.token_times.append(t)
        if fixed_pass is not None:
            self.token_ids.append(int(token))
            self.token_passes.append(int(fixed_pass))

    # -- readers -----------------------------------------------------------
    def t(self, name: str) -> Optional[float]:
        """Timestamp of the FIRST occurrence of ``name``, or None."""
        for n, ts, _ in self.events:
            if n == name:
                return ts
        return None

    def count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.events if n == name)

    @property
    def submitted_at(self) -> float:
        return self.events[0][1]

    @property
    def finished_at(self) -> Optional[float]:
        for n, ts, _ in reversed(self.events):
            if n in TERMINAL_EVENTS:
                return ts
        return None

    @property
    def completed(self) -> bool:
        return self.finished_at is not None

    @property
    def ttft_ms(self) -> Optional[float]:
        """Submit → first token, the latency a client feels."""
        if not self.token_times:
            return None
        return (self.token_times[0] - self.submitted_at) * 1e3

    @property
    def tpot_ms(self) -> Optional[float]:
        """Mean inter-token interval after the first token (needs >= 2
        tokens — a single-token request has no decode cadence)."""
        if len(self.token_times) < 2:
            return None
        return (self.token_times[-1] - self.token_times[0]) * 1e3 \
            / (len(self.token_times) - 1)

    @property
    def admission_wait_ms(self) -> Optional[float]:
        """Submit → first admission: the queueing share of TTFT (the
        lane-wait the weighted-fair admission exists to bound)."""
        t_adm = self.t("admitted")
        if t_adm is None:
            return None
        return (t_adm - self.submitted_at) * 1e3

    @property
    def decode_intervals_ms(self) -> List[float]:
        tt = self.token_times
        return [(b - a) * 1e3 for a, b in zip(tt, tt[1:])]

    def timeline(self) -> List[Dict[str, Any]]:
        """JSON-friendly event list, times in ms relative to submit."""
        t0 = self.submitted_at
        out = [{"event": n, "t_ms": round((ts - t0) * 1e3, 3),
                **({"meta": m} if m else {})}
               for n, ts, m in self.events]
        for i, ts in enumerate(self.token_times):
            out.append({"event": "token", "i": i,
                        "t_ms": round((ts - t0) * 1e3, 3)})
        out.sort(key=lambda e: e["t_ms"])
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Self-contained JSON dict of the whole trace — the unit the
        flight recorder tail-samples and ``/tracez`` serves. Derived
        latencies are materialized here so a retained snapshot stays
        meaningful after the live trace object is gone."""
        return {"request": self.request_id,
                **({"tenant": self.tenant} if self.tenant else {}),
                **({"lane": self.lane} if self.lane else {}),
                "completed": self.completed,
                "ttft_ms": self.ttft_ms,
                "tpot_ms": self.tpot_ms,
                "tokens": len(self.token_times),
                **({"token_passes": list(self.token_passes)}
                   if self.token_passes else {}),
                "preempts": self.count("preempt"),
                "prefix_hits": self.count("prefix_hit"),
                "timeline": self.timeline()}

    # -- chrome-trace export -----------------------------------------------
    def export_spans(self) -> None:
        """Emit this (finished) request as a chrome-trace lane into the
        armed profiler span buffer: one whole-lifetime span plus
        queued/prefill/decode phase children and zero-duration marks for
        preemptions and prefix hits. No-op (one bool check) when no
        profile() session is active — the scheduler calls this from the
        terminal path unconditionally."""
        if not _prof.is_active():
            return
        t0, t1 = self.submitted_at, self.finished_at
        if t1 is None:
            t1 = time.perf_counter()
        tid = REQUEST_LANE_BASE + self.request_id
        _prof.set_thread_name(f"request {self.request_id}", tid=tid)
        _prof.add_event(
            f"request {self.request_id}", "serving/request", t0, t1,
            tid=tid, depth=0,
            args={"ttft_ms": self.ttft_ms, "tpot_ms": self.tpot_ms,
                  "tokens": len(self.token_times),
                  "preempts": self.count("preempt")})
        name = f"request {self.request_id}"
        t_adm = self.t("admitted")
        if t_adm is not None:
            _prof.add_event("queued", "serving/request", t0, t_adm,
                            tid=tid, depth=1, parent=name)
        pending_ps = None   # pair prefill_start/_end sequentially: a
        for n, ts, meta in self.events:   # preempted request has several
            if n == "prefill_start":
                pending_ps = ts
            elif n == "prefill_end":
                if pending_ps is not None:
                    _prof.add_event("prefill", "serving/request",
                                    pending_ps, ts, tid=tid, depth=1,
                                    parent=name, args=meta)
                    pending_ps = None
            elif n in ("preempt", "prefix_hit"):
                _prof.add_event(n, "serving/request", ts, ts, tid=tid,
                                depth=1, parent=name, args=meta)
        if self.token_times:
            _prof.add_event("decode", "serving/request",
                            self.token_times[0], t1, tid=tid, depth=1,
                            parent=name,
                            args={"tokens": len(self.token_times)})
        # block generation: one span a block, from the block before it to
        # the stamp its tokens share, with the passes that fixed them
        start, i = self.t("prefill_end") or t0, 0
        while i < len(self.token_passes):
            j = i
            while j < len(self.token_passes) \
                    and self.token_times[j] == self.token_times[i]:
                j += 1
            _prof.add_event("block", "serving/request", start,
                            self.token_times[i], tid=tid, depth=2,
                            parent="decode",
                            args={"tokens": j - i,
                                  "fixed_in_pass": self.token_passes[i:j]})
            start, i = self.token_times[i], j

    def __repr__(self):
        return (f"<RequestTrace #{self.request_id} events="
                f"{len(self.events)} tokens={len(self.token_times)} "
                f"ttft_ms={self.ttft_ms} tpot_ms={self.tpot_ms}>")
