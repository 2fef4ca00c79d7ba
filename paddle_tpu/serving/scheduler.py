"""Continuous-batching scheduler: admit, launch, retire — every turn.

The loop at the heart of ``GenerationEngine``. Unlike the gather-and-run
``inference.BatchingEngine`` (whole batch enters and leaves together),
membership of the in-flight batch changes EVERY turn:

* **admit** — pop from the bounded admission queue into free pool
  slots under WEIGHTED-FAIR scheduling: queued requests are classed by
  (lane, tenant) and served by weighted deficit-round-robin (priority
  lanes — ``interactive`` outweighs ``batch`` 4:1 by default, so a
  batch prompt flood cannot starve interactive TTFT while idle
  capacity still flows to batch; one queued class degenerates to
  FCFS exactly). Admission is pure host bookkeeping: blocks reserved
  (a prefix-cache match adopted), ``req.pending_feed`` armed;
* **launch** — ONE fused ragged, pool-donated launch a turn mixes
  ``prefill_budget`` tokens of prompt chunks with every decode row.
  Decode is never budget-charged, so a prompt burst cannot monopolize a
  launch, and the first generated token emits from the launch that feeds
  the final chunk (``serving/prefill_chunks``/``serving/chunk_tokens``,
  per-launch ``chunk_tokens`` in the flight recorder);
* **land** — TWO LAUNCHES ARE IN FLIGHT: a turn dispatches launch N+1
  and only then fetches and emits launch N, so the device goes from one
  launch to the next with no host in between and the host's half of a
  launch (the single fetch, each new token to its request's queue or
  the launch's tokens in ONE ``put`` to the sink its requests were
  submitted with, the thread or threads that wakes) runs in the
  device's shadow. What the next plan
  needs — positions, how much of a feed is drained, who reaches
  ``max_new_tokens`` — is host arithmetic applied at DISPATCH; the one
  thing it cannot know, a decode row's input token, the step reads from
  the previous launch's un-fetched result on the device
  (``GenerationRequest.in_flight``). The pipeline drains where the turn
  sees it must (speculative mode, pool pressure, an empty plan, the
  launch that opens a busy stretch): the serial cycle is this loop
  drained every turn, not a second loop;
* **retire** — finished (EOS / token budget), cancelled and
  deadline-expired slots are freed at the emit that finds them, so
  their capacity is reused by the next admit — mid-flight, not at batch
  end. EOS, a cancel and a deadline are learned one launch late: the
  row the request already holds in the launch after is dropped when
  that launch lands (``late_rows``).

Generation by diffusion over blocks (a model whose decoder spec says
``block_length`` B > 1, ``models/decoder_spec.py``) runs through the SAME
turn: a decode slot's rows are the B rows of its current block, a launch
is one PASS over them — a denoising pass fixes positions and keeps
nothing in the cache, so the pool's length stays — and the block's
tokens are emitted together, in position order, when its last denoising
pass lands. The COMMIT of a finished block — its rows once more with
their final tokens, whose K/V the cache keeps; the pool's length moves by
B — RIDES with the next block's first denoising pass: the slot gets 2 B
rows in that launch, the finished block's and then the next block's, so
a block of B costs its slot as many launches as it has denoising passes
and no launch is spent on rows nobody reads. A request's last block
takes no commit at all; a commit alone (B rows, no token) is the same
ride for a slot whose plan holds no rows of a next block, which
``_chunk_plan`` never hands out. The schedule is static
(``low_confidence_static``), so which pass a slot is in is host
arithmetic (``GenerationRequest.block_pass``) and two launches stay in
flight: what the next pass cannot know, the block's state — for a ride
the finished block's final tokens — the step reads from the un-fetched
result of the launch before.

Backpressure is explicit: a full queue raises :class:`QueueFullError`
in ``submit`` (the caller sheds load, nothing queues unboundedly), and
a per-request deadline turns into :class:`DeadlineExceeded` whether the
request is still queued or already decoding.

Observability (the serving SLO spine, ISSUE 6): every request carries a
:class:`~.tracing.RequestTrace` of timestamped lifecycle events
(submit → admitted → prefill → first token → per-token stamps →
finish/cancel/deadline, plus preemptions and prefix hits), from which
TTFT and TPOT derive per request; every LAUNCH writes a record into the
always-on bounded :class:`~.flight_recorder.FlightRecorder` (sweep /
admit / plan / decode-dispatch stamped in the turn that dispatched it,
host-fetch / emit in the turn that landed it; occupancy, queue depth,
``overlapped``, ``late_rows``) so a scheduler stall is debuggable
postmortem without the profiler armed. When a ``profiler.profile()``
session IS armed, the same phases additionally emit ``serving/cycle``
spans (one a turn) with children that each carry their LAUNCH's number,
and each finished request exports a chrome-trace lane.

Threading contract: ``submit``/``cancel`` may be called from any
thread; the loop body, the pool, and all slot state belong to the
scheduler thread alone (trace marks and launch records included — all
host stamps, taken outside every traced fn). The ONLY device→host sync
in the loop is :func:`_fetch` below — everything else stays async
(enforced by the ``serving-host-sync`` self-lint rule over this
package).
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..framework.monitor import stat_add, stat_observe
from ..profiler import memory as _memory
from ..profiler import span as _prof
from .flight_recorder import FlightRecorder
from .paging import PoolExhaustedError
from .tracing import RequestTrace

__all__ = ["QueueFullError", "DeadlineExceeded", "RequestCancelled",
           "GenerationRequest", "Scheduler"]


class QueueFullError(RuntimeError):
    """The admission queue is at capacity — shed load and retry later.

    Carries the scheduler's shed metadata, stamped AT RAISE TIME, so a
    wire layer can answer with an honest ``Retry-After`` instead of a
    guess: ``queue_depth`` (entries queued when the submit was refused)
    and ``est_wait_s`` (depth x the EWMA inter-admission interval;
    ``None`` until the scheduler has admitted at least two requests)."""

    def __init__(self, message: str = "", *,
                 queue_depth: Optional[int] = None,
                 est_wait_s: Optional[float] = None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.est_wait_s = est_wait_s


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed before it finished (it may have
    produced some tokens first — they were streamed). Like
    :class:`QueueFullError` it carries ``queue_depth``/``est_wait_s``
    stamped at raise time — a client whose deadline died in the queue
    learns how deep the queue was and what a retry would likely wait."""

    def __init__(self, message: str = "", *,
                 queue_depth: Optional[int] = None,
                 est_wait_s: Optional[float] = None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.est_wait_s = est_wait_s


class RequestCancelled(RuntimeError):
    """The request was cancelled via ``GenerationRequest.cancel()``."""


_DONE = object()          # stream terminator sentinel
_NO_PASS = (-1, 0, False)  # a slot a launch gave no pass of a block


def _fetch(device_array):
    """THE one device→host sync of the serving loop: one fetch per
    cycle (the launch's batch of tokens). Every
    other transfer in this package is host→device and async. The rule
    below is the package-wide lint (analysis/selflint.py
    ``serving-host-sync``); this call site is the argued exception."""
    import jax
    return np.asarray(jax.device_get(device_array))  # lint: ok


class GenerationRequest:
    """One submitted generation: the scheduler's work item AND the
    caller's handle (``stream()`` / ``result()`` / ``cancel()``).

    Caller-side API is thread-safe; the mutable decode state
    (``emitted``, ``last_token``) belongs to the scheduler thread.
    """

    _ids = itertools.count()

    def __init__(self, prompt: np.ndarray, max_new_tokens: int, *,
                 do_sample: bool = False, temperature: float = 1.0,
                 eos_token_id: Optional[int] = None, pad_token_id: int = 0,
                 timeout: Optional[float] = None,
                 tenant: str = "default", lane: str = "interactive",
                 sink=None):
        self.id = next(self._ids)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.eos_token_id = None if eos_token_id is None \
            else int(eos_token_id)
        self.pad_token_id = int(pad_token_id)
        # multi-tenancy identity: the (lane, tenant) pair is the
        # weighted-fair admission class — untagged traffic all lands in
        # one class, which degenerates to the old FCFS order exactly
        self.tenant = str(tenant)
        self.lane = str(lane)
        self._preempted = False     # preemption victims outrank the queue
        # hierarchical-KV promotion state (engines with a host
        # tier): the in-flight PromotionTicket this request waits on,
        # and whether its admission was served through a promotion
        # (engine classifies the hit as tier=host)
        self._promo_ticket = None
        self._tier_promoted = False
        self.submitted_at = time.perf_counter()
        self.deadline = None if timeout is None \
            else self.submitted_at + float(timeout)
        # scheduler-side decode state
        self.tokens: List[int] = []     # generated so far (incl. EOS)
        self.emitted = 0
        self.last_token: Optional[int] = None
        # generated tokens DISPATCHED but not yet fetched: a launch whose
        # row produces this request's next token raises it, the emit of
        # that launch lowers it. While it is nonzero the newest token
        # exists only in the un-fetched result of the launch in flight —
        # the next launch reads it there, on the device
        self.in_flight = 0
        # the not-yet-fed feed tokens (the prompt past a prefix-cache
        # hit; after a preemption, the request's own history too) —
        # drained in token-budget chunks through the fused ragged step,
        # mixed into decode launches. Rebuilt at every admission; the
        # first generated token emits from the launch that feeds the
        # final chunk.
        self.pending_feed: List[int] = []
        # generation by diffusion over blocks (module doc): passes of
        # the current block DISPATCHED so far; the feed's tokens past
        # its last block boundary, which open the first block already
        # fixed; and the block's state as the newest LANDED pass left it
        # (token ids [B], the pass each position was fixed in [B])
        self.block_pass = 0
        self.block_given: List[int] = []
        self.block_state: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.first_token_at: Optional[float] = None
        self._last_token_at: Optional[float] = None
        # lifecycle trace (host stamps; the scheduler marks events, the
        # caller reads derived TTFT/TPOT after result() returns)
        self.trace = RequestTrace(self.id, t_submit=self.submitted_at,
                                  tenant=self.tenant, lane=self.lane)
        self._recorder: Optional[FlightRecorder] = None   # set at submit
        # caller-side plumbing: where the tokens and the terminal item
        # go. Without a sink, this request's own queue (``stream()``);
        # a sink is anything with ``put(batch)``, ``batch`` a list of
        # ``(request, item)`` in emit order — an int a token, ``None``
        # or the error at the end — and takes a launch's emissions for
        # ALL its requests in one call (``Scheduler._land``). Given at
        # construction: a first token can land right after the submit
        self.sink = sink
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self.error: Optional[BaseException] = None
        self._cancelled = False

    # -- caller side -------------------------------------------------------
    def cancel(self) -> None:
        """Ask the scheduler to drop this request; queued requests are
        rejected at admission, active ones retire at the next decode
        cycle. Already-finished requests are unaffected."""
        self._cancelled = True

    @property
    def cancelled(self) -> bool:
        return self._cancelled and not self._done.is_set()

    def stream(self):
        """Iterator of generated token ids, yielded as each is produced
        (the first from the launch that fed the prompt's last chunk). Raises the terminal error
        (:class:`RequestCancelled` / :class:`DeadlineExceeded`) after
        any tokens produced before it."""
        if self.sink is not None:
            raise RuntimeError(
                f"request {self.id} was submitted with a sink: its "
                f"tokens go there, not to stream()")
        _prof.set_thread_name(
            f"stream consumer ({threading.current_thread().name})")
        while True:
            item = self._q.get()
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request finishes; returns the full sequence
        ``[prompt_len + max_new_tokens]`` int32 with post-EOS positions
        filled with ``pad_token_id`` — exactly ``models.generate``'s
        output row for this request."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.id} not finished within {timeout}s")
        if self.error is not None:
            raise self.error
        pad = self.max_new_tokens - len(self.tokens)
        return np.concatenate([
            self.prompt, np.asarray(self.tokens, np.int32),
            np.full(pad, self.pad_token_id, np.int32)])

    def done(self) -> bool:
        return self._done.is_set()

    # -- scheduler side ----------------------------------------------------
    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None \
            and (now or time.perf_counter()) > self.deadline

    def block_input(self, block_length: int):
        """The block's state as the host knows it before the pass about
        to be dispatched: ``(token ids [B], fixed-in-pass [B])`` — a new
        block holds the feed's leftover tokens, given, and is open
        elsewhere; a block between passes is as its last landed pass
        left it. (The state of a pass still in flight is on the device:
        the step reads it there.)"""
        from ..models.generation import BLOCK_GIVEN, BLOCK_UNFIXED
        if self.block_pass and self.block_state is not None:
            return self.block_state
        n = len(self.block_given)
        tok = np.zeros(block_length, np.int32)
        tok[:n] = self.block_given
        fixed = np.full(block_length, BLOCK_UNFIXED, np.int32)
        fixed[:n] = BLOCK_GIVEN
        return tok, fixed

    def block_commits_next(self, rule) -> bool:
        """Whether the block is finished and its commit is what comes
        next: every denoising pass the positions the prompt did not give
        take (``GenerationRule.passes``) has been dispatched."""
        return self.block_pass == rule.passes(
            rule.block_length - len(self.block_given))

    def _deliver(self, item, out: Optional[dict]) -> None:
        """One item on its way to the caller: into the request's own
        queue, or — with a sink — into ``out``, the landing launch's
        outbox (sink → batch), which the scheduler hands over once the
        per-slot loop is through; outside a landing (``out`` None) the
        sink gets it at once."""
        sink = self.sink
        if sink is None:
            self._q.put(_DONE if item is None else item)
        elif out is None:
            sink.put([(self, item)])
        else:
            out.setdefault(sink, []).append((self, item))

    def _emit(self, tok: int, fixed_pass: Optional[int] = None,
              now: Optional[float] = None,
              out: Optional[dict] = None) -> None:
        now = time.perf_counter() if now is None else now
        if self.first_token_at is None:
            self.first_token_at = now
            stat_observe("serving/ttft_ms",
                         (now - self.submitted_at) * 1e3)
            self.trace.mark("first_token", t=now)
            if self._recorder is not None:
                self._recorder.record_event(self.id, "first_token", t=now)
        else:
            # the streaming cadence: one inter-token sample per decoded
            # token after the first (re-fed tokens never land here)
            stat_observe("serving/tpot_ms",
                         (now - self._last_token_at) * 1e3)
        self._last_token_at = now
        self.trace.stamp_token(now, tok, fixed_pass)
        self.tokens.append(tok)
        self.emitted += 1
        self.last_token = tok
        self._deliver(tok, out)

    def _finish(self, error: Optional[BaseException] = None,
                out: Optional[dict] = None) -> None:
        self.error = error
        if error is None:
            name = "finish"
        elif isinstance(error, RequestCancelled):
            name = "cancelled"
        elif isinstance(error, DeadlineExceeded):
            name = "deadline"
        else:
            name = "error"
        self.trace.mark(name,
                        **({} if error is None else {"error": repr(error)}))
        if self._recorder is not None:
            self._recorder.record_event(
                self.id, name,
                meta=None if error is None else {"error": repr(error)})
            self._recorder.retire(self.trace)
        self.trace.export_spans()   # chrome-trace lane; no-op unarmed
        self._done.set()
        self._deliver(error, out)

    def __repr__(self):
        return (f"<GenerationRequest #{self.id} prompt={len(self.prompt)} "
                f"max_new={self.max_new_tokens} emitted={self.emitted}>")


class Scheduler:
    """The continuous-batching loop over a :class:`~.paging.PagedKVPool`.

    Device work is delegated to engine-provided callables so the
    policy here stays host-pure and unit-testable:

    * ``do_prefill(request, slot)`` — the admission hook: reserve the
      feed's blocks (adopting a prefix-cache match), set the slot's
      position, arm ``request.pending_feed``. No program runs;
    * ``do_chunked_step(slot_requests, plan, prev) -> token array`` —
      DISPATCH the turn's ONE ragged launch (``plan``: rows a slot —
      budgeted prompt chunks and the decode rows) and return its result
      UN-fetched (a device array; plain numpy passes through). It is
      called with positions and ``pending_feed`` as they stand BEFORE
      this launch (the scheduler advances them right after the call).
      ``prev`` is ``None``, or ``(result, slots)``: the un-fetched
      result of the launch dispatched before this one and the slots
      whose decode row must take its input token from it, because the
      host has not seen that token yet (``request.last_token`` is one
      behind for them). The result is fetched a turn LATER, after the
      next launch has been dispatched, by the scheduler's one windowed
      ``_fetch`` — a step that syncs internally would serialize the
      pipeline and hide the fetch inside ``decode_dispatch_ms``. Every
      slot gets a token (garbage for inactive and mid-feed slots);
    * ``do_copy(dst, src)`` — device block copy (copy-on-write append);
    * ``do_spec_step(slot_requests, plan, spec)`` — the speculative
      verify launch, see below; fetched in the turn that dispatched it.

    ``prefill_budget`` is the per-launch CHUNK token budget: decode rows
    are never charged, so a prompt burst cannot monopolize a launch.
    """

    def __init__(self, pool, do_prefill: Callable,
                 do_chunked_step: Callable, *,
                 max_queue: int = 128, prefill_budget: Optional[int] = None,
                 do_copy: Optional[Callable] = None,
                 do_spec_step: Optional[Callable] = None,
                 spec_k: int = 0,
                 recorder: Optional[FlightRecorder] = None,
                 lane_weights: Optional[Dict[str, float]] = None,
                 generation=None):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self._pool = pool
        self._do_prefill = do_prefill
        self._do_chunked = do_chunked_step
        self.prefill_chunks = 0          # chunk launches fed (slot-cycles)
        self.chunk_tokens = 0            # prompt tokens fed via chunks
        # speculative decoding: ``do_spec_step(active,
        # plan, spec) -> [2S + S*spec_k + 1] device array`` — per slot
        # the accepted-prefix length, the corrected/sampled token, the
        # echoed draft tokens (the host never saw the device-side
        # proposals) and the logits-finite sentinel, all in ONE fetch.
        # Decode slots contribute min(spec_k, remaining) candidate rows
        # to the fused launch instead of 1; feed slots chunk as before.
        self._do_spec = do_spec_step
        self._spec = do_spec_step is not None
        self._spec_k = int(spec_k)
        if self._spec and self._spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        # the served model's generation rule (models/decoder_spec.py):
        # one token a sequence a step unless it says block_length > 1
        from ..models.decoder_spec import GenerationRule
        self._gen = generation if generation is not None \
            else GenerationRule()
        self._block = int(self._gen.block_length)
        if self._spec and self._block > 1:
            raise ValueError(
                "speculative decoding does not compose with block "
                "generation")
        self.spec_cycles = 0             # cycles that verified >= 1 slot
        self.spec_proposed = 0           # draft tokens verified
        self.spec_accepted = 0           # draft tokens accepted
        # serving numerics sentinel: the step appends a logits-finite
        # flag past the token row (models/generation.py), riding the one
        # windowed _fetch — cycles whose logits went NaN/Inf are counted
        # here and flagged in the flight-recorder cycle record
        self.nonfinite_cycles = 0
        # always-on postmortem telemetry: bounded cycle/event rings +
        # the per-engine TTFT/TPOT reservoirs stats() reads
        self.recorder = recorder if recorder is not None \
            else FlightRecorder()
        self._cycle = 0
        self._rec: Optional[dict] = None   # the record being stamped
        # the pipeline: the launch dispatched but not yet fetched (its
        # record, rows and un-fetched result — see _dispatch), and the
        # records of the launches that landed in the current turn
        self._inflight: Optional[dict] = None
        self._landed: List[dict] = []
        self._landed_at = 0.0
        # the landing launch's outbox: sink → [(request, item)] in emit
        # order, handed over ONCE when the per-slot loop is through
        # (_land); None between landings
        self._out: Optional[dict] = None
        self._do_copy = do_copy          # device block copy (COW append)
        self.preempts = 0                # requests evicted mid-flight
        self.late_rows = 0               # rows launched for ended requests
        self._max_queue = int(max_queue)
        # prompt tokens fed per cycle, split over the feeding slots
        # (_chunk_plan)
        self._prefill_budget = int(prefill_budget or pool.max_len)
        if self._prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {self._prefill_budget}")
        self._queue: List[GenerationRequest] = []
        # weighted deficit-round-robin admission (the priority lanes):
        # each queued (lane, tenant) pair is a fairness class; every
        # rotation credits a class `quantum x lane weight` prefill
        # tokens of deficit and the class at the rotation head admits
        # while its deficit covers its head-of-line request's feed
        # cost. With ONE class queued the selector short-circuits to
        # plain FCFS.
        # Deficits are capped so an idle class cannot bank unbounded
        # credit and then monopolize admission for whole seconds.
        self._lane_weights: Dict[str, float] = {
            "interactive": 4.0, "batch": 1.0}
        if lane_weights:
            for lane, w in lane_weights.items():
                if float(w) <= 0:
                    raise ValueError(
                        f"lane weight must be > 0, got {lane}={w}")
                self._lane_weights[str(lane)] = float(w)
        self._wdrr_quantum = 32.0            # deficit tokens per weight
        self._deficit: Dict[Tuple[str, str], float] = {}
        self._rr: List[Tuple[str, str]] = []  # class rotation order
        # inter-admission EWMA: the honest-Retry-After estimate carried
        # by QueueFullError/DeadlineExceeded (est_wait ~ depth x this)
        self._admit_stamp: Optional[float] = None
        self._admit_interval_s: Optional[float] = None
        self._slots: Dict[int, GenerationRequest] = {}
        self._cond = threading.Condition()
        self._closing = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="paddle-serving-scheduler")
        self._thread.start()

    # -- producer side -----------------------------------------------------
    def _est_wait_s(self, depth: int) -> Optional[float]:
        """Estimated queue wait for ``depth`` entries: depth x the EWMA
        inter-admission interval (None before two admissions — an
        estimate with no evidence behind it is a lie, not a hint).
        Callers hold ``self._cond`` or tolerate a stale read."""
        if self._admit_interval_s is None:
            return None
        return depth * self._admit_interval_s

    def submit(self, req: GenerationRequest) -> GenerationRequest:
        _prof.set_thread_name(
            f"submitter ({threading.current_thread().name})")
        with self._cond:
            if self._closing:
                raise RuntimeError("GenerationEngine is closed")
            if len(self._queue) >= self._max_queue:
                stat_add("serving/queue_full")
                depth = len(self._queue)
                raise QueueFullError(
                    f"admission queue is full ({self._max_queue} "
                    f"requests); retry after in-flight work drains",
                    queue_depth=depth,
                    est_wait_s=self._est_wait_s(depth))
            req._recorder = self.recorder
            # recorded before notify so the event ring can never show
            # this request admitted ahead of its own submit
            self.recorder.record_event(
                req.id, "submit", t=req.submitted_at,
                meta={"tenant": req.tenant, "lane": req.lane})
            self._queue.append(req)
            stat_observe("serving/queue_depth", len(self._queue))
            self._cond.notify_all()
        return req

    def close(self, cancel_pending: bool = False) -> None:
        """Stop accepting work and DRAIN: every queued and in-flight
        request runs to completion before the loop exits (with
        ``cancel_pending`` queued requests are cancelled instead —
        in-flight slots still finish)."""
        with self._cond:
            if self._closing and not self._thread.is_alive():
                return
            self._closing = True
            if cancel_pending:
                for r in self._queue:
                    r.cancel()
            self._cond.notify_all()
        self._thread.join()

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    @property
    def active(self) -> int:
        return len(self._slots)

    # -- scheduler thread --------------------------------------------------
    def _loop(self) -> None:
        _prof.set_thread_name("serving scheduler")
        while True:
            # the stretch between two turns: idle until there is work,
            # or the queue's lock held by a submitter
            with _prof.record("serving/wait", "serving",
                              args={"cycle": self._cycle + 1}):
                with self._cond:
                    while not self._closing and not self._queue \
                            and not self._slots and self._inflight is None:
                        self._cond.wait()
                    if self._closing and not self._queue \
                            and not self._slots and self._inflight is None:
                        return
            self._cycle += 1
            t0 = time.perf_counter()
            # the record is ALWAYS captured (bounded ring, host dicts
            # only) and describes a LAUNCH: the one this turn dispatches
            # (sweep, admit, plan and dispatch are stamped now; fetch and
            # emit when the launch lands, a turn later — the record
            # enters the ring then). A turn that dispatches nothing
            # records itself at once. The spans below tile the turn —
            # each takes its own bookkeeping in, so the scheduler thread
            # is between two of them for a few bytecodes only — and each
            # carries its launch's number: as TraceAnnotations they land
            # in any running jax trace, on the device ops' clock, and in
            # the profiler buffer when a profile() session is armed
            rec = self._rec = {
                "cycle": self._cycle, "t": t0, "sweep_ms": 0.0,
                "admit_ms": 0.0, "prefill_ms": 0.0, "plan_ms": 0.0,
                "decode_dispatch_ms": 0.0, "fetch_ms": 0.0,
                "emit_ms": 0.0,
                "admitted": [], "retired": [], "emitted": 0,
                "preempts": 0, "active": 0, "occupancy": 0.0,
                "promo_waits": 0, "promoted_blocks": 0,
                "overlapped": False, "late_rows": 0,
            }
            failed = None
            # a turn that begins with nothing in the pool opens a busy
            # stretch (see _chunked_cycle: its launch is not left in flight)
            cold = not self._slots and self._inflight is None
            cyc = {"cycle": self._cycle}     # this turn's launch
            cycle_span = _prof.record("serving/cycle", "serving",
                                      args=cyc).begin()
            try:
                with _prof.record("serving/sweep", "serving", args=cyc):
                    self._sweep_queue()
                    t = time.perf_counter()
                    rec["sweep_ms"] = (t - t0) * 1e3
                with _prof.record("serving/admit", "serving", args=cyc):
                    if self._pool.host_tier is not None:
                        # demotion pump: blocks freed by LAST turn's
                        # retirements spill before THIS turn's
                        # admissions can evict them (dispatch-only)
                        self._pool.tier_tick()
                        # promotion prefetch: start/land H2D copies
                        # for the queue FRONT while the decode slots
                        # are still busy (the pending-feed overlap)
                        self._prefetch_promotions()
                    t = time.perf_counter()     # admit_ms: _admit alone
                    self._admit()
                    rec["admit_ms"] = (time.perf_counter() - t) * 1e3
                if self._slots or self._inflight is not None:
                    self._chunked_cycle(cold)
                elif rec["promo_waits"]:
                    # nothing decoding and the only queued work is
                    # waiting on in-flight promotions: nap on the
                    # tier's progress beacon (host Event, ~2ms)
                    # instead of hot-spinning the admit loop. With
                    # decode slots active this branch never runs —
                    # decode turns never block on a promotion.
                    self._pool.host_tier.wait_progress(0.002)
            except Exception as e:                      # noqa: BLE001
                # a step failure (OOM, bad artifact) poisons the affected
                # requests, never the loop: fail everything in flight and
                # keep serving — the BatchingEngine worker-survival rule
                failed = e
                self._fail_inflight(e)
            finally:
                with _prof.record("serving/record", "serving", args=cyc):
                    with self._cond:
                        rec["queue_depth"] = len(self._queue)
                    rec["blocks_in_use"] = self._pool.blocks_in_use
                    if failed is not None:
                        rec["failed"] = repr(failed)
                    rec["cycle_ms"] = (time.perf_counter() - t0) * 1e3
                    stat_observe("serving/cycle_ms", rec["cycle_ms"])
                    # into the ring, oldest launch first: what landed
                    # this turn, then this turn's own record unless it
                    # rides a launch still in flight
                    done, self._landed = self._landed, []
                    if self._inflight is None \
                            or self._inflight["rec"] is not rec:
                        done.append(rec)
                    for r in done:
                        self.recorder.record_cycle(r)
                    # HBM watermark per turn — a host-only stamp
                    # (profiler/memory.py mark: ledger total, NO device
                    # poll — polling belongs to the sampler thread; the
                    # memory-stats-hot-path self-lint rule enforces it)
                    _memory.mark("serving/cycle", cycle=self._cycle,
                                 active=rec["active"])
                    self._rec = None
                cycle_span.end()
                if failed is not None:
                    # leave the postmortem behind: the profiler is
                    # almost never armed when a production step dies,
                    # but the recorder's rings (this poisoned turn
                    # included) hold what led here
                    self.recorder.auto_dump(reason=repr(failed))
                    if _memory.is_resource_exhausted(failed):
                        # out-of-HBM death: the memory picture (ledger,
                        # timeline, largest live arrays) lands as JSON
                        # next to the flight recorder's dump — best
                        # effort, the original error is already on its
                        # way to every poisoned request
                        _memory.oom_postmortem(failed, extra={
                            "phase": "serving.scheduler",
                            "cycle": self._cycle,
                            "flight_recorder":
                                self.recorder.last_dump_path})

    def _note_nonfinite(self, toks, rec, idx: Optional[int] = None) \
            -> None:
        """Read the step's logits-finite sentinel off the fetched
        token row (element ``[num_slots]`` — or ``idx`` for layouts
        like the speculative verify output whose sentinel sits past the
        draft echo; absent from a mock step that returns exactly
        ``num_slots`` tokens). A tripped flag marks the cycle record
        and counts ``serving/nonfinite_cycles`` — the tokens themselves
        still flow (an argmax over NaN logits is garbage, not a crash),
        so the loop survives and the operator sees WHY the output went
        bad."""
        idx = self._pool.num_slots if idx is None else int(idx)
        shape = getattr(toks, "shape", None)
        if shape and shape[0] > idx and bool(toks[idx]):
            self.nonfinite_cycles += 1
            stat_add("serving/nonfinite_cycles")
            if rec is not None:
                rec["nonfinite"] = True

    def _note_routed(self, toks, rec) -> None:
        """Read a routed model's launch counters off the fetched token
        row (the ``decoder_spec.ROUTED_COUNTERS`` elements after the
        sentinel; absent from every other model's row) into the
        cycle record: ``moe_pairs`` (real rows x held experts they
        chose, summed over the expert layers), ``moe_experts_hit`` (held
        experts with at least one token, summed over the layers),
        ``moe_rows`` (real rows routed, summed over the layers) and
        ``moe_rows_walked`` (the rows the grouped products were handed:
        the pairs and the zero rows that pad an expert's group to whole
        tiles, summed over the layers) and ``moe_zero_pairs`` (real rows
        x identity experts they chose — experts without weights, which
        cost nothing: of ``moe_rows`` x k choices, these and the ones on
        absent experts are in no ``moe_pairs``; 0 for a model without
        such experts). They ride the cycle's one fetch: no sync of their
        own."""
        from ..models.decoder_spec import ROUTED_COUNTERS
        at = self._pool.num_slots + 1
        shape = getattr(toks, "shape", None)
        if rec is not None and shape and shape[0] >= at + ROUTED_COUNTERS:
            rec.update(zip(("moe_pairs", "moe_experts_hit", "moe_rows",
                            "moe_rows_walked", "moe_zero_pairs"),
                           (int(v) for v in toks[at:at + ROUTED_COUNTERS])))

    def note_decode_flops(self, flops: float) -> None:
        """Record the FLOPs of the decode program dispatched THIS cycle
        into the live cycle record (called by the engine's step,
        scheduler thread). cycle_throughput sums it alongside emitted,
        keeping stats() achieved-FLOP/s on the same ring window as its
        wall-time denominator."""
        if self._rec is not None:
            self._rec["decode_flops"] = \
                self._rec.get("decode_flops", 0.0) + float(flops)

    def note_launch(self, rows: int, q: int, t: int, program: str,
                    kv_tokens: int, kv_steps: int, kv_fetches: int,
                    q_blocks: int = 0,
                    q_blocks_wide: int = 0, kv_row_tokens: int = 0,
                    kv_write_blocks: int = 0,
                    kv_tokens_window: Optional[int] = None,
                    kv_row_tokens_window: Optional[int] = None,
                    state_slots: Optional[int] = None, ssm_rows: int = 0,
                    ssm_chunk_rows: int = 0,
                    tower_rows: Optional[int] = None,
                    cache_layers: Optional[int] = None,
                    state_layers: Optional[int] = None,
                    kv_walks: Optional[int] = None,
                    kv_walks_handed: int = 0) -> None:
        """Record the shape of the ragged launch built THIS cycle into
        the live cycle record (called by the engine's
        ``_ragged_operands``, scheduler thread; host ints only):
        ``launch_rows`` real query rows inside the ``(launch_q,
        launch_t)`` program's buckets — ``launch_q`` the rows of its
        attention KERNEL, each slot's padded to whole q blocks — and
        ``launch_tower_rows`` (``tower_rows``; the kernel's where not
        given) the rows everything else of the program runs on, which
        hold the slots' real rows back to back
        (``models/generation.py:_row_axes``); monitors
        ``serving/launch_rows`` and ``serving/tower_rows`` sum the two;
        ``launch_program``, that step
        program's name — the device's ``XLA Modules`` line of a profiler
        trace calls the launch ``jit_<launch_program>(…)``;
        ``kv_tokens``, the context
        tokens the kernel must read (sum of the planned slots'
        ``kv_len``); ``kv_steps``, the KV blocks its walks fetch per
        layer, one DMA of a whole block (every head) each;
        ``kv_fetches``, the groups of G blocks those DMAs go out in, each
        waited for and computed on once — both as the kernel really
        walks (``ops.ragged_paged_attention.ragged_walk_counts``: a q
        block's walk, or ONE for the q blocks of a wide step, ended
        where its last row stops seeing); ``q_blocks``, the launch's
        real q blocks, and ``q_blocks_wide``, those of them a wide step
        served; ``kv_row_tokens``, the (query row, cached token) pairs of
        the causal mask — a row at position ``p`` sees ``p + 1`` tokens
        — which is what an attention kernel's products are counted
        from; ``kv_write_blocks``, the (slot, block) pairs the real rows
        land in — the blocks the cache append reads, fills in and
        writes back, once each a layer (``ops/kv_append.py``). For a model
        with a sliding-window cache group (``models/decoder_spec.py``)
        ``kv_tokens`` / ``kv_row_tokens`` / ``kv_steps`` / ``kv_fetches``
        are the window-0 (first) group's, and two keys join them:
        ``kv_tokens_window``, the tokens a window layer must read (sum
        over the planned slots of ``min(kv_len, W - 1 + rows)``), and
        ``kv_row_tokens_window``, the (row, visible token) pairs under
        the window mask. For a model whose layers hold a recurrent state
        three more: ``state_slots``, the sequences whose state the launch
        reads and writes (once a layer with state); ``ssm_rows``, the
        real rows through the mixer, and ``ssm_chunk_rows``, those of
        them in a sequence of more than one row — the chunked scan's
        (``ops/ssm.py``). ``cache_layers`` (given only by a model with
        layers whose mixer is a state alone): the layers that read and
        write the pool, which is what ``kv_tokens`` and the walk counts
        are a layer OF; ``state_layers`` (given only where the layers
        with a recurrent state are fewer than the layers): what
        ``state_slots`` and the mixer's rows are a layer of. ``kv_walks``
        (given by the per-head ragged
        kernel's counts alone): the walks of at least one block the
        kernel makes a layer, and ``kv_walks_handed``, those of them
        whose first group the walk before had started (monitor
        ``serving/kv_walks_handed``) — every walk but a launch's first
        and one after a pad block."""
        if self._rec is not None:
            tower = int(q if tower_rows is None else tower_rows)
            stat_add("serving/launch_rows", int(rows))
            stat_add("serving/tower_rows", tower)
            self._rec.update(launch_rows=int(rows), launch_q=int(q),
                             launch_tower_rows=tower,
                             launch_t=int(t), launch_program=str(program),
                             kv_tokens=int(kv_tokens),
                             kv_steps=int(kv_steps),
                             kv_fetches=int(kv_fetches),
                             q_blocks=int(q_blocks),
                             q_blocks_wide=int(q_blocks_wide),
                             kv_row_tokens=int(kv_row_tokens),
                             kv_write_blocks=int(kv_write_blocks))
            if kv_tokens_window is not None:
                self._rec.update(
                    kv_tokens_window=int(kv_tokens_window),
                    kv_row_tokens_window=int(kv_row_tokens_window))
            if state_slots is not None:
                self._rec.update(state_slots=int(state_slots),
                                 ssm_rows=int(ssm_rows),
                                 ssm_chunk_rows=int(ssm_chunk_rows))
            if cache_layers is not None:
                self._rec["cache_layers"] = int(cache_layers)
            if state_layers is not None:
                self._rec["state_layers"] = int(state_layers)
            if kv_walks is not None:
                stat_add("serving/kv_walks_handed", int(kv_walks_handed))
                self._rec.update(kv_walks=int(kv_walks),
                                 kv_walks_handed=int(kv_walks_handed))

    def note_build(self, build: dict) -> None:
        """A program was BUILT by this turn's dispatch (the ``on_build``
        of the engine's jit sites — ``program_registry.AotSite`` —
        called on the scheduler thread once the fresh executable's first
        call returned). The build event learns what the launch that
        asked for it held: ``launch_rows``, the launch's real rows
        (:meth:`note_launch`; ``None`` for a program that is no launch —
        a block copy, the draft's) and ``slots_active``. The live cycle
        record gets ``built_ms``, the build's parts and first call
        summed: the one key a record holds ONLY when its dispatch built
        a program, so the turn that paid shows in the ring."""
        rec = self._rec
        if rec is None:
            return
        build["launch_rows"] = rec.get("launch_rows")
        build["slots_active"] = rec["active"]
        rec["built_ms"] = rec.get("built_ms", 0.0) + sum(
            build.get(k) or 0.0 for k in (
                "trace_ms", "lower_ms", "compile_ms", "first_call_ms",
                "wall_ms"))

    def note_spec_dispatches(self, n: int) -> None:
        """Count the draft-proposal programs dispatched THIS cycle into
        the live cycle record (called by the engine's spec step,
        scheduler thread). The scanned proposal chain lands exactly 1
        here where the unrolled loop dispatched spec_k launches — the
        flight-recorder evidence for the one-dispatch-per-cycle win."""
        if self._rec is not None:
            self._rec["spec_draft_dispatches"] = \
                self._rec.get("spec_draft_dispatches", 0) + int(n)

    def _fail_inflight(self, error: BaseException) -> None:
        # let go of the launch in flight: its result is never fetched
        # (every request it carried fails below), its record still
        # enters the ring, marked
        launch, self._inflight = self._inflight, None
        if launch is not None:
            launch["rec"]["failed"] = repr(error)
            if launch["rec"] is not self._rec:  # the turn records its own
                self._landed.append(launch["rec"])
        for slot in list(self._slots):
            req = self._slots.pop(slot)
            self._pool.free(slot)
            req.in_flight = 0
            req._finish(RuntimeError(
                f"serving step failed for request {req.id}: {error!r}"))
        # the steps DONATE the pool buffer, so a step that failed at XLA
        # runtime may have left pool.data already deleted — reallocate
        # before serving on, or every later step dies on the stale handle
        self._pool.reset_data()

    def _sweep_queue(self) -> None:
        """Resolve terminal (cancelled / deadline-expired) entries
        ANYWHERE in the queue, not just at the head: a dead request
        behind a slot-starved head must fail its caller NOW, not when
        its turn finally comes, and must stop holding ``max_queue``
        capacity. Terminal entries are removed, so live-request FCFS
        order is untouched."""
        now = time.perf_counter()
        with self._cond:
            live = []
            for r in self._queue:
                if r.cancelled:
                    self._drop_ticket(r)
                    stat_add("serving/cancelled")
                    r._finish(RequestCancelled(
                        f"request {r.id} cancelled while queued"))
                elif r.expired(now):
                    self._drop_ticket(r)
                    stat_add("serving/deadline_exceeded")
                    depth = len(self._queue)
                    r._finish(DeadlineExceeded(
                        f"request {r.id} exceeded its deadline while "
                        f"queued",
                        queue_depth=depth,
                        est_wait_s=self._est_wait_s(depth)))
                else:
                    live.append(r)
            if len(live) != len(self._queue):
                self._queue[:] = live
                stat_observe("serving/queue_depth", len(live))

    def _select_next(self, skip=frozenset()) -> int:
        """Index into ``self._queue`` of the next admission candidate —
        weighted deficit-round-robin over the queued (lane, tenant)
        classes (caller holds ``self._cond``). Request ids in ``skip``
        (promotion-waiters this cycle) are invisible to the rotation;
        returns -1 when nothing else is queued.

        Preemption victims outrank everything (they predate every
        queued arrival and their history is hot). A single queued class
        short-circuits to its FCFS head, so untagged traffic and
        idle-capacity batch flow are plain FCFS. With several classes, each rotation credits the
        rotation head ``quantum x lane weight`` tokens of deficit and a
        class admits while its deficit covers its head request's feed
        cost — an interactive lane at weight 4 admits ~4x the token
        rate of a batch flood, and the flood still drains whenever
        interactive has nothing queued (work-conserving)."""
        q = self._queue
        for i, r in enumerate(q):
            if r._preempted and r.id not in skip:
                return i
        heads: Dict[Tuple[str, str], int] = {}
        for i, r in enumerate(q):
            if r.id in skip:
                continue
            key = (r.lane, r.tenant)
            if key not in heads:
                heads[key] = i
        if not heads:
            return -1
        if len(heads) == 1:
            return next(iter(heads.values()))
        # keep the rotation stable across calls; retire dead classes
        self._rr = [k for k in self._rr if k in heads]
        for k in heads:
            if k not in self._rr:
                self._rr.append(k)
                self._deficit.setdefault(k, 0.0)
        # the deficit cap must exceed any admissible feed cost (feeds
        # are bounded by pool.max_len at submit time) or a fat head
        # could starve its own class forever
        cap = max(2.0 * self._pool.max_len, 8.0 * self._wdrr_quantum)
        for _ in range(10_000):
            k = self._rr[0]
            head = q[heads[k]]
            cost = float(max(1, len(head.prompt) + len(head.tokens)))
            if self._deficit.get(k, 0.0) >= cost:
                self._deficit[k] -= cost
                return heads[k]
            w = self._lane_weights.get(k[0], 1.0)
            self._deficit[k] = min(
                self._deficit.get(k, 0.0) + self._wdrr_quantum * w, cap)
            self._rr.append(self._rr.pop(0))
        return heads[self._rr[0]]     # unreachable: cap >= any cost

    def _drop_ticket(self, req: GenerationRequest) -> None:
        """Release a dead waiter's promotion ticket so the tier's
        registry (and the staged device buffers it pins) don't outlive
        the request. A ticket shared by a coalesced waiter survives —
        ``ticket_done`` only unregisters; adoption by the other waiter
        still works."""
        tk = req._promo_ticket
        if tk is None:
            return
        req._promo_ticket = None
        if self._pool.host_tier is not None:
            self._pool.host_tier.ticket_done(tk)

    def _prefetch_promotions(self) -> None:
        """Overlap promotion with decode (scheduler thread, right
        after the demotion pump): drive the promotion state machine
        for the FRONT of the queue while every decode slot is still
        busy, so a host-resident chain is requested BEFORE a slot
        frees up. Without this the ticket would only be requested
        when the waiter reaches admission with capacity in hand; a
        competing fresh request would steal that slot during the
        copy's one-or-two-cycle flight and the waiter would sit out
        a whole generation. Adoption is deliberately NOT driven here
        (``adopt=False``): republishing staged blocks before the
        waiter can take references would leave them refcount-0 in a
        pressured pool, where the very next fresh admission evicts
        them again — the ticket pins the staged copy instead, and
        the admission path adopts and refs in one step. Bounded to
        the promoter's double-buffer depth — everything here is host
        bookkeeping plus dispatch-only device calls."""
        with self._cond:
            head = [r for r in self._queue if not r.cancelled][:2]
            for req in head:
                self._promotion_state(req, adopt=False)

    def _promotion_state(self, req: GenerationRequest,
                         adopt: bool = True) -> str:
        """Drive ``req``'s host-tier promotion state machine (caller
        holds ``_cond``; scheduler thread). Returns ``"go"`` — admit
        now (no host-resident prefix, the tier degraded to a plain
        miss, or the staged blocks were just adopted) — or ``"wait"`` — an H2D copy is in flight,
        skip this request until it lands."""
        pool = self._pool
        tk = req._promo_ticket
        if tk is not None:
            if not tk.ready.is_set():
                return "wait"
            if not adopt:
                return "go"     # staged; admission adopts + refs
            req._promo_ticket = None
            if pool.adopt_promotion(tk):
                req._tier_promoted = True
                if self._rec is not None:
                    self._rec["promoted_blocks"] += len(tk.staged_keys)
            return "go"                  # failed ticket = plain miss
        feed = req.prompt if not req.tokens else np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        host_keys, _ = pool.tier_match(feed)
        if not host_keys:
            return "go"
        tk = pool.host_tier.request_promotion(host_keys)
        if tk is None:
            return "go"                  # tier degraded to a plain miss
        req._promo_ticket = tk
        return "wait"

    # admission: weighted-fair over (lane, tenant) classes — FCFS
    # within a class and when only one class is queued (the loop sweeps
    # the queue under its own span/timer right before calling this)
    def _admit(self) -> None:
        skip: set = set()       # promotion-waiters sit out this cycle
        while True:
            with self._cond:
                if not self._queue:
                    return
                # a promotion whose H2D copy has LANDED admits ahead
                # of the fair rotation: landing it is a block adoption
                # plus the uncovered tail's chunks, so the jump costs
                # the queue almost nothing, while making the waiter sit
                # behind one more fresh prompt's feed would hand back
                # most of the latency the tier just saved
                idx = -1
                for i, r in enumerate(self._queue):
                    tk = r._promo_ticket
                    if r.id in skip:
                        continue
                    # _tier_promoted with no ticket = the chain was
                    # adopted on an earlier pass that then bounced off
                    # a capacity gate: its blocks sit refcount-0 and
                    # evictable, so admit it before any fresh admission
                    # can steal them back
                    if (tk is not None and tk.ready.is_set()) \
                            or (tk is None and r._tier_promoted):
                        idx = i
                        break
                if idx < 0:
                    idx = self._select_next(skip)
                if idx < 0:
                    return      # only promotion-waiters left queued
                req = self._queue[idx]
                # re-check the head: cancel/expiry may race the sweep
                if req.cancelled:
                    self._queue.pop(idx)
                    self._drop_ticket(req)
                    stat_add("serving/cancelled")
                    req._finish(RequestCancelled(
                        f"request {req.id} cancelled while queued"))
                    continue
                if req.expired():
                    self._queue.pop(idx)
                    self._drop_ticket(req)
                    stat_add("serving/deadline_exceeded")
                    depth = len(self._queue)
                    req._finish(DeadlineExceeded(
                        f"request {req.id} exceeded its deadline while "
                        f"queued",
                        queue_depth=depth,
                        est_wait_s=self._est_wait_s(depth)))
                    continue
                # hierarchical KV: a request whose prefix continues in
                # the HOST tier is treated like a pending feed — start
                # (or poll) its async H2D promotion and admit the cycle
                # the blocks land. Meanwhile the rotation moves on to
                # other queued work, so a copy in flight never blocks a
                # decode cycle or a promotion-free admission.
                if self._pool.host_tier is not None \
                        and self._promotion_state(req) == "wait":
                    if self._rec is not None:
                        self._rec["promo_waits"] += 1
                    tk = req._promo_ticket
                    if tk is not None and \
                            time.perf_counter() - tk.created_at < 0.05:
                        # hold the admission line while the copy is
                        # YOUNG: it lands within a cycle or two, and
                        # letting a later-arriving prompt overtake now
                        # would occupy the launches for exactly the time
                        # the hit was about to save (decode slots keep
                        # running — only fresh admissions wait). The
                        # age bound keeps a wedged promoter from
                        # starving the queue: past it, the rotation
                        # resumes overtaking as before.
                        return
                    skip.add(req.id)
                    continue
                # re-admission after a preemption feeds the request's
                # own generated tokens again, so the "prompt" being fed
                # is the whole sequence so far
                if not self._pool.can_admit(
                        len(req.prompt) + len(req.tokens)):
                    # block pressure: wait for retirements (the head
                    # keeps its FCFS place; submit-time capacity checks
                    # guarantee it fits an idle pool, so no deadlock)
                    return
                slot = self._pool.alloc()
                if slot is None:
                    return              # pool full: a cycle will retire
                self._queue.pop(idx)
                req._preempted = False
                # admission-rate EWMA: the evidence behind est_wait_s
                now = time.perf_counter()
                if self._admit_stamp is not None:
                    dt = now - self._admit_stamp
                    self._admit_interval_s = dt \
                        if self._admit_interval_s is None \
                        else 0.8 * self._admit_interval_s + 0.2 * dt
                self._admit_stamp = now
                stat_observe("serving/queue_depth", len(self._queue))
            try:
                self._prefill(req, slot)
            except Exception as exc:                    # noqa: BLE001
                # at this point the request is in neither queue nor
                # slots: fail it HERE (or its caller hangs forever) and
                # reclaim the slot, then let the loop's handler fail the
                # other in-flight slots and reset the donated pool
                self._slots.pop(slot, None)
                if self._pool.is_allocated(slot):
                    self._pool.free(slot)
                if not req.done():
                    req._finish(RuntimeError(
                        f"serving step failed for request {req.id}: "
                        f"{exc!r}"))
                raise

    def _prefill(self, req: GenerationRequest, slot: int) -> None:
        """Admit ``req`` into ``slot``: the engine's admission hook
        reserves its blocks and arms ``req.pending_feed``; the feed
        itself rides the cycles' launches."""
        # admission wait: submit -> this admission (a re-admission after
        # preemption restarts nothing — the client has been waiting the
        # whole time, so the wall clock since submit IS the lane wait)
        wait_ms = (time.perf_counter() - req.submitted_at) * 1e3
        stat_observe("serving/lane_wait_ms", wait_ms)
        self._event(req, "admitted", slot=slot,
                    feed=len(req.prompt) + len(req.tokens),
                    tenant=req.tenant, lane=req.lane,
                    wait_ms=round(wait_ms, 3))
        if self._rec is not None:
            self._rec["admitted"].append(req.id)
        req.trace.mark("prefill_start")
        t0 = time.perf_counter()
        with _prof.record("serving/prefill", "serving",
                          args={"slot": slot}):
            self._do_prefill(req, slot)
        dt_ms = (time.perf_counter() - t0) * 1e3
        if self._rec is not None:
            self._rec["prefill_ms"] += dt_ms
        req.trace.mark("prefill_end")
        self._slots[slot] = req

    def _event(self, req: GenerationRequest, name: str, **meta) -> None:
        """One lifecycle event, stamped once into both the request's
        trace and the flight recorder's event ring."""
        t = time.perf_counter()
        req.trace.mark(name, t=t, **meta)
        self.recorder.record_event(req.id, name, t=t, meta=meta or None)

    def _finished(self, req: GenerationRequest, tok: int) -> bool:
        return (req.eos_token_id is not None and tok == req.eos_token_id) \
            or req.emitted >= req.max_new_tokens

    def _retire(self, slot: int,
                error: Optional[BaseException] = None) -> None:
        req = self._slots.pop(slot)
        self._pool.free(slot)
        if error is None:
            stat_add("serving/completed")
        if self._rec is not None:
            self._rec["retired"].append(req.id)
        req._finish(error, out=self._out)

    # -- memory pressure: growth, copy-on-write, preemption ----------------
    def _preempt_youngest(self) -> bool:
        """Evict the youngest active request to free its blocks: the
        request is failed OUT of the pool but not failed to its caller
        — it re-enters the queue at the head (it predates everything
        queued) and feeds its own history again on re-admission.
        Returns False when nothing is active to evict."""
        if not self._slots:
            return False
        slot = max(self._slots, key=lambda s: self._slots[s].id)
        req = self._slots.pop(slot)
        self._pool.free(slot)
        req.pending_feed = []            # rebuilt at re-admission
        # a block in the middle of its passes starts again all masked:
        # only emitted tokens are fed (the pipeline was drained before
        # this, so every dispatched pass has landed)
        req.block_pass, req.block_state = 0, None
        req._preempted = True            # outranks WDRR selection
        req._tier_promoted = False       # re-classified at re-admission
        self.preempts += 1
        self._event(req, "preempt", emitted=req.emitted)
        if self._rec is not None:
            self._rec["preempts"] += 1
        stat_add("serving/preempt")
        with self._cond:
            self._queue.insert(0, req)
            stat_observe("serving/queue_depth", len(self._queue))
            self._cond.notify_all()
        return True

    # -- the turn: plan, dispatch, land ------------------------------------
    def _chunk_plan(self) -> Dict[int, int]:
        """Per-launch row plan: how many query rows each active slot
        contributes to the fused ragged launch. Decode slots (feed
        drained) always get their 1 row — decode is NEVER budget-
        charged, which is the anti-starvation guarantee — unless the
        token that completes ``max_new_tokens`` is already in flight:
        that request waits for its emit with no row (the count does not
        depend on the token's value, so nothing is wasted). Feeding
        slots split the prefill TOKEN budget FCFS by request age; a slot
        whose share hits 0 simply waits a launch (its blocks are already
        reserved). Under block generation a decode slot's rows are the B
        rows of its block, every pass of it — 2 B where the block is
        finished: its commit rides with the next block's first pass
        (module doc) — and a chunk ends on a block boundary (the feed is
        whole blocks: ``engine._run_admit``)."""
        budget = self._prefill_budget
        B = self._block
        plan: Dict[int, int] = {}
        for slot in sorted(self._slots,
                           key=lambda s: self._slots[s].id):
            req = self._slots[slot]
            if req.pending_feed:
                n = min(len(req.pending_feed), budget)
                n -= n % B
                budget -= n
                if n > 0:
                    plan[slot] = n
            elif req.emitted + req.in_flight < req.max_new_tokens:
                plan[slot] = 2 * B if B > 1 and req.block_commits_next(
                    self._gen) else B
        return plan

    def _prepare_chunked(self, plan: Dict[int, int]) -> Dict[int, int]:
        """Before the launch: every planned slot must own writable
        blocks for its WHOLE row range (a chunk writes ``[pos, pos +
        n)``) — grow tables, resolve copy-on-write appends, and answer
        exhaustion by preempting the youngest request (oldest-first
        order makes the youngest the victim, never the beneficiary);
        evicted slots drop out of the plan. Pool pressure DRAINS the
        pipeline first: a copy-on-write or an exhausted pool lands the
        launch in flight before anything else, so a victim's history is
        whole when it is preempted (its newest token emitted, not on the
        device) and the retirements of that landing may make the
        preemption unnecessary."""
        for slot in sorted(plan, key=lambda s: self._slots[s].id
                           if s in self._slots else -1):
            while slot in self._slots and slot in plan:
                try:
                    cows = self._pool.ensure_writable_range(
                        slot, self._pool.slot_pos(slot) + plan[slot] - 1)
                except PoolExhaustedError as e:
                    drained = self._drain()
                    # COW table swaps before the failure are already in
                    # place — their device copies must happen NOW (the
                    # retry sees a refcount-1 block and would never
                    # re-order them)
                    if self._do_copy is not None:
                        for cow in getattr(e, "partial_cows", ()):
                            self._do_copy(*cow)
                    if not drained:
                        self._preempt_youngest()
                    continue
                if cows:
                    self._drain()
                    if self._do_copy is not None:
                        for cow in cows:
                            self._do_copy(*cow)
                break
        return {s: n for s, n in plan.items() if s in self._slots}

    def _spec_plan(self, plan: Dict[int, int]) -> Dict[int, int]:
        """Speculative row plan: every DECODE slot (feed drained)
        contributes ``min(spec_k, remaining budget)`` candidate rows to
        the verify launch instead of 1 — the rows are the draft's
        proposals, and the slot emits up to that many tokens this
        cycle. Feed slots keep their chunk rows. Mutates ``plan`` (so
        ``_prepare_chunked`` reserves writable blocks for the whole
        candidate range) and returns ``{slot: n_candidates}``."""
        spec: Dict[int, int] = {}
        for slot, n in list(plan.items()):
            req = self._slots[slot]
            if req.pending_feed:
                continue
            k = min(self._spec_k, req.max_new_tokens - req.emitted)
            plan[slot] = spec[slot] = max(1, k)
        return spec

    def _chunked_cycle(self, cold: bool = False) -> None:
        """One turn of the pipeline: plan launch N+1, dispatch it, THEN
        fetch and emit launch N — so N+1 is queued on the device behind
        N before the host blocks on N's tokens, and the emit of N (the
        handler threads it wakes, the result's free) runs while N+1
        computes. A launch is one fused ragged program: budgeted prompt
        chunks mixed with every decode row; its next-token array is real
        for decode slots AND for slots whose final feed chunk rode it
        (their first generated token comes out of the same launch);
        mid-feed slots' rows are ignored.

        The depth adapts to what the turn sees, and the serial cycle is
        this loop with the pipeline drained every turn: SPECULATIVE mode
        lands each verify launch in the turn that dispatched it (the
        accepted count decides the next positions — its launch returns
        ``[accepted | corrected | draft echo | sentinel]``, accepted
        candidates emit host-side, the slot's pool position rolls back
        over the rejected rows, and any cache registration the dead rows
        touched is dropped); a plan that must preempt or copy-on-write
        lands the launch in flight first (``_prepare_chunked``); a plan
        with no rows (every live request waits for its last token, or
        the batch has just ended) only lands; and the launch of a
        ``cold`` turn — the pool was empty when the turn began, so this
        is the first arrival after idleness and as a rule the head of a
        burst — lands in its own turn: while the host blocks on it the
        rest of the burst arrives and the next launch carries it whole,
        where a launch sent out at once would carry the one or two
        requests that happened to be through the door (and, on a chip,
        as likely as not an odd-sized program nobody has compiled). The
        pipeline fills from the launch after."""
        rec = self._rec
        with _prof.record("serving/plan", "serving",
                          args={"cycle": self._cycle}):
            t0 = time.perf_counter()
            plan = self._chunk_plan()
            spec = self._spec_plan(plan) if self._spec else {}
            plan = self._prepare_chunked(plan)
            spec = {s: n for s, n in spec.items() if s in plan}
            if plan:
                occupancy = len(self._slots) / self._pool.num_slots
                stat_observe("serving/active_slots", len(self._slots))
                stat_observe("serving/batch_occupancy", occupancy)
                rec["active"] = len(self._slots)
                rec["occupancy"] = occupancy
            rec["plan_ms"] += (time.perf_counter() - t0) * 1e3
        if not plan:
            self._drain()
            return
        prev = self._inflight
        self._inflight = self._dispatch(plan, spec, prev)
        if prev is not None:
            self._land(prev)
        if self._spec or cold:
            self._drain()

    def _dispatch(self, plan, spec, prev) -> dict:
        """Dispatch this turn's launch behind ``prev`` (the launch in
        flight, or None) and apply what the host already knows of its
        outcome: positions advance, ``pending_feed`` drains and each
        request that gets a token out of it counts one ``in_flight`` —
        the next plan then needs nothing the fetch would tell it. A
        decode row whose request's newest token is still in ``prev``'s
        un-fetched result is named to the step, which reads the token
        there, on the device. Returns the launch: its record, rows and
        un-fetched result, to be handed to ``_land``."""
        rec = self._rec
        active = {s: self._slots[s] for s in plan}
        if self._block > 1:
            # a block between two passes whose newest is un-fetched
            from_prev = set() if prev is None else {
                s for s, r in active.items()
                if r.block_pass and prev["active"].get(s) is r
                and prev["passes"].get(s, _NO_PASS)[0] >= 0}
        else:
            from_prev = {s for s, r in active.items() if r.in_flight}
        rec["overlapped"] = prev is not None
        if prev is not None:
            stat_add("serving/launch_overlapped")
        # dispatch and the windowed host fetch are timed APART: a slow
        # launch with fat fetch_ms is a host-sync problem, one with fat
        # dispatch_ms is tracing/compile churn — the flight recorder
        # must distinguish them postmortem
        with _prof.record("serving/decode_dispatch", "serving", args={
                "cycle": self._cycle, "active": len(active),
                "spec_slots": len(spec),
                "chunk_rows": sum(n for s, n in plan.items()
                                  if active[s].pending_feed)}):
            t1 = time.perf_counter()
            if spec:
                toks_dev = self._do_spec(active, plan, spec)
            else:
                toks_dev = self._do_chunked(
                    active, plan,
                    (prev["toks"], from_prev) if from_prev else None)
            fed: Dict[int, int] = {}    # slot -> feed left after its chunk
            # block generation: slot -> (its denoising pass, or -1 for a
            # commit alone; the tokens its landing emits; whether a
            # commit rode with the pass)
            passes: Dict[int, Tuple[int, int, bool]] = {}
            freed0 = self._pool.window_blocks_freed
            for slot, req in active.items():
                if self._block > 1 and not req.pending_feed:
                    passes[slot] = self._advance_block(slot, req,
                                                       plan[slot])
                    continue
                self._pool.advance(slot, plan[slot])
                if req.pending_feed:
                    del req.pending_feed[:plan[slot]]
                    fed[slot] = len(req.pending_feed)
                if not req.pending_feed and self._block == 1:
                    req.in_flight += 1
            if len(self._pool.groups) > 1:
                # what the cache groups hold once this launch's rows are
                # in: the blocks a window group gave back behind its
                # window
                rec["window_blocks_freed"] = \
                    self._pool.window_blocks_freed - freed0
            if len(self._pool.groups) > 1 or self._pool.state_parts:
                # the bytes of the blocks live tables still name (every
                # group), the tokens of the live contexts, and the
                # recurrent state the live slots hold
                rec["kv_live_bytes"] = self._pool.live_bytes
                rec["kv_live_tokens"] = self._pool.live_tokens
            if self._pool.state_parts:
                rec["state_live_bytes"] = self._pool.state_live_bytes
            rec["decode_dispatch_ms"] += (time.perf_counter() - t1) * 1e3
        return {"cycle": self._cycle, "rec": rec, "active": active,
                "plan": plan, "spec": spec, "fed": fed, "toks": toks_dev,
                "passes": passes, "t": t1}

    def _advance_block(self, slot: int, req: GenerationRequest,
                       rows: int) -> Tuple[int, int, bool]:
        """What the host knows of a block-generation decode slot once the
        launch that holds its ``rows`` is dispatched. A denoising pass
        ``k`` leaves the pool's length alone (its K/V are not kept: the
        block's rows stay writable and the next pass rewrites them); the
        LAST one of a block raises ``in_flight`` by the tokens the block
        emits — the positions the prompt did not give, short of
        ``max_new_tokens`` (the surplus of a last block is denoised and
        dropped). The launch after it holds the finished block's rows
        once more, the COMMIT: the pool advances by the block and the
        next one opens, all masked — and the rows past the first B are
        that next block in its pass 0, the ride, so the slot is in pass 1
        of the NEW block from here on. With B rows only the commit is
        alone. A request's last block takes no commit: the plan gives it
        no row once its tokens are all in flight, and nobody reads that
        block's K/V. Returns ``(k, tokens its landing emits, whether a
        commit rode with it)``, ``k`` -1 for a commit alone."""
        B = self._block
        rode = req.block_commits_next(self._gen)
        if rode:
            self._pool.advance(slot, B)
            req.block_pass, req.block_given = 0, []
            if rows <= B:
                return -1, 0, False
        k = req.block_pass
        req.block_pass = k + 1
        emits = 0
        if req.block_commits_next(self._gen):       # the last denoising pass
            emits = min(B - len(req.block_given),
                        req.max_new_tokens - req.emitted - req.in_flight)
            req.in_flight += emits
        return k, emits, rode

    def _drain(self) -> bool:
        """Land the launch in flight, if any: the pipeline is empty
        afterwards. Returns whether there was one."""
        launch, self._inflight = self._inflight, None
        if launch is not None:
            self._land(launch)
        return launch is not None

    def _land(self, launch: dict) -> None:
        """Fetch ``launch``'s tokens — the loop's one device→host sync —
        and do the host half of it: emit, retire. Spans and stamps carry
        the LAUNCH's number and go into its record, whichever turn this
        is; the record enters the ring at this turn's end."""
        rec = launch["rec"]
        cyc = {"cycle": launch["cycle"]}
        turn_rec, self._rec = self._rec, rec    # _retire stamps the launch
        if rec is not turn_rec:                 # the turn records its own
            self._landed.append(rec)
        try:
            with _prof.record("serving/host_fetch", "serving", args=cyc):
                t2 = time.perf_counter()
                toks = _fetch(launch["toks"])
                t3 = time.perf_counter()
                rec["fetch_ms"] += (t3 - t2) * 1e3
            with _prof.record("serving/emit", "serving", args=cyc):
                # tokens over the launch's own stretch of the device:
                # from its dispatch, or the landing before it if later
                dt = t3 - max(launch["t"], self._landed_at)
                self._landed_at = t3
                out = self._out = {}
                try:
                    self._emit_chunked(launch, toks, dt)
                finally:
                    # the hand-over: everything the loop emitted and
                    # retired for the requests of one sink in ONE put —
                    # one wake a launch, not one a token (a request
                    # without a sink woke its own consumer in the loop)
                    self._out = None
                    for sink, batch in out.items():
                        sink.put(batch)
                # freed inside the span: freeing a device array lets go
                # of the GIL, and whoever the hand-over woke (a sink's
                # one reader; the consumer thread of each request that
                # has no sink) may hold it for a while — host time of
                # this launch that would otherwise lie in no span
                launch["toks"] = None
                rec["emit_ms"] += (time.perf_counter() - t3) * 1e3
        except Exception as e:                          # noqa: BLE001
            rec["failed"] = repr(e)
            raise
        finally:
            self._rec = turn_rec

    def _emit_chunked(self, launch: dict, toks, dt: float) -> None:
        """The host half of a launch once its tokens are fetched:
        account chunks and verify outcomes, emit, retire. What the host
        learns here it learns one launch late — EOS, ``cancel()``, a
        deadline: a request that ends now may already hold a row in the
        launch dispatched since. That row is a LATE row: when its launch
        lands the request is found done, the row's token is dropped and
        counted, and nothing of the slot is touched — it was freed here
        (after the later launch's dispatch, so a new owner's writes are
        ordered behind the dead row's on the device) and may have a new
        owner."""
        rec = launch["rec"]
        active, plan, spec = launch["active"], launch["plan"], launch["spec"]
        fed, passes = launch["fed"], launch["passes"]
        B = self._block
        if B > 1:
            rec.update(denoise_slots=0, commit_slots=0, ride_slots=0,
                       tokens_fixed=0)
        S = self._pool.num_slots
        K = self._spec_k
        if spec:
            # spec layout: [accepted (S) | corrected (S) | draft echo
            # (S*K) | sentinel] — the default S-indexed sentinel parse
            # would read a corrected token instead
            acc_row = toks[:S]
            corr_row = toks[S:2 * S]
            draft_rows = toks[2 * S:2 * S + S * K].reshape(S, K)
            self._note_nonfinite(toks, rec, idx=2 * S + S * K)
        else:
            self._note_nonfinite(toks, rec)
            self._note_routed(toks, rec)
        emitted = 0
        chunks = 0
        chunk_tokens = 0
        late_rows = 0
        spec_accepted = 0
        spec_proposed = 0
        spec_emitted = 0
        now = time.perf_counter()
        for slot, req in active.items():
            n = plan[slot]
            feeding = slot in fed
            if feeding:
                # the feed tokens' K/V are in the pool now: account the
                # chunk BEFORE the terminal checks so a cancel mid-feed
                # still leaves honest chunk telemetry behind
                chunks += 1
                chunk_tokens += n
                self.prefill_chunks += 1
                self.chunk_tokens += n
                stat_add("serving/prefill_chunks")
                stat_add("serving/chunk_tokens", n)
            if req.done():
                late_rows += n          # ended at the landing before
                continue
            if feeding:
                req.trace.mark("prefill_chunk", tokens=n,
                               remaining=fed[slot])
            elif slot in spec:
                # verify outcome: the longest agreeing candidate prefix
                # is kept plus (on a rejection) one corrected token;
                # the pool position rolls back over the dead rows
                # (signed advance) and any cache registration they
                # touched is dropped — paged tables address by pos, so
                # the rollback is pure bookkeeping
                a = min(int(acc_row[slot]), n)
                cov = a + 1 if a < n else n
                if cov < n:
                    self._pool.advance(slot, cov - n)
                    self._pool.unpublish_from(
                        slot, self._pool.slot_pos(slot))
                spec_proposed += n
                spec_accepted += a
                self.spec_proposed += n
                self.spec_accepted += a
                stat_add("serving/spec_proposed", n)
                stat_add("serving/spec_accept", a)
                req.trace.mark("spec_verify", proposed=n, accepted=a)
            if B > 1:
                req.in_flight -= passes.get(slot, _NO_PASS)[1]
            elif not fed.get(slot):
                req.in_flight -= 1      # this launch's token lands now
            if req.cancelled:
                stat_add("serving/cancelled")
                self._retire(slot, RequestCancelled(
                    f"request {req.id} cancelled mid-generation"))
                continue
            if req.expired(now):
                stat_add("serving/deadline_exceeded")
                self._retire(slot, DeadlineExceeded(
                    f"request {req.id} exceeded its deadline after "
                    f"{req.emitted} token(s)",
                    queue_depth=len(self._queue),
                    est_wait_s=self._est_wait_s(len(self._queue))))
                continue
            if feeding:
                if fed[slot]:
                    continue             # mid-feed: row output ignored
                # final chunk landed: publish the fully-written feed
                # blocks to the prefix cache, then emit the first
                # generated token — produced by this same launch. (Block
                # generation fed whole blocks only: the trie is never
                # handed a cache block that holds an uncommitted
                # diffusion block, and a prefill yields no token)
                feed = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
                self._pool.register_prefix(slot, feed[:feed.size // B * B])
                req.trace.mark("chunked_prefill_done",
                               emitted=req.emitted)
                if B > 1:
                    continue
            if B > 1:
                emitted += self._land_block_pass(slot, req, passes[slot],
                                                 toks, rec)
                continue
            if slot in spec and not feeding:
                a = min(int(acc_row[slot]), n)
                emit = [int(t) for t in draft_rows[slot, :a]]
                if a < n:
                    emit.append(int(corr_row[slot]))
                slot_emitted = 0
                for tok in emit:
                    req._emit(tok, out=self._out)
                    emitted += 1
                    slot_emitted += 1
                    if self._finished(req, tok):
                        self._retire(slot)
                        break
                spec_emitted += slot_emitted
                stat_observe("serving/spec_tokens_per_cycle",
                             slot_emitted)
                continue
            tok = int(toks[S + slot] if spec else toks[slot])
            req._emit(tok, out=self._out)
            emitted += 1
            if self._finished(req, tok):
                self._retire(slot)
        if spec:
            self.spec_cycles += 1
            stat_add("serving/spec_cycles")
        stat_add("serving/tokens", emitted)
        self.late_rows += late_rows
        rec["emitted"] += emitted
        rec["late_rows"] += late_rows
        rec["prefill_chunks"] = rec.get("prefill_chunks", 0) + chunks
        rec["chunk_tokens"] = rec.get("chunk_tokens", 0) + chunk_tokens
        if spec:
            rec["spec_proposed"] = spec_proposed
            rec["spec_accepted"] = spec_accepted
            rec["spec_emitted"] = spec_emitted
            rec["spec_slots"] = len(spec)
        if dt > 0 and emitted:
            stat_observe("serving/tokens_per_sec", emitted / dt)

    def _land_block_pass(self, slot: int, req: GenerationRequest,
                         landed: Tuple[int, int, bool], toks, rec) -> int:
        """The host half of one slot's pass of a block: keep the block's
        state as the pass left it (the next pass takes it from here if
        the pipeline is drained by then; after a ride it is the NEW
        block's), count the pass — a riding slot-pass once, as a
        denoising pass, and in ``ride_slots``; ``commit_slots`` counts
        commits that rode alone — and if it was the block's last
        denoising pass emit the block — the positions the passes fixed,
        in position order, each with the pass that fixed it — retiring
        the request where it ends. Returns the tokens emitted."""
        k, emits, rode = landed
        if k < 0:
            rec["commit_slots"] += 1
            stat_add("serving/commit_passes")
            return 0
        S, B = self._pool.num_slots, self._block
        rec["denoise_slots"] += 1
        stat_add("serving/denoise_passes")
        if rode:
            rec["ride_slots"] += 1
            stat_add("serving/commit_rides")
        # the block state closes the step's result: [S * B] token ids,
        # [S * B] the pass each position was fixed in
        # (models/generation.py block_result_layout)
        at = len(toks) - 2 * S * B + slot * B
        tok = np.array(toks[at:at + B])
        fixed = np.array(toks[at + S * B:at + S * B + B])
        req.block_state = (tok, fixed)
        rec["tokens_fixed"] += int(np.sum(fixed == k))
        out = 0
        now = time.perf_counter()
        for j in range(B):
            if out == emits:
                break               # the surplus of a last block
            if fixed[j] < 0:
                continue            # given by the prompt
            req._emit(int(tok[j]), fixed_pass=int(fixed[j]), now=now,
                      out=self._out)
            out += 1
            if self._finished(req, int(tok[j])):
                self._retire(slot)
                break
        return out
