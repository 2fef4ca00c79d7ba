"""The scheduler's policy tests' device: a real ``PagedKVPool`` of
``head_dim=1`` under a fake admission hook and a fake fused step.

One place for what ``test_serving_engine.py``, ``test_frontdoor.py``,
``test_serving_trace.py``, ``test_numerics.py``, ``test_memory_tracker.py``
and ``test_ragged_attention.py`` drive ``Scheduler`` with when no model is
wanted: the pool's bookkeeping (blocks, page tables, preemption) is the
engine's own, only the launches are stand-ins.
"""
import threading
import time

import numpy as np

from paddle_tpu.serving import PagedKVPool, Scheduler


def mock_pool(slots=2, max_len=64, block_size=8, **kw):
    return PagedKVPool(num_layers=1, num_slots=slots, num_heads=1,
                       max_len=max_len, head_dim=1, block_size=block_size,
                       **kw)


class _Pending:
    """An un-fetched launch result: ``values`` are there at once (the
    next launch may read them, as on the device), the HOST gets them at
    ``ready_at`` — ``np.asarray`` of it, which is what the scheduler's
    one fetch does, waits until then."""

    def __init__(self, values, ready_at):
        self.values, self.ready_at = values, ready_at

    def __getitem__(self, i):
        return self.values[i]

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        return self.values


class MockDevice:
    """Deterministic stand-in for the engine's device side.

    ``do_prefill`` does the engine's admission bookkeeping (fresh blocks
    only — no prefix cache — so freed blocks return to the free list and
    pressure must be answered by preemption); ``do_step`` answers every
    slot with ``token`` (then ``tail``, e.g. the logits-finite sentinel).
    ``prefill_gate`` holds the scheduler inside an admission while clear.
    ``decode_delay`` is device time, not host time: a launch returns at
    once and its result is ready that long after the launch before it
    (or after its own dispatch, if later), so two launches in flight
    behave as they do on a device's queue.

    With ``chain=True`` a slot's answer depends on the row's input token
    as a model's does: ``next_token(t)`` of the last token the slot fed
    this launch — a chunk's last token, a decode row's ``last_token``,
    or, for the slots the scheduler names in ``prev``, that slot's entry
    of the previous launch's result, which the mock kept as the engine's
    device does. A request's whole output then follows from its prompt
    (``expected``), whichever launches carried it.
    """

    VOCAB = 97

    def __init__(self, pool, prefill_delay=0.0, decode_delay=0.0, token=2,
                 tail=(), chain=False):
        self.pool = pool
        self.prefill_delay = prefill_delay
        self.decode_delay = decode_delay
        self.token = token
        self.tail = tuple(tail)
        self.chain = chain
        self.prefill_gate = threading.Event()
        self.prefill_gate.set()
        self.launches = []              # the plan of each launch
        self.from_prev = []             # the slots each launch read from prev
        self._busy_until = 0.0          # when the device's queue runs dry

    @classmethod
    def next_token(cls, tok):
        return (int(tok) * 31 + 7) % cls.VOCAB

    @classmethod
    def expected(cls, prompt, n):
        """The ``n`` tokens a chained mock generates after ``prompt``."""
        out, tok = [], prompt[-1]
        for _ in range(n):
            tok = cls.next_token(tok)
            out.append(tok)
        return out

    def do_prefill(self, req, slot):
        self.prefill_gate.wait()
        if self.prefill_delay:
            time.sleep(self.prefill_delay)
        feed = np.concatenate([req.prompt,
                               np.asarray(req.tokens, np.int32)])
        self.pool.admit_fresh(slot, feed.size)
        self.pool.set_slot(slot, pos=0, lo=0)
        req.pending_feed = [int(t) for t in feed]

    def do_step(self, slot_requests, plan, prev=None):
        self.launches.append(dict(plan))
        prev_toks, from_prev = prev if prev is not None else (None, ())
        self.from_prev.append(sorted(from_prev))
        toks = [self.token] * self.pool.num_slots
        if self.chain:
            for slot, req in slot_requests.items():
                if slot in from_prev:
                    fed = prev_toks[slot]
                elif req.pending_feed:
                    fed = req.pending_feed[plan[slot] - 1]
                else:
                    fed = req.last_token
                toks[slot] = self.next_token(fed)
        toks = np.asarray(toks + list(self.tail), np.int32)
        if not self.decode_delay:
            return toks
        self._busy_until = max(self._busy_until, time.perf_counter()) \
            + self.decode_delay
        return _Pending(toks, self._busy_until)

    def scheduler(self, **kw):
        return Scheduler(self.pool, self.do_prefill, self.do_step, **kw)


class RaggedMockDevice(MockDevice):
    """A ``MockDevice`` whose launches are laid out by the ENGINE's own
    ``_ragged_operands``: the real scheduler plans, the real pool resolves
    the page tables, the engine's code picks the ``(Q, T)`` program and its
    tower rows and stamps the launch record — only the program itself is
    missing (no model, nothing compiled). ``programs`` holds the ``(Q, T,
    R)`` of every launch, in order.

    The engine is its methods on a bare instance: what ``_ragged_operands``
    reads of it (the pool, a one-layer full-attention spec, the chunk
    budget) is set here, and a launch's step function is a name."""

    def __init__(self, pool, prefill_budget, **kw):
        import types

        from paddle_tpu.models import decoder_spec as DS
        from paddle_tpu.serving import GenerationEngine
        super().__init__(pool, **kw)
        self.programs = []
        self.budget = int(prefill_budget)
        eng = self.engine = GenerationEngine.__new__(GenerationEngine)
        eng._pool, eng._mesh, eng._mp, eng._window = pool, None, 1, 0
        eng._spec, eng._spec_k = False, 0
        eng._chunk_budget = self.budget
        eng._decoder_spec = DS.DecoderSpec(
            layers=(DS.LayerSpec(DS.FULL, DS.CacheSpec(rows=1, lanes=2),
                                 DS.DENSE),),
            vocab_size=self.VOCAB, max_positions=pool.max_len)
        eng._fused_step_fn = lambda Q, T: types.SimpleNamespace(
            jitted=types.SimpleNamespace(__name__=f"fused_step_q{Q}_t{T}"))

    def do_step(self, slot_requests, plan, prev=None):
        from_prev = prev[1] if prev is not None else ()
        Q, T, ops, *_ = self.engine._ragged_operands(
            slot_requests, plan, from_prev=from_prev)
        self.programs.append((Q, T, int(ops[0].shape[0])))
        return super().do_step(slot_requests, plan, prev)

    def scheduler(self, **kw):
        sched = super().scheduler(prefill_budget=self.budget, **kw)
        self.engine._sched = sched
        return sched


class MockEngine:
    """The ``submit`` contract of ``GenerationEngine`` over a mock
    device's scheduler, for what stands in front of an engine (the HTTP
    front door): requests are real ``GenerationRequest``s through the
    real ``Scheduler`` — sink, deadline, cancel and all — and only the
    launches are stand-ins. ``handles`` keeps every accepted request."""

    def __init__(self, device, **sched_kw):
        self.device = device
        self.sched = device.scheduler(**sched_kw)
        self.handles = []

    def submit(self, prompt_ids, max_new_tokens=32, **kw):
        from paddle_tpu.serving.scheduler import GenerationRequest
        req = GenerationRequest(np.asarray(prompt_ids, np.int32),
                                int(max_new_tokens), **kw)
        self.handles.append(self.sched.submit(req))
        return req

    def stats(self):
        return {"queue_depth": self.sched.queue_depth,
                "active_requests": self.sched.active}

    def close(self):
        self.sched.close(cancel_pending=True)
