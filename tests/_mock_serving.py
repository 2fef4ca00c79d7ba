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


class MockDevice:
    """Deterministic stand-in for the engine's device side.

    ``do_prefill`` does the engine's admission bookkeeping (fresh blocks
    only — no prefix cache — so freed blocks return to the free list and
    pressure must be answered by preemption); ``do_step`` answers every
    slot with ``token`` (then ``tail``, e.g. the logits-finite sentinel).
    ``prefill_gate`` holds the scheduler inside an admission while clear.
    """

    def __init__(self, pool, prefill_delay=0.0, decode_delay=0.0, token=2,
                 tail=()):
        self.pool = pool
        self.prefill_delay = prefill_delay
        self.decode_delay = decode_delay
        self.token = token
        self.tail = tuple(tail)
        self.prefill_gate = threading.Event()
        self.prefill_gate.set()
        self.launches = []              # the plan of each launch

    def do_prefill(self, req, slot):
        self.prefill_gate.wait()
        if self.prefill_delay:
            time.sleep(self.prefill_delay)
        feed = np.concatenate([req.prompt,
                               np.asarray(req.tokens, np.int32)])
        self.pool.admit_fresh(slot, feed.size)
        self.pool.set_slot(slot, pos=0, lo=0)
        req.pending_feed = [int(t) for t in feed]

    def do_step(self, slot_requests, plan):
        if self.decode_delay:
            time.sleep(self.decode_delay)
        self.launches.append(dict(plan))
        return np.asarray(
            [self.token] * self.pool.num_slots + list(self.tail), np.int32)

    def scheduler(self, **kw):
        return Scheduler(self.pool, self.do_prefill, self.do_step, **kw)
