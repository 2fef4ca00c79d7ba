"""Two launches in flight (``paddle_tpu/serving/scheduler.py``): launch N+1
is dispatched before launch N is fetched and emitted, and the next tokens
stay on the device. Greedy parity with ``models.generate`` whatever ends a
request one launch late, late rows counted and dropped, a sampled batch
reproducible from its seed, and the pipeline's order on the mock device.
Part of ``tests/test_serving_engine.py`` until a file had to fit a worker's
share of the suite (PR 45)."""
import time

import numpy as np
import pytest

from paddle_tpu.models import generate
from paddle_tpu.serving import (DeadlineExceeded, GenerationEngine,
                                GenerationRequest, RequestCancelled,
                                Scheduler)

import _toys
from _mock_serving import MockDevice, mock_pool

# two slots with room for a request of 40 tokens beside two others
# (``engines`` hands the engine out drained, its pool and trie as new)
LONG = dict(num_slots=2, max_len=64)


def _prompt(rng, n):
    return rng.randint(1, _toys.VOCAB, n).astype(np.int32)


def _launch_records(recorder, since=0):
    """The records that describe a launch (a turn that only lands
    records itself too, with no ``launch_q`` / ``decode_dispatch_ms``),
    of the turns after ``since``."""
    return [c for c in recorder.snapshot()["cycles"]
            if c["decode_dispatch_ms"] > 0 and c["cycle"] > since]


def _submit_together(eng, specs):
    """Submit ``specs`` (``(prompt, kwargs)``) so that ONE turn of the
    scheduler admits them all: the queue's lock is re-entrant, so the
    loop cannot look at the queue until the last one is in it."""
    with eng._sched._cond:
        return [eng.submit(p, **kw) for p, kw in specs]


class TestTwoLaunchesInFlight:
    @pytest.mark.parametrize("end", ["eos", "max_new_tokens", "cancel",
                                     "deadline"])
    def test_greedy_parity_whatever_ends_a_request_one_launch_late(
            self, served_model, engines, end):
        """Two slots: X ends by ``end`` beside a long-running Y, and F,
        queued behind them, takes over X's slot and blocks. Every token
        anyone got is ``models.generate``'s; a request that the host
        found ended one launch late (EOS, cancel, deadline) leaves a
        LATE row behind, counted and dropped: nothing is emitted after
        the end, and F reads none of the dead row's K/V."""
        eng = engines(served_model, **LONG)
        since, late0 = eng._sched._cycle, eng._sched.late_rows
        rng = np.random.RandomState(31)
        px, py, pf = _prompt(rng, 6), _prompt(rng, 9), _prompt(rng, 7)
        # warm the (Q, T) programs so that a deadline is not spent on a
        # compile
        eng.submit(py, max_new_tokens=2).result(timeout=300)
        ref_x = generate(served_model, px[None, :],
                         max_new_tokens=30).numpy()[0, 6:]
        kw = {"max_new_tokens": 30}
        if end == "eos":
            # a token first seen mid-stream, when two launches are in
            # flight (the stretch's first launch lands in its own turn)
            seen = list(ref_x)
            at = next(i for i in range(3, 30) if seen.index(seen[i]) == i)
            kw["eos_token_id"], n_x = int(seen[at]), at + 1
        elif end == "max_new_tokens":
            kw["max_new_tokens"] = n_x = 5
        y = eng.submit(py, max_new_tokens=40)
        x = eng.submit(px, **kw)
        f = eng.submit(pf, max_new_tokens=6)
        if end in ("cancel", "deadline"):
            it = x.stream()
            next(it)
            if end == "cancel":
                x.cancel()
            else:
                x.deadline = time.perf_counter()    # it passes mid-stream
            with pytest.raises(RequestCancelled if end == "cancel"
                               else DeadlineExceeded):
                x.result(timeout=300)
            n_x = len(x.tokens)
            assert 1 <= n_x < 30
        else:
            assert x.result(timeout=300).shape == \
                (6 + kw["max_new_tokens"],)
        assert x._q.qsize() <= n_x + 1      # its tokens and the terminator
        out_f, out_y = f.result(timeout=300), y.result(timeout=300)
        _toys.settle(eng)
        assert len(x.tokens) == n_x         # nothing emitted after the end
        np.testing.assert_array_equal(x.tokens, ref_x[:n_x])
        for p, n, out in ((pf, 6, out_f), (py, 40, out_y)):
            ref = generate(served_model, p[None, :], max_new_tokens=n)
            np.testing.assert_array_equal(out, ref.numpy()[0])
        launches = _launch_records(eng.flight_recorder, since)
        late = sum(c["late_rows"] for c in launches)
        # max_new_tokens is known at plan time: the request gets no row
        # in the launch after its last token's. The other three the host
        # learns at the emit, after that launch went out
        assert late == (0 if end == "max_new_tokens" else 1), launches
        assert late == eng._sched.late_rows - late0
        # two busy stretches (the warming request's, then this one): each
        # opens with a launch that lands in its own turn, and the launch
        # after that finds nothing in flight
        assert sum(c["overlapped"] for c in launches) >= len(launches) - 6

    def test_sampled_batch_is_reproducible_from_the_seed(self, served_model):
        """Which launch a request lands in decides its key, so a batch
        submitted together — one launch sequence — gives the same tokens
        from two engines of one seed, and other tokens from another
        seed's."""
        rng = np.random.RandomState(32)
        specs = [(_prompt(rng, 4 + i), dict(
            max_new_tokens=8, do_sample=True, temperature=0.9))
            for i in range(3)]

        def run(seed):
            eng = GenerationEngine(served_model, num_slots=4, max_len=48,
                                   seed=seed)
            outs = [h.result(timeout=300)
                    for h in _submit_together(eng, specs)]
            launches = _launch_records(eng.flight_recorder)
            eng.close()
            assert any(c["overlapped"] for c in launches)
            return outs

        a, b, c = run(5), run(5), run(6)
        for u, v in zip(a, b):
            np.testing.assert_array_equal(u, v)
        assert any((u != w).any() for u, w in zip(a, c))

    def test_the_next_token_never_visits_the_host(self):
        """The chained mock answers a row by its INPUT token: a decode
        row dispatched while the request's newest token is un-fetched
        can only be right if the scheduler named the slot and the step
        read the previous result."""
        pool = mock_pool(slots=3, max_len=64)
        dev = MockDevice(pool, chain=True)
        sched = dev.scheduler(prefill_budget=8)
        rng = np.random.RandomState(33)
        prompts = [_prompt(rng, n) for n in (5, 13, 3)]
        hs = [sched.submit(GenerationRequest(p, 7)) for p in prompts]
        for p, h in zip(prompts, hs):
            out = h.result(timeout=30)
            assert list(out[len(p):]) == MockDevice.expected(p, 7)
        sched.close()
        assert any(dev.from_prev), "no launch read the previous result"
        # a row reads the previous result only for a slot that had a
        # token in it: planned there, feed drained by then
        for before, plan, slots in zip(dev.launches, dev.launches[1:],
                                       dev.from_prev[1:]):
            assert set(slots) <= set(before) & set(plan)
            assert all(plan[s] == 1 for s in slots)
        launches = _launch_records(sched.recorder)
        assert not launches[0]["overlapped"] and not launches[1]["overlapped"]
        assert all(c["overlapped"] for c in launches[2:])
        assert sched.late_rows == 0

    def test_pool_pressure_drains_the_pipeline_before_it_preempts(self):
        """4 usable blocks of 8, two requests that want 3 each: growth
        exhausts the pool mid-decode. The launch in flight is landed
        first, so the victim's history is whole at re-admission — the
        chained mock would answer a dropped or doubled token with a
        wrong successor."""
        pool = mock_pool(slots=2, max_len=32, num_blocks=4)
        dev = MockDevice(pool, chain=True)
        sched = dev.scheduler()
        rng = np.random.RandomState(34)
        prompts = [_prompt(rng, 8), _prompt(rng, 8)]
        hs = [sched.submit(GenerationRequest(p, 12)) for p in prompts]
        for p, h in zip(prompts, hs):
            out = h.result(timeout=30)
            assert list(out[8:]) == MockDevice.expected(p, 12)
        sched.close()
        assert sched.preempts >= 1
        launches = _launch_records(sched.recorder)
        for c in launches:
            if c["preempts"]:
                assert not c["overlapped"], c
        assert any(c["overlapped"] for c in launches)
        assert pool.n_active == 0

    def test_a_copy_on_write_drains_the_pipeline_first(self):
        """A plan that has to copy a shared block lands the launch in
        flight before the copy goes out."""
        pool = mock_pool(slots=1, max_len=32)
        dev = MockDevice(pool, chain=True)
        seen = []

        def step(slot_requests, plan, prev=None):
            if len(dev.launches) == 3:
                # someone else takes a reference to the block the NEXT
                # decode row writes into: its append must copy
                block = pool.slot_table(0)[pool.slot_pos(0) // 8]
                pool._ref[block] = pool._ref.get(block, 1) + 1
            return dev.do_step(slot_requests, plan, prev)

        def copy(dst, src):
            seen.append((sched._inflight is None, dst, src))

        sched = Scheduler(pool, dev.do_prefill, step, do_copy=copy)
        p = _prompt(np.random.RandomState(35), 4)
        out = sched.submit(GenerationRequest(p, 10)).result(timeout=30)
        sched.close()
        assert list(out[4:]) == MockDevice.expected(p, 10)
        assert len(seen) == 1 and seen[0][0], seen
        launches = _launch_records(sched.recorder)
        assert [c["overlapped"] for c in launches[:6]] == \
            [False, False, True, True, False, True]

    def test_a_failing_step_fails_the_launch_in_flight_too(self):
        pool = mock_pool(slots=2)
        dev = MockDevice(pool, chain=True)

        def step(slot_requests, plan, prev=None):
            if len(dev.launches) == 2:
                dev.launches.append("failed")
                raise RuntimeError("device fell over")
            return dev.do_step(slot_requests, plan, prev)

        sched = Scheduler(pool, dev.do_prefill, step)
        hs = [sched.submit(GenerationRequest(np.ones(4, np.int32), 9))
              for _ in range(2)]
        for h in hs:
            with pytest.raises(RuntimeError, match="serving step failed"):
                h.result(timeout=10)
            assert len(h.tokens) <= 1       # launch 2's tokens never came
        assert pool.n_active == 0 and sched._inflight is None
        # the loop survived and serves on
        p = _prompt(np.random.RandomState(36), 5)
        out = sched.submit(GenerationRequest(p, 4)).result(timeout=10)
        assert list(out[5:]) == MockDevice.expected(p, 4)
        sched.close()
        failed = [c for c in sched.recorder.snapshot()["cycles"]
                  if "failed" in c]
        # the launch in flight and the turn whose dispatch failed
        assert len(failed) == 2 and failed[0]["decode_dispatch_ms"] > 0
