"""Continuous-batching serving engine (paddle_tpu/serving/).

Three layers of guarantees:

* **parity** — greedy engine output is token-identical to a reference
  ``models.generate`` run per request, under any admission interleaving
  (the paged pool + chunked feed + fused ragged step must be EXACTLY
  the compiled generate loop's semantics);
* **compile discipline** — one trace per fused ``(Q, T)`` program,
  greedy and sampled rows in the same one, asserted via the
  ``trace_probe`` / ``dispatch/retrace_cause`` counters;
* **scheduler policy** — churn (join/leave/cancel/timeout in any
  order), slot reuse without leaks, queue-full backpressure, deadline
  errors and graceful drain, fuzzed over a real engine plus
  deterministic mock-device scheduler tests.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import monitor, trace_probe
from paddle_tpu.models import GPTConfig, GPTForPretraining, generate
from paddle_tpu.serving import (DeadlineExceeded, GenerationEngine,
                                GenerationRequest, QueueFullError,
                                RequestCancelled, Scheduler)

from _mock_serving import MockDevice, mock_pool

VOCAB = 96


@pytest.fixture(scope="module")
def served_model():
    """A tiny char GPT trained for a few steps: trained logits have
    clear argmax margins, so greedy parity cannot flake on numeric
    noise between the batched-slot and single-request programs."""
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=128,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.Adam(learning_rate=3e-3,
                                parameters=model.parameters())
    corpus = ("the quick brown fox jumps over the lazy dog. "
              "pack my box with five dozen liquor jugs. ") * 6
    data = np.frombuffer(corpus.encode(), np.uint8).astype(np.int32) % VOCAB
    rng = np.random.RandomState(0)
    seq, batch = 24, 8
    for _ in range(30):
        starts = rng.randint(0, len(data) - seq - 1, batch)
        chunk = np.stack([data[s:s + seq + 1] for s in starts])
        loss, _ = model(paddle.to_tensor(chunk[:, :-1]),
                        paddle.to_tensor(chunk[:, 1:].astype(np.int64)))
        loss.backward()
        opt.step()
        opt.clear_grad()
    model.eval()
    return model


def _prompt(rng, n):
    return rng.randint(1, VOCAB, n).astype(np.int32)


# ---------------------------------------------------------------------------
# parity + compile discipline (the real engine)
# ---------------------------------------------------------------------------

class TestParity:
    def test_single_request_matches_generate(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=48)
        p = _prompt(np.random.RandomState(1), 7)
        out = eng.submit(p, max_new_tokens=8).result(timeout=300)
        ref = generate(served_model, p[None, :], max_new_tokens=8)
        np.testing.assert_array_equal(out, ref.numpy()[0])
        eng.close()

    def test_32_mixed_requests_parity_and_one_trace_per_bucket(
            self, served_model):
        """The acceptance criterion: 8 slots, 32 concurrent mixed-length
        requests — all complete, outputs match per-request greedy
        generate, and every fused (Q, T) program the storm reached was
        traced exactly once, with no retrace cause on record."""
        eng = GenerationEngine(served_model, num_slots=8, max_len=48)
        rng = np.random.RandomState(2)
        specs = [(_prompt(rng, int(rng.randint(2, 21))),
                  int(rng.randint(1, 9))) for _ in range(32)]

        handles = [None] * len(specs)

        def client(i):
            p, n = specs[i]
            handles[i] = eng.submit(p, max_new_tokens=n)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(specs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = [h.result(timeout=300) for h in handles]
        eng.close()

        for (p, n), out in zip(specs, outs):
            ref = generate(served_model, p[None, :], max_new_tokens=n)
            np.testing.assert_array_equal(out, ref.numpy()[0])
        # compile discipline: which (Q, T) buckets a storm reaches
        # depends on scheduling, but the fused step is the ONLY serving
        # program and every bucket traces EXACTLY ONCE (traces > 1 would
        # be the retrace-storm bug class); the ladder is bounded by the
        # pow2 products — q in {8..128} x table in {1, 2, 3} here
        sites = {k: v for k, v in trace_probe.snapshot().items()
                 if k.startswith("serving/") and f"#{eng._eid}" in k}
        assert sites, "serving probe sites missing"
        assert all(k.startswith("serving/fused[q") for k in sites), \
            sorted(sites)
        assert len(sites) <= 15, sorted(sites)
        for name, rec in sites.items():
            assert rec["traces"] == 1, (name, rec)
            assert not rec["causes"], (name, rec)

    def test_an_engine_built_with_no_options_serves_the_fused_step(
            self, served_model):
        """One serving path: nothing has to be asked for. The only
        serving program a bare engine traces is the fused (Q, T) step,
        the prompt goes in as chunks of the cycles' launches, and the
        snapshot no longer reports a layout or an attention kind."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=48,
                               prefill_budget=4)
        p = _prompt(np.random.RandomState(21), 11)
        out = eng.submit(p, max_new_tokens=3).result(timeout=300)
        stats = eng.stats()
        cycles = eng.flight_recorder.snapshot()["cycles"]
        eng.close()
        ref = generate(served_model, p[None, :], max_new_tokens=3)
        np.testing.assert_array_equal(out, ref.numpy()[0])
        sites = [k for k in trace_probe.snapshot()
                 if k.startswith("serving/") and f"#{eng._eid}" in k]
        assert sites and all(k.startswith("serving/fused[q")
                             for k in sites), sites
        # 11 prompt tokens at 4 a cycle: three chunk launches, the first
        # token out of the third
        fed = [c["chunk_tokens"] for c in cycles if c.get("chunk_tokens")]
        assert fed == [4, 4, 3], cycles
        assert all("launch_q" in c and "kv_tokens" in c
                   for c in cycles if c.get("chunk_tokens"))
        assert stats["prefill_chunks"] == 3
        assert stats["chunked_prefill_tokens"] == 11
        assert stats["block_size"] == 16 and stats["num_blocks"] == 6
        assert "kv_layout" not in stats and "attention" not in stats

    @pytest.mark.parametrize("kwargs,match", [
        (dict(kv_layout="dense"), "kv_layout='dense'.*removed in PR 31"),
        (dict(attention="gather"), "attention='gather'.*removed in PR 31"),
        (dict(kv_layout="paged", attention="fused"), None),
    ])
    def test_the_two_removed_options_accept_one_literal_each(
            self, served_model, kwargs, match):
        """``benchmark/`` still passes ``kv_layout="paged",
        attention="fused"``: those construct (and select nothing); every
        other value is refused by the option's name."""
        if match is None:
            GenerationEngine(served_model, num_slots=1, max_len=16,
                             **kwargs).close()
            return
        with pytest.raises(ValueError, match=match):
            GenerationEngine(served_model, num_slots=1, max_len=16,
                             **kwargs)

    def test_eos_early_stop_matches_generate(self, served_model):
        p = _prompt(np.random.RandomState(3), 6)
        ref8 = generate(served_model, p[None, :], max_new_tokens=8)
        eos = int(ref8.numpy()[0, 6 + 2])   # stop at the third new token
        ref = generate(served_model, p[None, :], max_new_tokens=8,
                       eos_token_id=eos, pad_token_id=0)
        eng = GenerationEngine(served_model, num_slots=2, max_len=48)
        out = eng.submit(p, max_new_tokens=8, eos_token_id=eos) \
                 .result(timeout=300)
        eng.close()
        np.testing.assert_array_equal(out, ref.numpy()[0])

    def test_streaming_yields_tokens_incrementally(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=48)
        p = _prompt(np.random.RandomState(4), 5)
        got = list(eng.stream(p, max_new_tokens=6))
        eng.close()
        ref = generate(served_model, p[None, :], max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got, np.int32),
                                      ref.numpy()[0, 5:])

    def test_sampled_requests_share_the_one_decode_trace(
            self, served_model):
        eng = GenerationEngine(served_model, num_slots=4, max_len=48)
        rng = np.random.RandomState(5)
        greedy = eng.submit(_prompt(rng, 6), max_new_tokens=5)
        sampled = eng.submit(_prompt(rng, 6), max_new_tokens=5,
                             do_sample=True, temperature=0.7)
        o1, o2 = greedy.result(timeout=300), sampled.result(timeout=300)
        eng.close()
        assert o1.shape == o2.shape == (11,)
        assert ((0 <= o2) & (o2 < VOCAB)).all()
        # mixed sampling, one program a (Q, T) bucket: do_sample and
        # temperature are traced values of the fused step
        sites = {k: v for k, v in trace_probe.snapshot().items()
                 if k.startswith("serving/") and f"#{eng._eid}" in k}
        assert sites and all(k.startswith("serving/fused[q")
                             for k in sites), sorted(sites)
        for name, rec in sites.items():
            assert rec["traces"] == 1, (name, rec)

    def test_analyze_clean_bill(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=32)
        eng.submit(_prompt(np.random.RandomState(6), 4),
                   max_new_tokens=2).result(timeout=300)
        report = eng.analyze()
        eng.close()
        assert report.ok(), report.table()
        # donation-safe AND host-sync-free, not merely "no findings ran"
        assert "donation-safety" in report.passes_run
        assert "host-sync" in report.passes_run


# ---------------------------------------------------------------------------
# churn over the real engine
# ---------------------------------------------------------------------------

class TestChurn:
    def test_slot_reuse_no_leak_200_requests_through_8_slots(
            self, served_model):
        eng = GenerationEngine(served_model, num_slots=8, max_len=32,
                               max_queue=256)
        rng = np.random.RandomState(7)
        monitor.stat_reset("serving/completed")
        handles = [eng.submit(_prompt(rng, int(rng.randint(1, 9))),
                              max_new_tokens=int(rng.randint(1, 4)))
                   for _ in range(200)]
        outs = [h.result(timeout=600) for h in handles]
        assert len(outs) == 200
        assert eng._pool.n_active == 0
        assert eng._pool.n_free == 8
        assert monitor.stat_get("serving/completed") == 200
        eng.close()

    def test_cancel_mid_generation_frees_the_slot(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=64)
        p = _prompt(np.random.RandomState(8), 4)
        h = eng.submit(p, max_new_tokens=40)
        it = h.stream()
        first = next(it)
        assert isinstance(first, int)
        h.cancel()
        with pytest.raises(RequestCancelled):
            for _ in it:
                pass
        with pytest.raises(RequestCancelled):
            h.result(timeout=300)
        # capacity was reclaimed: a follow-up request still serves
        out = eng.submit(p, max_new_tokens=3).result(timeout=300)
        assert out.shape == (7,)
        assert eng._pool.n_active == 0
        eng.close()

    def test_close_drains_in_flight_work(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=48)
        rng = np.random.RandomState(9)
        handles = [eng.submit(_prompt(rng, 5), max_new_tokens=4)
                   for _ in range(6)]
        eng.close()          # must serve all 6, not abandon the queue
        for h in handles:
            assert h.result(timeout=1).shape == (9,)
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(_prompt(rng, 3))

    def test_close_cancel_pending_rejects_the_queue(self, served_model):
        eng = GenerationEngine(served_model, num_slots=1, max_len=48)
        rng = np.random.RandomState(10)
        handles = [eng.submit(_prompt(rng, 5), max_new_tokens=6)
                   for _ in range(5)]
        for _ in range(400):            # let the head request go in-flight
            if eng.active_requests:
                break
            time.sleep(0.005)
        eng.close(cancel_pending=True)
        resolved = {"done": 0, "cancelled": 0}
        for h in handles:
            try:
                h.result(timeout=1)
                resolved["done"] += 1
            except RequestCancelled:
                resolved["cancelled"] += 1
        assert resolved["done"] >= 1          # in-flight work finished
        assert resolved["cancelled"] >= 1     # the queue was rejected
        assert sum(resolved.values()) == 5

    def test_fuzz_join_leave_cancel_timeout_orderings(self, served_model):
        """Random concurrent churn: submissions racing cancels and tiny
        deadlines from many threads. Every handle must resolve (token
        sequence or the matching error), the pool must end empty, and
        the engine must still serve afterwards."""
        eng = GenerationEngine(served_model, num_slots=4, max_len=32,
                               max_queue=512)
        rng = np.random.RandomState(12)
        results = []
        lock = threading.Lock()

        def client(i):
            r = np.random.RandomState(100 + i)
            p = _prompt(r, int(r.randint(1, 9)))
            kw = {"max_new_tokens": int(r.randint(1, 6))}
            roll = r.rand()
            if roll < 0.25:
                kw["timeout"] = float(r.rand() * 0.05)   # likely expires
            h = eng.submit(p, **kw)
            if 0.25 <= roll < 0.5:
                time.sleep(float(r.rand() * 0.02))
                h.cancel()
            try:
                out = h.result(timeout=600)
                outcome = ("ok", out.shape[0])
            except (RequestCancelled, DeadlineExceeded) as e:
                outcome = (type(e).__name__,)
            with lock:
                results.append(outcome)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(48)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 48
        kinds = {r[0] for r in results}
        assert "ok" in kinds, results
        assert eng._pool.n_active == 0
        assert eng._pool.n_free == 4
        # still healthy after the storm
        p = _prompt(rng, 4)
        out = eng.submit(p, max_new_tokens=2).result(timeout=300)
        ref = generate(served_model, p[None, :], max_new_tokens=2)
        np.testing.assert_array_equal(out, ref.numpy()[0])
        eng.close()


# ---------------------------------------------------------------------------
# scheduler policy (deterministic, mock device steps)
# ---------------------------------------------------------------------------

class TestSchedulerPolicy:
    def test_queue_full_raises_synchronously(self):
        pool = mock_pool(slots=1)
        dev = MockDevice(pool)
        dev.prefill_gate.clear()        # scheduler blocks inside prefill
        sched = dev.scheduler(max_queue=2)
        sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        for _ in range(50):             # wait until the head is claimed
            if sched.queue_depth == 0:
                break
            time.sleep(0.01)
        sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        with pytest.raises(QueueFullError):
            sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        dev.prefill_gate.set()
        sched.close()

    def test_deadline_exceeded_while_queued(self):
        pool = mock_pool(slots=1)
        dev = MockDevice(pool)
        dev.prefill_gate.clear()
        sched = dev.scheduler()
        a = sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        b = sched.submit(GenerationRequest(np.ones(4, np.int32), 2,
                                           timeout=0.03))
        time.sleep(0.1)                 # b's deadline passes in queue
        dev.prefill_gate.set()
        a.result(timeout=5)
        with pytest.raises(DeadlineExceeded):
            b.result(timeout=5)
        sched.close()

    def test_deadline_exceeded_behind_queue_head(self):
        """A dead request BEHIND a slot-starved head must fail promptly
        (queue sweep), not when its turn finally comes — and must stop
        holding queue capacity meanwhile."""
        pool = mock_pool(slots=1)
        dev = MockDevice(pool, decode_delay=0.05)
        sched = dev.scheduler()
        # occupies the single slot for >= 50 * 0.05 = 2.5s
        long = sched.submit(GenerationRequest(np.ones(4, np.int32), 50))
        for _ in range(200):
            if sched.active:
                break
            time.sleep(0.005)
        a = sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        b = sched.submit(GenerationRequest(np.ones(4, np.int32), 2,
                                           timeout=0.05))
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceeded):
            b.result(timeout=30)
        assert time.perf_counter() - t0 < 1.5   # not after `long` drains
        assert not long.done()
        assert sched.queue_depth == 1           # b no longer holds a place
        long.cancel()
        a.cancel()
        sched.close()

    def test_deadline_exceeded_mid_generation(self):
        pool = mock_pool(slots=1)
        dev = MockDevice(pool, decode_delay=0.03)
        sched = dev.scheduler()
        h = sched.submit(GenerationRequest(np.ones(4, np.int32), 1000,
                                           timeout=0.15))
        with pytest.raises(DeadlineExceeded):
            h.result(timeout=10)
        assert h.emitted >= 1           # it streamed before expiring
        assert pool.n_active == 0       # and the slot was reclaimed
        sched.close()

    def test_admission_is_not_charged_the_chunk_budget(self):
        """Admission is host bookkeeping, so the per-cycle token budget
        does not gate it: three queued requests enter the SAME cycle
        into a pool with room for them, and the budget then bounds what
        each launch feeds of their prompts."""
        pool = mock_pool(slots=4)
        dev = MockDevice(pool)
        dev.prefill_gate.clear()        # hold the first admission...
        sched = dev.scheduler(prefill_budget=2)
        hs = [sched.submit(GenerationRequest(np.ones(5, np.int32), 1))
              for _ in range(3)]        # ...until all three are queued
        dev.prefill_gate.set()
        for h in hs:
            assert h.result(timeout=30).shape == (6,)
        sched.close()
        cycles = sched.recorder.snapshot()["cycles"]
        first = next(c for c in cycles if c["admitted"])
        assert first["admitted"] == [h.id for h in hs]
        fed = [sum(n for n in plan.values()) for plan in dev.launches]
        assert max(fed) <= 2 and sum(fed) == 15     # 3 prompts of 5

    def test_the_scheduler_has_one_step_callable(self):
        """One cycle: the third positional argument is the fused step,
        and there is no ``do_decode`` to pass beside it."""
        import inspect
        params = list(inspect.signature(Scheduler.__init__).parameters)
        assert params[:4] == ["self", "pool", "do_prefill",
                              "do_chunked_step"]
        assert "do_decode" not in params
        pool = mock_pool()
        dev = MockDevice(pool)
        with pytest.raises(TypeError):
            Scheduler(pool, dev.do_prefill, dev.do_step, dev.do_step)

    def test_step_failure_poisons_requests_not_the_loop(self):
        pool = mock_pool(slots=2)
        dev = MockDevice(pool)
        boom = {"armed": True}

        def bad_step(slot_requests, plan, prev=None):
            if boom["armed"]:
                # a real failed donated step leaves pool.data DELETED —
                # reproduce that, not just the exception
                pool.data.delete()
                raise RuntimeError("device fell over")
            return dev.do_step(slot_requests, plan, prev)

        sched = Scheduler(pool, dev.do_prefill, bad_step)
        h = sched.submit(GenerationRequest(np.ones(4, np.int32), 5))
        with pytest.raises(RuntimeError, match="serving step failed"):
            h.result(timeout=10)
        assert pool.n_active == 0
        boom["armed"] = False           # the loop survived and serves on
        h2 = sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        assert h2.result(timeout=10).shape == (6,)
        # the failure path reallocated the donated-then-deleted buffer
        assert float(np.asarray(pool.data).sum()) == 0.0
        sched.close()

    def test_prefill_failure_fails_only_that_request(self):
        """An admission-hook exception must fail ITS caller (not hang it),
        free the slot, and leave the loop serving — the request is in
        neither queue nor slots when it fails, so it needs its own
        failure path."""
        pool = mock_pool(slots=2)
        dev = MockDevice(pool)
        boom = {"armed": True}

        def bad_prefill(req, slot):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("prefill fell over")
            return dev.do_prefill(req, slot)

        sched = Scheduler(pool, bad_prefill, dev.do_step)
        h = sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        with pytest.raises(RuntimeError, match="serving step failed"):
            h.result(timeout=10)        # failed, not hung
        assert pool.n_active == 0       # the slot was reclaimed
        h2 = sched.submit(GenerationRequest(np.ones(4, np.int32), 2))
        assert h2.result(timeout=10).shape == (6,)
        sched.close()


# ---------------------------------------------------------------------------
# pool + validation surface
# ---------------------------------------------------------------------------

class TestPoolAndValidation:
    def test_pool_alloc_free(self):
        pool = mock_pool(slots=3, max_len=64)
        a, b = pool.alloc(), pool.alloc()
        assert (a, b) == (0, 1)
        pool.free(a)
        assert pool.alloc() == 0        # lowest-free-first, reused
        with pytest.raises(ValueError, match="not allocated"):
            pool.free(2)
        assert pool.n_active == 2 and pool.n_free == 1

    def test_pool_position_tracking(self):
        pool = mock_pool(slots=2, max_len=16)
        s = pool.alloc()
        pool.set_slot(s, pos=8, lo=3)
        assert pool.advance(s) == 9
        assert pool.slot_pos(s) == 9
        with pytest.raises(RuntimeError, match="rollback below"):
            pool.advance(s, -7)         # under the slot's floor lo=3
        assert pool.slot_pos(s) == 9    # a refused advance changes nothing
        with pytest.raises(ValueError, match="bad position"):
            pool.set_slot(s, pos=16, lo=0)

    @pytest.mark.parametrize("kwargs,want", [
        (dict(), 16),
        (dict(kv_dtype="int8"), 32),
        (dict(kv_dtype="int8", block_size=16), "block_size 16 < 32"),
    ])
    def test_block_size_defaults_to_the_kernels_floor_for_the_dtype(
            self, served_model, kwargs, want):
        """The fused kernel's block floor is the engine's: nobody has to
        know that an int8 tile needs 32 rows to build an int8 pool, and
        an explicit value under the floor still raises."""
        if isinstance(want, str):
            with pytest.raises(ValueError, match=want):
                GenerationEngine(served_model, num_slots=1, max_len=64,
                                 **kwargs)
            return
        eng = GenerationEngine(served_model, num_slots=1, max_len=64,
                               **kwargs)
        stats = eng.stats()
        eng.close()
        assert stats["block_size"] == want
        assert stats["num_blocks"] == 64 // want

    def test_statusz_row_names_no_layout(self, served_model):
        from paddle_tpu.framework import metrics
        eng = GenerationEngine(served_model, num_slots=2, max_len=32)
        row = next(ln for ln in metrics.statusz().splitlines()
                   if ln.startswith(f"engine #{eng._eid} "))
        eng.close()
        assert row.startswith(
            f"engine #{eng._eid} queue=0 active=0 slots=0/2 blocks=0/4 "
            f"prefix_hit=0.00"), row

    def test_submit_validation(self, served_model):
        eng = GenerationEngine(served_model, num_slots=1, max_len=16,
                               min_bucket=8)
        with pytest.raises(ValueError, match="capacity"):
            eng.submit(np.ones(9, np.int32), max_new_tokens=8)  # 16+8>16
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.ones(4, np.int32), max_new_tokens=0)
        with pytest.raises(ValueError, match="at least one"):
            eng.submit(np.zeros(0, np.int32))
        eng.close()


# ---------------------------------------------------------------------------
# two launches in flight: launch N+1 is dispatched before launch N is
# fetched and emitted, the next tokens stay on the device
# ---------------------------------------------------------------------------

def _launch_records(recorder):
    """The records that describe a launch (a turn that only lands
    records itself too, with no ``launch_q`` / ``decode_dispatch_ms``)."""
    return [c for c in recorder.snapshot()["cycles"]
            if c["decode_dispatch_ms"] > 0]


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
            self, served_model, end):
        """Two slots: X ends by ``end`` beside a long-running Y, and F,
        queued behind them, takes over X's slot and blocks. Every token
        anyone got is ``models.generate``'s; a request that the host
        found ended one launch late (EOS, cancel, deadline) leaves a
        LATE row behind, counted and dropped: nothing is emitted after
        the end, and F reads none of the dead row's K/V."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=64)
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
        eng.close()
        assert len(x.tokens) == n_x         # nothing emitted after the end
        np.testing.assert_array_equal(x.tokens, ref_x[:n_x])
        for p, n, out in ((pf, 6, out_f), (py, 40, out_y)):
            ref = generate(served_model, p[None, :], max_new_tokens=n)
            np.testing.assert_array_equal(out, ref.numpy()[0])
        launches = _launch_records(eng.flight_recorder)
        late = sum(c["late_rows"] for c in launches)
        # max_new_tokens is known at plan time: the request gets no row
        # in the launch after its last token's. The other three the host
        # learns at the emit, after that launch went out
        assert late == (0 if end == "max_new_tokens" else 1), launches
        assert late == eng._sched.late_rows
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
