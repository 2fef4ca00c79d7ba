"""Speculative decoding on the fused ragged serving step
(GenerationEngine(spec_draft=..., spec_k=...)).

Four layers of guarantees:

* **greedy parity** — speculative output is TOKEN-IDENTICAL to the
  non-speculative fused engine and to per-request ``models.generate``,
  for 32 mixed concurrent requests, with zero retraces on warm
  (q, table) buckets and a clean ``analyze()`` bill — regardless of how
  bad the draft is (rejection + correction IS the guarantee; the draft
  only moves the accept rate);
* **the multiplier** — on an agreeing workload (draft == target)
  ``spec_tokens_per_cycle > 1`` and the accept rate is 1.0: more than
  one token per decode cycle through the existing one-fetch contract;
* **distribution correctness** — sampled mode passes the
  rejection-sampling identity test: the emitted-token distribution
  equals the target's sampling distribution for ANY draft proposal
  distribution;
* **machinery** — signed ``advance`` rollback bookkeeping, cache
  un-publishing on rollback, preemption/prefix-cache interplay, and
  fail-fast construction validation.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import trace_probe
from paddle_tpu.models import GPTConfig, GPTForPretraining, generate
from paddle_tpu.models.generation import make_draft_model
from paddle_tpu.serving import GenerationEngine, PagedKVPool

VOCAB = 96


@pytest.fixture(scope="module")
def served_model():
    """A tiny char GPT trained for a few steps: trained logits have
    clear argmax margins, so greedy parity between the speculative and
    plain programs cannot flake on numeric noise."""
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


@pytest.fixture(scope="module")
def weak_draft(served_model):
    """A 1-layer draft: disagrees with the target often, so the
    rejection/correction path is genuinely exercised."""
    return make_draft_model(served_model, num_layers=1)


def _prompt(rng, n):
    return rng.randint(1, VOCAB, n).astype(np.int32)


# ---------------------------------------------------------------------------
# greedy parity + the multiplier (the acceptance criteria)
# ---------------------------------------------------------------------------

class TestGreedyParity:
    def test_32_mixed_requests_spec_equals_plain_equals_generate(
            self, served_model, weak_draft):
        """The acceptance criterion: 32 mixed-length concurrent greedy
        requests through the SPECULATIVE engine (weak draft — real
        rejections) produce output token-identical to the plain fused
        engine and to per-request ``models.generate`` (EOS early-stop
        included); a second identical wave causes ZERO retraces on the
        warm (q, table) buckets; the verify step analyzes clean."""
        rng = np.random.RandomState(2)
        specs = [(_prompt(rng, int(rng.randint(2, 21))),
                  int(rng.randint(2, 12))) for _ in range(32)]
        refs = [generate(served_model, p[None, :], max_new_tokens=n,
                         eos_token_id=3).numpy()[0] for p, n in specs]

        def run(spec_draft):
            eng = GenerationEngine(
                served_model, num_slots=8, max_len=48,
                block_size=8, spec_draft=spec_draft, spec_k=4, prefill_budget=16)
            hs = [eng.submit(p, max_new_tokens=n, eos_token_id=3)
                  for p, n in specs]
            outs = [h.result(timeout=600) for h in hs]
            return eng, outs

        eng, outs = run(weak_draft)
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        stats = eng.stats()
        assert 0 < stats["spec_accept_rate"] <= 1.0
        assert stats["spec_proposed"] > 0
        report = eng.analyze()
        assert report.ok(), report.table()
        # warm wave: every (q, table) bucket still traced exactly ONCE
        # with no recorded retrace cause — verify rows must not start a
        # retrace storm. (A new bucket FIRST-compiling in the second
        # wave is legal: the concurrent admission interleaving is
        # thread-timing-dependent, so the wave can reach a q bucket the
        # first one never formed.)
        hs = [eng.submit(p, max_new_tokens=n, eos_token_id=3)
              for p, n in specs]
        outs2 = [h.result(timeout=600) for h in hs]
        sites = {k: v for k, v in trace_probe.snapshot().items()
                 if k.endswith(f"#{eng._eid}")}
        eng.close()
        for ref, out in zip(refs, outs2):
            np.testing.assert_array_equal(out, ref)
        retraced = {k: v["traces"] for k, v in sites.items()
                    if v["traces"] != 1 or v["causes"]}
        assert not retraced, f"warm buckets retraced: {retraced}"
        # and the plain fused engine agrees too (no-spec oracle)
        eng2 = GenerationEngine(
            served_model, num_slots=8, max_len=48, block_size=8, prefill_budget=16)
        hs = [eng2.submit(p, max_new_tokens=n, eos_token_id=3)
              for p, n in specs]
        outs3 = [h.result(timeout=600) for h in hs]
        eng2.close()
        for ref, out in zip(refs, outs3):
            np.testing.assert_array_equal(out, ref)

    def test_agreeing_workload_multiplies_tokens_per_cycle(
            self, served_model):
        """Draft == target: every candidate agrees, the accept rate is
        1.0 and a decode slot nets MORE THAN ONE token per cycle
        (spec_tokens_per_cycle > 1) — the multiplier the tentpole
        exists for, through the unchanged one-fetch-per-cycle
        contract."""
        rng = np.random.RandomState(9)
        prompts = [_prompt(rng, n) for n in (5, 9, 14, 3)]
        refs = [generate(served_model, p[None, :],
                         max_new_tokens=10).numpy()[0] for p in prompts]
        eng = GenerationEngine(
            served_model, num_slots=4, max_len=48, block_size=8, spec_draft=served_model,
            spec_k=4, prefill_budget=16)
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        stats = eng.stats()
        eng.close()
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        assert stats["spec_accept_rate"] == 1.0
        assert stats["spec_tokens_per_cycle"] > 1.0
        assert stats["spec_accepted"] == stats["spec_proposed"] > 0

    def test_draft_chain_is_one_dispatch_per_cycle(self, served_model,
                                                   weak_draft):
        """The draft proposal loop is FUSED into one ``lax.scan``
        program (ISSUE-15 satellite): every spec cycle in the flight
        recorder carries exactly ONE draft dispatch where the unrolled
        loop launched spec_k of them — and the fused chain still
        matches ``generate`` token-for-token through a weak draft's
        real rejections."""
        rng = np.random.RandomState(12)
        prompts = [_prompt(rng, n) for n in (4, 8, 13)]
        refs = [generate(served_model, p[None, :],
                         max_new_tokens=10).numpy()[0] for p in prompts]
        eng = GenerationEngine(
            served_model, num_slots=4, max_len=48, block_size=8, spec_draft=weak_draft,
            spec_k=4, prefill_budget=16)
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        cycles = eng.flight_recorder.snapshot()["cycles"]
        eng.close()
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        disp = [c["spec_draft_dispatches"] for c in cycles
                if "spec_draft_dispatches" in c]
        assert disp, "no spec draft dispatches recorded"
        assert all(d == 1 for d in disp), disp

    def test_spec_with_int8_blocks(self, served_model):
        """The two tentpole halves compose: speculative verify over a
        QUANTIZED pool (block_size 32 — the int8 kernel tile floor)
        still matches the fp32 generate() reference on trained
        margins."""
        rng = np.random.RandomState(4)
        prompts = [_prompt(rng, n) for n in (5, 11, 3)]
        refs = [generate(served_model, p[None, :],
                         max_new_tokens=8).numpy()[0] for p in prompts]
        eng = GenerationEngine(
            served_model, num_slots=4, max_len=64, block_size=32, kv_dtype="int8",
            spec_draft=served_model, spec_k=4, prefill_budget=16)
        hs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        stats = eng.stats()
        eng.close()
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        assert stats["kv_dtype"] == "int8"
        assert stats["spec_accept_rate"] == 1.0


# ---------------------------------------------------------------------------
# sampled mode: the rejection-sampling identity
# ---------------------------------------------------------------------------

class TestRejectionSamplingIdentity:
    def test_emitted_distribution_equals_target(self):
        """The distribution-correctness criterion, on the device math
        itself: for ARBITRARY fixed p (target) and q (draft), the first
        token emitted by a speculative cycle — accepted draft OR
        residual correction — is distributed exactly as p[0]. Run
        vectorized over many independent slots so the empirical check
        is cheap."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.generation import (_categorical_probs,
                                                  _spec_accept)
        rng = np.random.RandomState(0)
        V, K, S, ROUNDS = 6, 3, 512, 12
        p1 = rng.dirichlet(np.ones(V)).astype(np.float32)
        q1 = rng.dirichlet(np.ones(V)).astype(np.float32)
        p = np.broadcast_to(
            rng.dirichlet(np.ones(V), size=K).astype(np.float32),
            (S, K, V)).copy()
        p[:, 0] = p1
        q = np.broadcast_to(
            rng.dirichlet(np.ones(V), size=K).astype(np.float32),
            (S, K, V)).copy()
        q[:, 0] = q1
        base = np.broadcast_to(p1, (S, V)).copy()
        n_spec = np.full(S, K, np.int32)
        counts = np.zeros(V)
        key = jax.random.PRNGKey(0)
        for _ in range(ROUNDS):
            key, kd, kv = jax.random.split(key, 3)
            d = np.zeros((S, K), np.int32)
            for j in range(K):
                kd, sub = jax.random.split(kd)
                d[:, j] = np.asarray(
                    _categorical_probs(sub, jnp.asarray(q[:, j])))
            acc, tok = _spec_accept(
                jnp.asarray(p), jnp.asarray(q), jnp.asarray(d),
                jnp.asarray(n_spec), jnp.asarray(base), kv)
            acc, tok = np.asarray(acc), np.asarray(tok)
            first = np.where(acc >= 1, d[:, 0], tok)
            counts += np.bincount(first, minlength=V)
        emp = counts / counts.sum()
        assert np.abs(emp - p1).max() < 0.02, (emp, p1)

    def test_greedy_degenerate_case_is_exact(self):
        """One-hot p/q (the greedy degenerate case): acceptance is
        token equality, the correction is the target argmax, and the
        draw consumes no randomness that could flip it — byte-exact,
        every key."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.generation import _spec_accept
        V, K = 8, 3
        eye = np.eye(V, dtype=np.float32)
        # target argmaxes 1,2,3; draft proposes 1,5,3 -> accept 1,
        # reject at candidate 2, correct to target argmax 2
        p = eye[[1, 2, 3]][None]
        q = eye[[1, 5, 3]][None]
        d = np.array([[1, 5, 3]], np.int32)
        for seed in range(5):
            acc, tok = _spec_accept(
                jnp.asarray(p), jnp.asarray(q), jnp.asarray(d),
                np.array([K], np.int32), jnp.asarray(p[:, 0]),
                jax.random.PRNGKey(seed))
            assert int(acc[0]) == 1
            assert int(tok[0]) == 2
        # full agreement: everything accepted, any key
        acc, tok = _spec_accept(
            jnp.asarray(p), jnp.asarray(p), np.array([[1, 2, 3]],
                                                     np.int32),
            np.array([K], np.int32), jnp.asarray(p[:, 0]),
            jax.random.PRNGKey(7))
        assert int(acc[0]) == K

    def test_sampled_requests_complete_through_spec_engine(
            self, served_model, weak_draft):
        """End-to-end sampled speculative serving: mixed greedy and
        sampled requests share the one verify program, complete at full
        length, and the accept telemetry is live."""
        rng = np.random.RandomState(5)
        prompts = [_prompt(rng, n) for n in (4, 9, 6, 3)]
        eng = GenerationEngine(
            served_model, num_slots=4, max_len=48, block_size=8, spec_draft=weak_draft,
            spec_k=3, prefill_budget=16)
        hs = [eng.submit(p, max_new_tokens=6, do_sample=bool(i % 2),
                         temperature=0.9)
              for i, p in enumerate(prompts)]
        outs = [h.result(timeout=600) for h in hs]
        stats = eng.stats()
        eng.close()
        for p, out in zip(prompts, outs):
            assert out.shape == (p.size + 6,)
        assert stats["spec_proposed"] > 0
        assert 0.0 <= stats["spec_accept_rate"] <= 1.0


# ---------------------------------------------------------------------------
# machinery: rollback bookkeeping, preemption/prefix interplay, validation
# ---------------------------------------------------------------------------

class TestRollbackMachinery:
    def test_signed_advance_and_floor(self):
        """advance() takes a signed delta: rollback unwinds rejected
        rows, zero is rejected, and unwinding below the slot floor (a
        bug, not a rollback) raises."""
        pool = PagedKVPool(num_layers=1, num_slots=2, num_heads=1,
                           max_len=64, head_dim=1, block_size=8)
        slot = pool.alloc()
        pool.admit_fresh(slot, 10)
        pool.set_slot(slot, pos=10, lo=0)
        assert pool.advance(slot, 4) == 14       # candidate rows written
        assert pool.advance(slot, -3) == 11      # 3 rejected, 1 kept
        with pytest.raises(ValueError, match="n != 0"):
            pool.advance(slot, 0)
        with pytest.raises(RuntimeError, match="rollback below"):
            pool.advance(slot, -12)
        with pytest.raises(RuntimeError, match="overran"):
            pool.advance(slot, 64)

    def test_rollback_unpublishes_dirtied_blocks(self):
        """A cached block whose positions a rejected candidate touched
        must leave the prefix cache on rollback — serving a later hit
        off it would replay bytes that no longer match its token key."""
        pool = PagedKVPool(num_layers=1, num_slots=2, num_heads=1,
                           max_len=64, head_dim=1, block_size=8)
        slot = pool.alloc()
        pool.admit_fresh(slot, 16)               # two full blocks
        toks = np.arange(1, 17, dtype=np.int32)
        pool.register_prefix(slot, toks)
        assert pool.cached_blocks == 2
        pool.set_slot(slot, pos=16, lo=0)
        # speculative rows grew into a third block then rolled back to
        # pos 12 INSIDE cached block 1: its registration (and its
        # now-unreachable cached descendants) must drop; block 0, fully
        # below the rollback point, stays served
        pool.ensure_writable_range(slot, 19)
        pool.set_slot(slot, pos=20, lo=0)
        pool.advance(slot, -8)
        pool.unpublish_from(slot, pool.slot_pos(slot))
        assert pool.cached_blocks == 1
        assert pool.match_prefix(toks) == [pool.slot_table(slot)[0]]
        pool.free(slot)

    def test_preemption_and_prefix_cache_interplay(self, served_model):
        """Block pressure mid-speculation: the youngest is preempted
        and replayed, prefix hits adopt shared blocks, and every output
        still matches generate() exactly."""
        rng = np.random.RandomState(6)
        system = (np.arange(1, 17) % (VOCAB - 2) + 1).astype(np.int32)
        prompts = [np.concatenate([system, _prompt(rng, n)])
                   for n in (5, 9, 3, 7)]
        refs = [generate(served_model, p[None, :],
                         max_new_tokens=12).numpy()[0] for p in prompts]
        eng = GenerationEngine(
            served_model, num_slots=3, max_len=64, block_size=8, num_blocks=12, spec_draft=served_model, spec_k=4, prefill_budget=16)
        hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        stats = eng.stats()
        eng.close()
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        assert stats["prefix_hits"] > 0
        assert eng._pool.blocks_in_use == 0

    def test_draft_model_shares_embeddings_and_truncates(
            self, served_model):
        draft = make_draft_model(served_model, num_layers=1)
        assert draft.wte is served_model.gpt.wte       # SAME Layer
        assert draft.wpe is served_model.gpt.wpe
        assert draft.cfg.num_hidden_layers == 1
        assert len(draft.blocks) == 1
        # block 0 initialized FROM the target's block 0
        a = dict(draft.blocks[0].named_parameters())
        b = dict(served_model.gpt.blocks[0].named_parameters())
        for name in a:
            np.testing.assert_array_equal(a[name].numpy(),
                                          b[name].numpy())
        with pytest.raises(ValueError, match="num_layers"):
            make_draft_model(served_model, num_layers=9)

    def test_min_bucket_floors_the_drafts_prefill_ladder(self,
                                                        served_model):
        """What is left of the bucket ladder: the draft's context sync
        is a bucketed prefill, pow2 from ``min_bucket`` to ``max_len``."""
        eng = GenerationEngine(served_model, max_len=48, block_size=8,
                               spec_draft=served_model, min_bucket=16)
        try:
            assert [eng._draft_bucket(n) for n in (1, 16, 17, 33, 47)] \
                == [16, 16, 32, 48, 48]
        finally:
            eng.close()
        with pytest.raises(ValueError, match="min_bucket"):
            GenerationEngine(served_model, max_len=48, min_bucket=0)

    def test_construction_validation(self, served_model):
        with pytest.raises(ValueError, match="spec_k"):
            GenerationEngine(served_model, block_size=8,
                             max_len=48, spec_draft=served_model,
                             spec_k=0)
        with pytest.raises(ValueError, match="block_size 8 < 32"):
            GenerationEngine(served_model, block_size=8,
                             max_len=48, kv_dtype="int8")
        # draft vocab mismatch
        other = GPTForPretraining(GPTConfig(
            vocab_size=32, hidden_size=32, num_hidden_layers=1,
            num_attention_heads=2, intermediate_size=64,
            max_position_embeddings=64))
        with pytest.raises(ValueError, match="vocab"):
            GenerationEngine(served_model, block_size=8,
                             max_len=48, spec_draft=other)
