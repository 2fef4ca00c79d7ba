"""Speculative decoding on the fused ragged serving step
(GenerationEngine(spec_draft=..., spec_k=...)).

Four layers of guarantees:

* **greedy parity** — speculative output is TOKEN-IDENTICAL to the
  non-speculative fused engine and to per-request ``models.generate``,
  for 16 mixed concurrent requests, with zero retraces on warm
  (q, table) buckets and a clean ``analyze()`` bill — regardless of how
  bad the draft is (rejection + correction IS the guarantee; the draft
  only moves the accept rate);
* **the multiplier** — on an agreeing workload (draft == target)
  ``spec_tokens_per_cycle > 1`` and the accept rate is 1.0: more than
  one token per decode cycle through the existing one-fetch contract
  (``tests/test_spec_decode_self_draft.py``, with the machinery: a file is
  what the suite's workers are handed, and the engines whose draft is the
  target itself are that file's);
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

from paddle_tpu.framework import trace_probe
from paddle_tpu.models import generate
from paddle_tpu.models.generation import make_draft_model
from paddle_tpu.serving import GenerationEngine

import _toys

VOCAB = _toys.VOCAB

# the engine two tests share, under a weak draft (``engines`` hands it out
# drained, its pool as new): four slots, four candidates a cycle
WEAK = dict(num_slots=4, max_len=48, block_size=8, spec_k=4,
            prefill_budget=16)


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
        """The acceptance criterion: 16 mixed-length concurrent greedy
        requests through three slots (32 through eight until PR 45, with
        contexts of up to 31 tokens; a storm's cost here is its step
        programs — 3 to 9 s each to build, tens of ms to run — so contexts
        stay within 18 tokens and a launch within 32 rows: a chunk of 16
        beside two slots' candidates; the queue is five deep behind the
        slots, and a slot's verify rows are its candidates', whatever the
        queue) through the
        SPECULATIVE engine (weak draft — real rejections) produce output
        token-identical to the plain fused engine and to per-request
        ``models.generate`` (EOS early-stop included); a second identical
        wave causes ZERO retraces on the warm (q, table) buckets; the
        verify step analyzes clean."""
        rng = np.random.RandomState(2)
        specs = [(_prompt(rng, int(rng.randint(2, 13))),
                  int(rng.randint(2, 7))) for _ in range(16)]
        # greedy text is a prefix of longer greedy text: one ``generate``
        # program a prompt length, not one a (length, n) pair
        refs = [generate(served_model, p[None, :], max_new_tokens=6,
                         eos_token_id=3).numpy()[0][:p.size + n]
                for p, n in specs]

        def run(spec_draft):
            eng = GenerationEngine(
                served_model, num_slots=3, max_len=48, block_size=8,
                spec_draft=spec_draft, spec_k=4, prefill_budget=16)
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
        eng2 = GenerationEngine(served_model, num_slots=3, max_len=48,
                                block_size=8, prefill_budget=16)
        hs = [eng2.submit(p, max_new_tokens=n, eos_token_id=3)
              for p, n in specs]
        outs3 = [h.result(timeout=600) for h in hs]
        eng2.close()
        for ref, out in zip(refs, outs3):
            np.testing.assert_array_equal(out, ref)

    def test_draft_chain_is_one_dispatch_per_cycle(self, served_model,
                                                   weak_draft, engines):
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
        eng = engines(served_model, spec_draft=weak_draft, **WEAK)
        since = eng._sched._cycle
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        _toys.settle(eng)
        cycles = eng.flight_recorder.snapshot()["cycles"]
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        disp = [c["spec_draft_dispatches"] for c in cycles
                if "spec_draft_dispatches" in c and c["cycle"] > since]
        assert disp, "no spec draft dispatches recorded"
        assert all(d == 1 for d in disp), disp


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
            self, served_model, weak_draft, engines):
        """End-to-end sampled speculative serving: mixed greedy and
        sampled requests share the one verify program, complete at full
        length, and the accept telemetry is live."""
        rng = np.random.RandomState(5)
        prompts = [_prompt(rng, n) for n in (4, 9, 6, 3)]
        eng = engines(served_model, spec_draft=weak_draft, **WEAK)
        proposed = eng.stats()["spec_proposed"]
        hs = [eng.submit(p, max_new_tokens=6, do_sample=bool(i % 2),
                         temperature=0.9)
              for i, p in enumerate(prompts)]
        outs = [h.result(timeout=600) for h in hs]
        stats = eng.stats()
        for p, out in zip(prompts, outs):
            assert out.shape == (p.size + 6,)
        assert stats["spec_proposed"] > proposed
        assert 0.0 <= stats["spec_accept_rate"] <= 1.0
