"""Speculative decoding with the target as its own draft, and the
machinery under it (the second half of ``tests/test_spec_decode.py``, whose
module doc lists the guarantees; a file of its own since PR 45 because a
file is what the suite's workers are handed):

* **the multiplier** — draft == target: every candidate agrees, the accept
  rate is 1.0 and a decode slot nets more than one token a cycle; the same
  over int8 blocks;
* **machinery** — signed ``advance`` rollback bookkeeping, cache
  un-publishing on rollback, preemption/prefix-cache interplay, and
  fail-fast construction validation.
"""
import numpy as np
import pytest

from paddle_tpu.models import GPTConfig, GPTForPretraining, generate
from paddle_tpu.models.generation import make_draft_model
from paddle_tpu.serving import GenerationEngine, PagedKVPool

import _toys

VOCAB = _toys.VOCAB

# the engine two tests share, with the target as its own draft (``engines``
# hands it out drained, its pool as new): three slots on twelve blocks of
# 8, so that three contexts of 33 tokens and more do not fit and the
# youngest is preempted
SELF = dict(num_slots=3, max_len=64, block_size=8, num_blocks=12, spec_k=4,
            prefill_budget=16)


def _prompt(rng, n):
    return rng.randint(1, VOCAB, n).astype(np.int32)


# ---------------------------------------------------------------------------
# the multiplier
# ---------------------------------------------------------------------------

class TestSelfDraft:
    def test_agreeing_workload_multiplies_tokens_per_cycle(
            self, served_model, engines):
        """Draft == target: every candidate agrees, the accept rate is
        1.0 and a decode slot nets MORE THAN ONE token per cycle
        (spec_tokens_per_cycle > 1) — the multiplier the tentpole
        exists for, through the unchanged one-fetch-per-cycle
        contract."""
        rng = np.random.RandomState(9)
        prompts = [_prompt(rng, n) for n in (5, 9, 14, 3)]
        refs = [generate(served_model, p[None, :],
                         max_new_tokens=10).numpy()[0] for p in prompts]
        eng = engines(served_model, spec_draft=served_model, **SELF)
        proposed = eng.stats()["spec_proposed"]
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        stats = eng.stats()
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        # every request this engine ever serves has the target as draft
        assert stats["spec_accept_rate"] == 1.0
        assert stats["spec_tokens_per_cycle"] > 1.0
        assert stats["spec_accepted"] == stats["spec_proposed"] > proposed

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

    def test_preemption_and_prefix_cache_interplay(self, served_model,
                                                   engines):
        """Block pressure mid-speculation: the youngest is preempted
        and replayed, prefix hits adopt shared blocks, and every output
        still matches generate() exactly."""
        rng = np.random.RandomState(6)
        system = (np.arange(1, 17) % (VOCAB - 2) + 1).astype(np.int32)
        prompts = [np.concatenate([system, _prompt(rng, n)])
                   for n in (5, 9, 3, 7)]
        refs = [generate(served_model, p[None, :],
                         max_new_tokens=12).numpy()[0] for p in prompts]
        eng = engines(served_model, spec_draft=served_model, **SELF)
        hits = eng.stats()["prefix_hits"]
        hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        _toys.settle(eng)
        stats = eng.stats()
        for ref, out in zip(refs, outs):
            np.testing.assert_array_equal(out, ref)
        assert stats["prefix_hits"] > hits
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
