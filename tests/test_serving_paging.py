"""Paged KV-cache memory manager + prefix cache (paddle_tpu/serving/paging.py).

Four layers of guarantees:

* **parity** — greedy PAGED engine output is token-identical to the
  dense-slot engine AND to per-request ``models.generate``, for >= 32
  mixed concurrent requests, with zero retraces during the churn and a
  clean ``analyze()`` bill on the paged decode step (the acceptance
  criterion);
* **capacity** — a same-device-budget paged pool admits strictly more
  concurrent mixed-length requests than the dense pool (the point of
  paging);
* **memory manager** — free-list/refcount/copy-on-write bookkeeping,
  the prefix-cache trie with LRU eviction, and fail-fast named errors
  on misuse (double free, zero-length prompt, impossible admission)
  that never corrupt the free list;
* **policy** — prefix-cache hits skip prefill (tokens saved, outputs
  unchanged) and block pressure preempts the youngest request
  (requeued + replayed, never deadlocked), still token-exact.
"""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import monitor, trace_probe
from paddle_tpu.models import GPTConfig, GPTForPretraining, generate
from paddle_tpu.serving import (BlockError, GenerationEngine, KVCachePool,
                                PagedKVPool, PoolCapacityError,
                                PoolExhaustedError)

VOCAB = 96


@pytest.fixture(scope="module")
def served_model():
    """A tiny char GPT trained for a few steps: trained logits have
    clear argmax margins, so greedy parity between the paged (gathered,
    right-padded) and dense (left-padded) attention programs cannot
    flake on numeric noise."""
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


def _paged_pool(**kw):
    kw.setdefault("num_layers", 1)
    kw.setdefault("num_slots", 4)
    kw.setdefault("num_heads", 1)
    kw.setdefault("max_len", 64)
    kw.setdefault("head_dim", 1)
    kw.setdefault("block_size", 8)
    return PagedKVPool(**kw)


def _check_free_list(pool):
    """The bookkeeping invariant every misuse test re-asserts: each
    physical block is in EXACTLY one of {free list, referenced,
    released-but-cached (LRU)} — a corrupt free list double-counts or
    loses one."""
    free = set(pool._free)
    assert len(free) == len(pool._free), "free list holds duplicates"
    referenced = {b for b, rc in pool._ref.items() if rc > 0}
    lru = {n.block for n in pool._lru.values()}
    assert not free & referenced
    assert not free & lru
    assert not referenced & lru
    assert len(free) + len(referenced) + len(lru) == pool.num_blocks
    assert 0 not in free | referenced | lru   # scratch is never managed


# ---------------------------------------------------------------------------
# parity + compile discipline + analyze (the real paged engine)
# ---------------------------------------------------------------------------

class TestPagedParity:
    def test_single_request_matches_generate(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=48,
                               kv_layout="paged", block_size=8)
        p = _prompt(np.random.RandomState(1), 7)
        out = eng.submit(p, max_new_tokens=8).result(timeout=300)
        ref = generate(served_model, p[None, :], max_new_tokens=8)
        np.testing.assert_array_equal(out, ref.numpy()[0])
        eng.close()

    def test_32_mixed_requests_paged_equals_dense_equals_generate(
            self, served_model):
        """The acceptance criterion: the same 32 mixed-length concurrent
        greedy requests through the dense-slot engine and the paged
        engine produce token-identical output, each also matching a
        per-request ``models.generate`` reference; the storm causes
        ZERO retraces on the paged engine (one trace per prefill bucket
        and per pow2 table bucket) and its decode step analyzes clean."""
        rng = np.random.RandomState(2)
        specs = [(_prompt(rng, int(rng.randint(2, 21))),
                  int(rng.randint(1, 9))) for _ in range(32)]

        def storm(eng):
            outs = [None] * len(specs)

            def client(i):
                p, n = specs[i]
                outs[i] = eng.submit(p, max_new_tokens=n)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(specs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return [h.result(timeout=600) for h in outs]

        dense = GenerationEngine(served_model, num_slots=8, max_len=48,
                                 min_bucket=8)
        dense_outs = storm(dense)
        dense.close()

        eng = GenerationEngine(served_model, num_slots=8, max_len=48,
                               min_bucket=8, kv_layout="paged",
                               block_size=8)
        # warm every prefill bucket (8/16/32) and every pow2 table
        # bucket the storm can reach (1, 2 and 4 blocks: max feed is
        # 20 + 8 = 28 tokens = 4 blocks), then assert the storm itself
        # traces NOTHING
        eng.submit(_prompt(rng, 4), max_new_tokens=2).result(timeout=300)
        eng.submit(_prompt(rng, 9), max_new_tokens=2).result(timeout=300)
        eng.submit(_prompt(rng, 20), max_new_tokens=8).result(timeout=300)
        retrace0 = monitor.stat_get("dispatch/retrace_cause")
        paged_outs = storm(eng)
        retrace_after_storm = monitor.stat_get("dispatch/retrace_cause")
        report = eng.analyze()
        stats = eng.stats()
        eng.close()

        for (p, n), dout, pout in zip(specs, dense_outs, paged_outs):
            np.testing.assert_array_equal(pout, dout)
            ref = generate(served_model, p[None, :], max_new_tokens=n)
            np.testing.assert_array_equal(pout, ref.numpy()[0])
        assert retrace_after_storm == retrace0
        sites = {k: v for k, v in trace_probe.snapshot().items()
                 if k.startswith("serving/") and f"#{eng._eid}" in k}
        assert sites, "paged serving probe sites missing"
        for name, rec in sites.items():
            assert rec["traces"] == 1, (name, rec)
            assert not rec["causes"], (name, rec)
        # the clean bill: donation-safe, host-sync-free paged decode
        assert report.ok(), report.table()
        assert "donation-safety" in report.passes_run
        assert "host-sync" in report.passes_run
        # every request retired, no block leaked
        assert stats["active_requests"] == 0
        assert stats["kv_blocks_in_use"] == 0

    def test_eos_early_stop_matches_generate(self, served_model):
        p = _prompt(np.random.RandomState(3), 6)
        ref8 = generate(served_model, p[None, :], max_new_tokens=8)
        eos = int(ref8.numpy()[0, 6 + 2])
        ref = generate(served_model, p[None, :], max_new_tokens=8,
                       eos_token_id=eos, pad_token_id=0)
        eng = GenerationEngine(served_model, num_slots=2, max_len=48,
                               kv_layout="paged", block_size=8)
        out = eng.submit(p, max_new_tokens=8, eos_token_id=eos) \
                 .result(timeout=300)
        eng.close()
        np.testing.assert_array_equal(out, ref.numpy()[0])


# ---------------------------------------------------------------------------
# the capacity unlock: same device budget, strictly more admissions
# ---------------------------------------------------------------------------

class TestCapacityWin:
    def test_same_budget_paged_admits_strictly_more(self):
        """The acceptance criterion's capacity clause. Dense reserves a
        worst-case ``max_len`` stripe per request, so a 4 x 64-token
        budget admits exactly 4 requests of ANY length. The same 256
        KV-token budget cut into 32 x 8-token blocks admits one request
        per block-rounded FOOTPRINT — 16 eight-token requests here."""
        dense = KVCachePool(num_layers=1, num_slots=4, num_heads=1,
                            max_len=64, head_dim=1, min_bucket=8)
        paged = _paged_pool(num_slots=16, num_blocks=32)
        # identical device KV budget (paged adds only the one reserved
        # scratch block on top)
        assert paged.num_blocks * paged.block_size \
            == dense.num_slots * dense.max_len
        need = 8                      # prompt 5 + max_new 3, one block

        dense_admitted = 0
        while dense.bucket_for(need) + 0 <= dense.max_len:
            if dense.alloc() is None:
                break
            dense_admitted += 1
        paged_admitted = 0
        while paged.can_admit(need):
            slot = paged.alloc()
            if slot is None:
                break
            paged.admit_fresh(slot, need)
            paged_admitted += 1
        assert dense_admitted == 4
        assert paged_admitted == 16
        assert paged_admitted > dense_admitted
        _check_free_list(paged)


# ---------------------------------------------------------------------------
# quantized KV blocks: int8 storage + per-block max-abs scales
# ---------------------------------------------------------------------------

class TestQuantizedBlocks:
    def test_same_budget_int8_admits_2x_vs_fp32(self):
        """The tentpole capacity clause: at the SAME device byte budget
        (block storage + scale overhead included) an int8 pool admits
        at least 2x the concurrent requests of the fp32 paged pool —
        int8 blocks are 4x smaller, minus the f32 per-block-per-head
        scale array."""
        fp = _paged_pool(num_slots=64, num_blocks=16)
        budget = fp.capacity_bytes
        q_blocks = PagedKVPool.blocks_within_budget(
            budget, num_layers=fp.num_layers, num_heads=fp.num_heads,
            block_size=fp.block_size, head_dim=fp.head_dim,
            dtype="int8")
        q = _paged_pool(num_slots=64, num_blocks=q_blocks, dtype="int8")
        assert q.capacity_bytes <= budget       # honest accounting
        need = 8                                # one block per request

        def admitted(pool):
            n = 0
            while pool.can_admit(need):
                slot = pool.alloc()
                if slot is None:
                    break
                pool.admit_fresh(slot, need)
                n += 1
            return n

        n_fp, n_q = admitted(fp), admitted(q)
        assert n_q >= 2 * n_fp, (n_fp, n_q)
        _check_free_list(q)

    def test_quant_roundtrip_error_is_bounded(self):
        """The per-block max-abs scheme's unit bound: |dequant(quant(x))
        - x| <= scale/2 per element, scale = blockwise max|x|/127."""
        import jax.numpy as jnp

        from paddle_tpu.models.generation import (_gather_kv,
                                                  _quant_write_blocks)
        rng = np.random.RandomState(0)
        # K and V planes of three blocks, [2, Tp, H, bs, Dh]; V is
        # scaled apart from K so a swapped scale plane would show
        vals = rng.randn(2, 3, 2, 8, 4).astype(np.float32) * 2.0
        vals[1] *= 5.0
        pool = jnp.zeros((1, 5, 2, 8, 8), jnp.int8)    # [L,NB+1,H,bs,2Dh]
        scales = jnp.zeros((1, 2, 5, 2), jnp.float32)
        table = np.array([1, 2, 3], np.int32)
        pool, scales = _quant_write_blocks(
            pool, scales, 0, table, jnp.asarray(vals[0]),
            jnp.asarray(vals[1]), 127.0)
        # [1, Tp*bs, H, Dh] back to per-block [Tp, H, bs, Dh]
        deq = np.stack([
            np.asarray(g)[0].reshape(3, 8, 2, 4).transpose(0, 2, 1, 3)
            for g in _gather_kv(pool, scales, 0, table[None, :])])
        bound = np.abs(vals).max(axis=(3, 4), keepdims=True) / 127.0
        assert (np.abs(deq - vals) <= bound * 0.5001 + 1e-7).all()

    def test_recycled_block_scale_is_reset(self):
        """A freed block returning through the allocator must NOT keep
        its previous tenant's max-abs scale: ``_quant_append`` only
        GROWS scales (scatter-max), so a stale coarse scale would
        quantize the next tenant's growth appends to near-zero ints —
        the 'bounded drift' contract silently broken by block churn."""
        import jax.numpy as jnp

        from paddle_tpu.models.generation import _quant_write_blocks
        pool = _paged_pool(num_slots=2, num_blocks=2, max_len=16,
                           min_bucket=8, dtype="int8")
        a = pool.alloc()
        blocks = pool.admit_fresh(a, 16)          # takes both blocks
        vals = jnp.full((2, 1, 8, 1), 100.0)
        pool.data, pool.scales = _quant_write_blocks(
            pool.data, pool.scales, 0, np.asarray(blocks, np.int32),
            vals, vals, 127.0)
        assert np.asarray(pool.scales)[0, 0, blocks[1]] > 0.5
        pool.free(a)                              # blocks recycled
        b = pool.alloc()
        pool.admit_fresh(b, 8)
        pool.set_slot(b, pos=8, lo=0)
        pool.ensure_writable(b)                   # growth re-allocates
        grown = pool.slot_table(b)[1]
        assert float(np.asarray(pool.scales)[0, 0, grown]) == 0.0

    def test_int8_logit_drift_bounded_vs_fp32(self, served_model):
        """Identical prompt, identical decode step, fp32 vs int8 pool:
        the per-step LOGIT drift stays small relative to the logit
        scale — the bounded-drift half of the capacity win (token
        parity on trained margins is the other half, asserted by the
        parametrized engine tests)."""
        import jax

        from paddle_tpu.models.generation import (build_paged_decode_fn,
                                                  build_paged_prefill_fn)
        from paddle_tpu.nn.layer.layers import (get_buffers_tree,
                                                get_params_tree)
        model = served_model
        params = get_params_tree(model)
        buffers = get_buffers_tree(model)
        rng = np.random.RandomState(3)
        prompt = _prompt(rng, 13)
        bucket, bs, T = 16, 8, 2
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :prompt.size] = prompt
        kv = np.zeros((1, bucket), bool)
        kv[0, :prompt.size] = True
        table = np.array([1, 2], np.int32)
        key = jax.random.PRNGKey(0)
        logits = {}
        for dtype in ("float32", "int8"):
            pool = _paged_pool(num_slots=1, num_blocks=8, num_heads=4,
                               head_dim=16, num_layers=2, dtype=dtype)
            quant = pool.quantized
            pre = build_paged_prefill_fn(model, bucket, bs,
                                         quantized=quant)
            dec = build_paged_decode_fn(model, 1, T, bs, quantized=quant,
                                        debug_logits=True)
            sc = (pool.scales,) if quant else ()
            out = pre(params, buffers, pool.data, *sc, ids, kv, table,
                      np.int32(prompt.size), np.bool_(False),
                      np.float32(1.0), key)
            data, scales = out[0], (out[1] if quant else None)
            first = int(np.asarray(out[-2])[0])
            sc = (scales,) if quant else ()
            out = dec(params, buffers, data, *sc,
                      np.asarray([first], np.int32),
                      np.asarray([prompt.size], np.int32),
                      np.zeros(1, np.int32), table[None, :],
                      np.zeros(1, bool), np.ones(1, np.float32), key)
            logits[dtype] = np.asarray(out[-2])[0]
        scale = np.abs(logits["float32"]).max()
        drift = np.abs(logits["int8"] - logits["float32"]).max()
        assert drift < 0.05 * max(scale, 1.0), (drift, scale)
        # and the drift is small enough that the trained argmax holds
        assert logits["int8"].argmax() == logits["float32"].argmax()

    def test_nonfinite_sentinel_trips_through_quantized_pool(self):
        """The PR-9 serving logits-finite sentinel must survive int8
        storage: a NaN row drives its block's SCALE nonfinite (the
        EQuARX rule — int8 * NaN re-materializes the corruption instead
        of silently rounding it away), the logits go nonfinite, the
        sentinel rides the one-per-cycle fetch, and the loop SURVIVES."""
        import jax.numpy as jnp
        paddle.seed(0)
        poisoned = GPTForPretraining(GPTConfig.tiny())
        poisoned.eval()
        p = poisoned.parameters()[0]
        p._data = jnp.full(p.shape, jnp.nan, p._data.dtype)
        eng = GenerationEngine(poisoned, num_slots=2, max_len=32,
                               kv_layout="paged", block_size=8,
                               kv_dtype="int8")
        out = eng.submit(np.arange(1, 6, dtype=np.int32),
                         max_new_tokens=4).result(timeout=300)
        stats = eng.stats()
        eng.close()
        assert out.shape == (9,)        # the loop served, not crashed
        assert stats["nonfinite_cycles"] > 0


# ---------------------------------------------------------------------------
# the memory manager: free list, refcounts, COW, misuse fail-fast
# ---------------------------------------------------------------------------

class TestBlockBookkeeping:
    def test_double_free_of_slot_is_named_and_harmless(self):
        pool = _paged_pool()
        slot = pool.alloc()
        pool.admit_fresh(slot, 10)
        pool.free(slot)
        before = list(pool._free)
        with pytest.raises(ValueError, match="not allocated"):
            pool.free(slot)
        assert pool._free == before   # nothing double-returned
        _check_free_list(pool)

    def test_double_free_of_block_is_named_and_harmless(self):
        pool = _paged_pool()
        slot = pool.alloc()
        (block,) = pool.admit_fresh(slot, 4)
        pool.free(slot)               # refcount 1 -> 0, block -> free list
        before = list(pool._free)
        with pytest.raises(BlockError, match="not referenced"):
            pool._unref(block)
        assert pool._free == before
        _check_free_list(pool)

    def test_admit_fresh_rolls_back_on_exhaustion(self):
        pool = _paged_pool(num_slots=4, max_len=32, num_blocks=4)
        a = pool.alloc()
        pool.admit_fresh(a, 24)       # 3 of 4 blocks
        b = pool.alloc()
        with pytest.raises(PoolExhaustedError):
            pool.admit_fresh(b, 17)   # needs 3, only 1 left
        # all-or-nothing: the partial grab was returned
        assert pool.blocks_available == 1
        assert pool.slot_table(b) == []
        _check_free_list(pool)

    def test_growth_and_virtual_capacity_guard(self):
        pool = _paged_pool(num_slots=1, max_len=16, num_blocks=2)
        slot = pool.alloc()
        pool.admit_fresh(slot, 4)
        pool.set_slot(slot, pos=4, lo=0)
        for _ in range(4, 15):
            pool.ensure_writable(slot)
            pool.advance(slot)
        assert len(pool.slot_table(slot)) == 2
        with pytest.raises(RuntimeError, match="virtual capacity"):
            pool.ensure_writable(slot)
            pool.advance(slot)

    def test_copy_on_write_hands_out_a_private_block(self):
        """A block reachable from two page tables is never written
        through: ensure_writable on the sharer returns a (dst, src)
        device-copy order and swaps its table entry."""
        pool = _paged_pool()
        toks = list(range(40, 56))    # two full blocks
        a = pool.alloc()
        pool.admit_fresh(a, len(toks))
        pool.set_slot(a, pos=len(toks), lo=0)
        pool.register_prefix(a, toks)
        b = pool.alloc()
        shared = pool.match_prefix(toks + [1])
        assert shared == pool.slot_table(a)   # both full blocks match
        pool.admit_cached(b, shared)
        # force b's write position INSIDE the shared block (the normal
        # flow writes strictly past it; COW is the guard rail)
        pool.set_slot(b, pos=3, lo=0)
        cow = pool.ensure_writable(b)
        assert cow is not None
        dst, src = cow
        assert src == shared[0]
        assert dst != src
        assert pool.slot_table(b)[0] == dst
        assert pool.slot_table(a)[0] == src   # owner untouched
        pool.free(a)
        pool.free(b)
        _check_free_list(pool)

    def test_writable_appends_need_no_copy(self):
        pool = _paged_pool()
        slot = pool.alloc()
        pool.admit_fresh(slot, 8)
        pool.set_slot(slot, pos=8, lo=0)
        assert pool.ensure_writable(slot) is None   # fresh block appended
        assert len(pool.slot_table(slot)) == 2


class TestPrefixCache:
    def test_match_requires_a_proper_prefix(self):
        """Reuse is capped at (len - 1) // block_size full blocks: at
        least one token always recomputes (its forward pass produces
        the next-token logits), which also keeps every write strictly
        past the shared region."""
        pool = _paged_pool()
        toks = list(range(1, 17))     # two full blocks
        slot = pool.alloc()
        pool.admit_fresh(slot, 16)
        pool.register_prefix(slot, toks)
        assert pool.match_prefix(toks) == pool.slot_table(slot)[:1]
        assert pool.match_prefix(toks + [9]) == pool.slot_table(slot)
        assert pool.match_prefix(toks[:8]) == []      # no proper prefix
        assert pool.match_prefix(toks[:4]) == []      # below one block
        assert pool.match_prefix([7] + toks) == []    # different prefix

    def test_released_blocks_serve_hits_until_evicted(self):
        pool = _paged_pool(num_slots=4, max_len=32, num_blocks=4)
        toks = list(range(1, 17))
        a = pool.alloc()
        pool.admit_fresh(a, 16)
        pool.register_prefix(a, toks)
        pool.free(a)                  # blocks -> LRU, still matchable
        assert pool.blocks_available == 4
        assert pool.cached_blocks == 2
        hit = pool.match_prefix(toks + [1, 2])
        assert len(hit) == 2
        b = pool.alloc()
        pool.admit_cached(b, hit)     # re-referenced: leaves the LRU
        assert pool.prefix_hits == 1
        assert pool.tokens_saved == 16
        pool.free(b)
        _check_free_list(pool)

    def test_lru_eviction_drops_the_subtree(self):
        """Allocation pressure evicts the least-recently-released
        cached chain; its descendants become unreachable and are
        dropped with it, so the trie never dangles."""
        pool = _paged_pool(num_slots=4, max_len=32, num_blocks=4)
        toks = list(range(1, 17))
        a = pool.alloc()
        pool.admit_fresh(a, 16)       # 2 blocks
        pool.register_prefix(a, toks)
        pool.free(a)
        evict0 = monitor.stat_get("serving/prefix_evict")
        b = pool.alloc()
        got = pool.admit_fresh(b, 32)         # needs all 4 blocks
        assert len(got) == 4
        assert monitor.stat_get("serving/prefix_evict") > evict0
        assert pool.cached_blocks == 0        # parent AND child dropped
        assert pool.match_prefix(toks + [1]) == []
        pool.free(b)
        _check_free_list(pool)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_engine_prefix_hit_skips_prefill_and_stays_exact(
            self, served_model, kv_dtype):
        """Requests sharing a two-block system prompt: the first
        computes it, the rest adopt its cached blocks — prefill is
        skipped entirely (the tail replays through the decode step),
        tokens are saved, and the output still matches generate.
        Parametrized over int8 blocks: prefix caching rides on
        quantized storage unchanged (scales travel with the block
        ids)."""
        eng = GenerationEngine(served_model, num_slots=4, max_len=64,
                               kv_layout="paged", block_size=8,
                               kv_dtype=kv_dtype)
        rng = np.random.RandomState(5)
        system = _prompt(rng, 16)     # exactly two full blocks
        tails = [_prompt(rng, n) for n in (3, 1, 6)]
        first = eng.submit(np.concatenate([system, tails[0]]),
                           max_new_tokens=4).result(timeout=300)
        assert eng._pool.prefix_hits == 0
        outs = [eng.submit(np.concatenate([system, t]),
                           max_new_tokens=4).result(timeout=300)
                for t in tails[1:]]
        stats = eng.stats()
        eng.close()
        assert eng._pool.prefix_hits == 2
        assert eng._pool.tokens_saved == 2 * 16
        assert stats["prefix_hit_ratio"] > 0
        assert stats["prefill_tokens_saved"] == 32
        for t, out in zip([tails[0]] + tails[1:],
                          [first] + outs):
            p = np.concatenate([system, t])
            ref = generate(served_model, p[None, :], max_new_tokens=4)
            np.testing.assert_array_equal(out, ref.numpy()[0])

    def test_long_tail_declines_the_hit_and_prefills(self, served_model):
        """Replay costs one decode cycle per tail token, so a cached
        prefix with a LONG uncovered tail (> min_bucket) is served by a
        fresh prefill, not a token-by-token replay — the TTFT cliff the
        unconditional hit would reintroduce. Output stays exact either
        way."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=64,
                               kv_layout="paged", block_size=8)
        rng = np.random.RandomState(8)
        system = _prompt(rng, 16)     # two full cached blocks
        eng.submit(system, max_new_tokens=2).result(timeout=300)
        assert eng._pool.prefix_hits == 0
        # 24-token tail > min_bucket=8: the cached blocks are declined
        long = np.concatenate([system, _prompt(rng, 24)])
        out_long = eng.submit(long, max_new_tokens=4).result(timeout=300)
        assert eng._pool.prefix_hits == 0
        assert eng._pool.prefix_misses == 2
        # 4-token tail still takes the hit
        short = np.concatenate([system, _prompt(rng, 4)])
        out_short = eng.submit(short, max_new_tokens=4).result(timeout=300)
        assert eng._pool.prefix_hits == 1
        eng.close()
        for p, out in ((long, out_long), (short, out_short)):
            ref = generate(served_model, p[None, :], max_new_tokens=4)
            np.testing.assert_array_equal(out, ref.numpy()[0])


# ---------------------------------------------------------------------------
# scheduler policy under block pressure: preemption, not deadlock
# ---------------------------------------------------------------------------

class TestPreemption:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_block_pressure_preempts_youngest_and_both_finish_exact(
            self, served_model, kv_dtype):
        """Two long requests whose combined growth exceeds the block
        budget: the YOUNGEST is preempted (blocks freed, request
        requeued, history replayed on re-admission) instead of
        deadlocking — and both still produce the exact generate()
        sequence. Parametrized over int8 blocks: preemption/replay
        rides on quantized storage unchanged."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=32,
                               kv_layout="paged", block_size=8,
                               num_blocks=4,    # half the dense budget
                               kv_dtype=kv_dtype)
        pa = _prompt(np.random.RandomState(6), 4)
        pb = _prompt(np.random.RandomState(7), 4)
        ha = eng.submit(pa, max_new_tokens=24)
        hb = eng.submit(pb, max_new_tokens=24)
        oa = ha.result(timeout=600)
        ob = hb.result(timeout=600)
        stats = eng.stats()
        eng.close()
        assert stats["preempts"] >= 1
        ra = generate(served_model, pa[None, :], max_new_tokens=24)
        rb = generate(served_model, pb[None, :], max_new_tokens=24)
        np.testing.assert_array_equal(oa, ra.numpy()[0])
        np.testing.assert_array_equal(ob, rb.numpy()[0])
        assert eng._pool.blocks_in_use == 0
        _check_free_list(eng._pool)


# ---------------------------------------------------------------------------
# submit-time validation (fail fast, named errors) + stats()
# ---------------------------------------------------------------------------

class TestValidationAndStats:
    def test_zero_length_prompt_rejected(self, served_model):
        eng = GenerationEngine(served_model, num_slots=1, max_len=32,
                               kv_layout="paged", block_size=8)
        with pytest.raises(ValueError, match="at least one"):
            eng.submit(np.zeros(0, np.int32))
        eng.close()

    def test_max_new_tokens_alone_exceeding_capacity_rejected(
            self, served_model):
        eng = GenerationEngine(served_model, num_slots=1, max_len=32,
                               kv_layout="paged", block_size=8)
        with pytest.raises(PoolCapacityError, match="virtual capacity"):
            eng.submit(np.ones(1, np.int32), max_new_tokens=32)
        # the paged bound is the TRUE footprint: the same prompt fits
        # with max_new 31 (a dense engine would already charge the
        # 8-token bucket here)
        out = eng.submit(np.ones(1, np.int32), max_new_tokens=31) \
                 .result(timeout=300)
        assert out.shape == (32,)
        eng.close()

    def test_infeasible_prefill_bucket_rejected_at_submit(
            self, served_model):
        """A bucket ladder that overshoots max_len (non-pow2 max_len):
        a request whose prefill bucket — including the worst
        re-admission feed after a preemption — could never trace is a
        named submit-time error, NOT a scheduler-thread crash that
        poisons every in-flight request."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=48,
                               kv_layout="paged", block_size=8)
        # footprint 34 <= 48 but bucket_for(33) = 64 > 48
        with pytest.raises(PoolCapacityError, match="prefill bucket"):
            eng.submit(np.ones(33, np.int32), max_new_tokens=1)
        # prompt fits today, but a preemption replay could reach 33
        # tokens -> same infeasible bucket
        with pytest.raises(PoolCapacityError, match="preemption"):
            eng.submit(np.ones(20, np.int32), max_new_tokens=14)
        # one token shorter is admissible (worst feed 32 -> bucket 32)
        out = eng.submit(np.ones(20, np.int32), max_new_tokens=13) \
                 .result(timeout=300)
        assert out.shape == (33,)
        eng.close()

    def test_mixed_per_request_top_k_top_p_rejected(self, served_model):
        """Satellite: top_k/top_p are static truncation structure in
        _pick_token — part of the decode step's compile key. A
        mismatching per-request value is a ValueError at submit time,
        not a silent retrace storm; matching values are accepted."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=32,
                               kv_layout="paged", block_size=8, top_k=4)
        with pytest.raises(ValueError, match="compile key"):
            eng.submit(np.ones(3, np.int32), top_k=8)
        with pytest.raises(ValueError, match="compile key"):
            eng.submit(np.ones(3, np.int32), top_p=0.5)
        retrace0 = monitor.stat_get("dispatch/retrace_cause")
        out = eng.submit(np.ones(3, np.int32), max_new_tokens=2,
                         do_sample=True, temperature=0.8, top_k=4,
                         top_p=1.0).result(timeout=300)
        assert out.shape == (5,)
        eng.close()
        assert monitor.stat_get("dispatch/retrace_cause") == retrace0

    def test_pool_constructor_validation(self):
        with pytest.raises(ValueError, match="power of two"):
            _paged_pool(block_size=12)
        with pytest.raises(ValueError, match="multiple"):
            _paged_pool(min_bucket=12)
        with pytest.raises(ValueError, match="cannot hold even one"):
            _paged_pool(max_len=64, num_blocks=4)

    def test_max_len_beyond_position_embeddings_rejected(
            self, served_model):
        """Every paged jit is deferred, so this must fail at
        CONSTRUCTION like the dense layout does — past mpe the wpe
        gather clamps and the engine would stream silently wrong
        tokens."""
        with pytest.raises(ValueError, match="max_position_embeddings"):
            GenerationEngine(served_model, num_slots=2, max_len=128,
                             kv_layout="paged", block_size=8)

    def test_stats_snapshot(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=32,
                               kv_layout="paged", block_size=8)
        s0 = eng.stats()
        assert s0["kv_layout"] == "paged"
        assert s0["active_requests"] == 0
        assert s0["kv_blocks_in_use"] == 0
        eng.submit(np.ones(4, np.int32), max_new_tokens=2) \
           .result(timeout=300)
        s1 = eng.stats()
        eng.close()
        assert s1["prefix_misses"] == 1
        assert s1["prefix_hit_ratio"] == 0.0
        assert s1["num_blocks"] == eng._pool.num_blocks
        assert 0 <= s1["block_utilization"] <= 1
        # the dense engine reports the shared core without paged keys
        dense = GenerationEngine(served_model, num_slots=2, max_len=32)
        sd = dense.stats()
        dense.close()
        assert sd["kv_layout"] == "dense"
        assert "prefix_hit_ratio" not in sd
        assert sd["slots_in_use"] == 0
