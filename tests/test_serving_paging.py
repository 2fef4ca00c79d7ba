"""Paged KV-cache memory manager + prefix cache (paddle_tpu/serving/paging.py).

Three layers of guarantees (token parity of the engine over this pool
with per-request ``models.generate`` is ``test_serving_engine.py``'s):

* **memory manager** — free-list/refcount/copy-on-write bookkeeping,
  the prefix-cache trie with LRU eviction, and fail-fast named errors
  on misuse (double free, zero-length prompt, impossible admission)
  that never corrupt the free list;
* **quantized blocks** — int8 storage with per-block max-abs scales:
  capacity at the same byte budget, bounded round-trip and logit drift;
* **policy** — a prefix-cache hit feeds only the uncovered tail, in
  chunks (tokens saved, outputs unchanged), and block pressure preempts
  the youngest request (requeued + fed again, never deadlocked), still
  token-exact.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import monitor
from paddle_tpu.models import GPTConfig, GPTForPretraining, generate
from paddle_tpu.serving import (BlockError, GenerationEngine, PagedKVPool,
                                PoolCapacityError, PoolExhaustedError)

VOCAB = 96


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


def _writable(pool, slot):
    """The slot's next write position made writable: the (dst, src)
    copy-on-write order if the block there was shared, else None."""
    cows = pool.ensure_writable_range(slot, pool.slot_pos(slot))
    return cows[0] if cows else None


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

        from paddle_tpu.models.generation import (_quant_append,
                                                  _scale_lanes)
        rng = np.random.RandomState(0)
        # K and V rows filling three blocks, [2, Tp * bs, H, Dh]; V is
        # scaled apart from K so a swapped scale plane would show
        Tp, H, bs, Dh = 3, 2, 8, 4
        vals = rng.randn(2, Tp * bs, H, Dh).astype(np.float32) * 2.0
        vals[1] *= 5.0
        pool = jnp.zeros((1, 5, H, bs, 2 * Dh), jnp.int8)
        scales = jnp.zeros((1, 2, 5, H), jnp.float32)
        wb = np.repeat(np.array([1, 2, 3], np.int32), bs)
        off = np.tile(np.arange(bs, dtype=np.int32), Tp)
        pool, scales = _quant_append(
            pool, scales, 0, wb, off, jnp.asarray(vals[0]),
            jnp.asarray(vals[1]), 127.0)
        # dequantize blocks 1..3: [Tp, H, bs, 2*Dh] x the lane scales
        deq = np.asarray(pool[0, 1:4].astype(jnp.float32)
                         * _scale_lanes(scales[0][:, 1:4], Dh)[..., None, :])
        want = np.concatenate([vals[0], vals[1]], axis=-1) \
            .reshape(Tp, bs, H, 2 * Dh).transpose(0, 2, 1, 3)
        # one scale a (plane, block, head): max |x| over its rows / 127
        per = np.abs(vals).reshape(2, Tp, bs, H, Dh).max(axis=(2, 4)) / 127.0
        bound = np.repeat(per.transpose(1, 2, 0), Dh, axis=-1)[:, :, None, :]
        assert (np.abs(deq - want) <= bound * 0.5001 + 1e-7).all()

    def test_recycled_block_scale_is_reset(self):
        """A freed block returning through the allocator must NOT keep
        its previous tenant's max-abs scale: ``_quant_append`` only
        GROWS scales (scatter-max), so a stale coarse scale would
        quantize the next tenant's growth appends to near-zero ints —
        the 'bounded drift' contract silently broken by block churn."""
        import jax.numpy as jnp

        from paddle_tpu.models.generation import _quant_append
        pool = _paged_pool(num_slots=2, num_blocks=2, max_len=16,
                           dtype="int8")
        a = pool.alloc()
        blocks = pool.admit_fresh(a, 16)          # takes both blocks
        vals = jnp.full((2, 1, 1), 100.0)         # one row a block
        pool.data, pool.scales = _quant_append(
            pool.data, pool.scales, 0, np.asarray(blocks, np.int32),
            np.zeros(2, np.int32), vals, vals, 127.0)
        assert np.asarray(pool.scales)[0, 0, blocks[1]] > 0.5
        pool.free(a)                              # blocks recycled
        b = pool.alloc()
        pool.admit_fresh(b, 8)
        pool.set_slot(b, pos=8, lo=0)
        _writable(pool, b)                        # growth re-allocates
        grown = pool.slot_table(b)[1]
        assert float(np.asarray(pool.scales)[0, 0, grown]) == 0.0

    def test_int8_logit_drift_bounded_vs_fp32(self, served_model):
        """Identical prompt through the fused tower — the feed as one
        13-row chunk, then one decode row — over an fp32 and an int8
        pool: the decode row's LOGIT drift stays small relative to the
        logit scale — the bounded-drift half of the capacity win (token
        parity on trained margins is the other half, asserted by the
        parametrized engine tests)."""
        import jax.numpy as jnp

        from paddle_tpu.framework.tensor import Tensor, no_grad_guard
        from paddle_tpu.models.decoder_spec import serving_decoder
        from paddle_tpu.models.generation import _fused_tower
        from paddle_tpu.nn.layer.layers import (functional_state,
                                                get_buffers_tree,
                                                get_params_tree)
        from paddle_tpu.ops.ragged_paged_attention import ragged_layout
        model = served_model
        dec = serving_decoder(model)
        params, buffers = get_params_tree(model), get_buffers_tree(model)
        prompt = _prompt(np.random.RandomState(3), 13)
        bs, Q = 32, 16                  # the int8 tile's floor; one table
        table = np.array([[1]], np.int32)

        def launch(pool, scales, toks, p0, quant):
            n = len(toks)
            blk_seq, qstart, pos0, last_row, _ = ragged_layout(
                [n], [p0], q_bucket=Q)
            ids = np.zeros(Q, np.int32)
            ids[:n] = toks
            qpos = np.zeros(Q, np.int32)
            qpos[:n] = p0 + np.arange(n)
            wb = np.zeros(Q, np.int32)
            wb[:n] = 1
            with functional_state(model, params, buffers), no_grad_guard():
                x = dec.embed_tokens(ids, qpos)
                x, pool, scales, _, _ = _fused_tower(
                    dec, x, qpos, pool, scales, wb, qpos.copy(), blk_seq,
                    qstart, pos0, table, np.zeros(1, np.int32),
                    np.asarray([p0 + n], np.int32), quant, 127.0)
                logits = dec.logits(Tensor(
                    x._data[0, last_row][:, None, :]))._data[0, 0]
            return pool, scales, np.asarray(logits, np.float32)

        logits = {}
        for dtype in ("float32", "int8"):
            pool = _paged_pool(num_slots=1, num_blocks=2, num_heads=4,
                               head_dim=16, num_layers=2, dtype=dtype,
                               block_size=bs)
            data, scales, first = launch(pool.data, pool.scales, prompt, 0,
                                         pool.quantized)
            _, _, logits[dtype] = launch(data, scales,
                                         [int(first.argmax())],
                                         prompt.size, pool.quantized)
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
            _writable(pool, slot)
            pool.advance(slot)
        assert len(pool.slot_table(slot)) == 2
        with pytest.raises(RuntimeError, match="virtual capacity"):
            _writable(pool, slot)
            pool.advance(slot)

    def test_copy_on_write_hands_out_a_private_block(self):
        """A block reachable from two page tables is never written
        through: ensure_writable_range on the sharer returns a (dst,
        src) device-copy order and swaps its table entry."""
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
        cow = _writable(pool, b)
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
        assert _writable(pool, slot) is None    # fresh block appended
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

    @pytest.mark.parametrize("kv_dtype,block_size",
                             [(None, 8), ("int8", 32)])
    def test_engine_prefix_hit_skips_prefill_and_stays_exact(
            self, served_model, kv_dtype, block_size):
        """Requests sharing a system prompt of whole blocks: the first
        computes it, the rest adopt its cached blocks — only the tail is
        fed, tokens are saved, and the output still matches generate.
        Parametrized over int8 blocks (at the block size their tile
        needs): prefix caching rides on quantized storage unchanged
        (scales travel with the block ids)."""
        eng = GenerationEngine(served_model, num_slots=4, max_len=64,
                               block_size=block_size, kv_dtype=kv_dtype)
        rng = np.random.RandomState(5)
        n_sys = 16 if block_size == 8 else 32    # whole blocks exactly
        system = _prompt(rng, n_sys)
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
        assert eng._pool.tokens_saved == 2 * n_sys
        assert stats["prefix_hit_ratio"] > 0
        assert stats["prefill_tokens_saved"] == 2 * n_sys
        for t, out in zip([tails[0]] + tails[1:],
                          [first] + outs):
            p = np.concatenate([system, t])
            ref = generate(served_model, p[None, :], max_new_tokens=4)
            np.testing.assert_array_equal(out, ref.numpy()[0])

    def test_long_tail_takes_the_hit_and_drains_in_chunks(
            self, served_model):
        """A cached prefix is adopted whatever the length of the
        uncovered tail: the tail goes in as budgeted chunks of the
        cycles' launches (no per-token replay to decline it for), the
        cached tokens are never fed again, and the output stays exact."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=64,
                               block_size=8, prefill_budget=8)
        rng = np.random.RandomState(8)
        system = _prompt(rng, 16)     # two full cached blocks
        eng.submit(system, max_new_tokens=2).result(timeout=300)
        assert eng._pool.prefix_hits == 0
        fed0 = eng.stats()["chunked_prefill_tokens"]
        # 24-token tail, three times the chunk budget: the hit is TAKEN
        long = np.concatenate([system, _prompt(rng, 24)])
        h = eng.submit(long, max_new_tokens=4)
        out_long = h.result(timeout=300)
        stats = eng.stats()
        eng.close()
        assert eng._pool.prefix_hits == 1
        assert eng._pool.tokens_saved == 16
        assert stats["chunked_prefill_tokens"] - fed0 == 24
        hit = [m for n, _, m in h.trace.events if n == "prefix_hit"]
        assert hit == [{"tokens_saved": 16, "pending": 24}]
        chunks = [m["tokens"] for n, _, m in h.trace.events
                  if n == "prefill_chunk"]
        assert chunks == [8, 8, 8]
        ref = generate(served_model, long[None, :], max_new_tokens=4)
        np.testing.assert_array_equal(out_long, ref.numpy()[0])


# ---------------------------------------------------------------------------
# scheduler policy under block pressure: preemption, not deadlock
# ---------------------------------------------------------------------------

class TestPreemption:
    @pytest.mark.parametrize(
        "kv_dtype,block_size,max_len,num_blocks,new,seeds", [
            (None, 8, 32, 4, 24, (6, 7)),     # half the worst-case budget
            # three of the worst case's four. int8 rounds: one scale a
            # 32-row block keeps the float argmax on these two prompts
            # (on 3 of 6 seed pairs tried), so the case pins what
            # preemption adds on a pair where storage alone is exact
            ("int8", 32, 64, 3, 30, (16, 17)),
        ])
    def test_block_pressure_preempts_youngest_and_both_finish_exact(
            self, served_model, kv_dtype, block_size, max_len, num_blocks,
            new, seeds):
        """Two long requests whose combined growth exceeds the block
        budget: the YOUNGEST is preempted (blocks freed, request
        requeued, its history fed again on re-admission) instead of
        deadlocking — and both still produce the exact generate()
        sequence. Parametrized over int8 blocks (at the block size their
        tile needs): preemption rides on quantized storage unchanged."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=max_len,
                               block_size=block_size,
                               num_blocks=num_blocks, kv_dtype=kv_dtype)
        pa = _prompt(np.random.RandomState(seeds[0]), 4)
        pb = _prompt(np.random.RandomState(seeds[1]), 4)
        ha = eng.submit(pa, max_new_tokens=new)
        hb = eng.submit(pb, max_new_tokens=new)
        oa = ha.result(timeout=600)
        ob = hb.result(timeout=600)
        stats = eng.stats()
        eng.close()
        assert stats["preempts"] >= 1
        ra = generate(served_model, pa[None, :], max_new_tokens=new)
        rb = generate(served_model, pb[None, :], max_new_tokens=new)
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
                               block_size=8)
        with pytest.raises(ValueError, match="at least one"):
            eng.submit(np.zeros(0, np.int32))
        eng.close()

    def test_max_new_tokens_alone_exceeding_capacity_rejected(
            self, served_model):
        eng = GenerationEngine(served_model, num_slots=1, max_len=32,
                               block_size=8)
        with pytest.raises(PoolCapacityError, match="virtual capacity"):
            eng.submit(np.ones(1, np.int32), max_new_tokens=32)
        # the bound is the TRUE footprint: the same prompt fits with
        # max_new 31
        out = eng.submit(np.ones(1, np.int32), max_new_tokens=31) \
                 .result(timeout=300)
        assert out.shape == (32,)
        eng.close()

    def test_mixed_per_request_top_k_top_p_rejected(self, served_model):
        """Satellite: top_k/top_p are static truncation structure in
        _pick_token — part of the step's compile key. A
        mismatching per-request value is a ValueError at submit time,
        not a silent retrace storm; matching values are accepted."""
        eng = GenerationEngine(served_model, num_slots=2, max_len=32,
                               block_size=8, top_k=4)
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
        with pytest.raises(ValueError, match="max_len"):
            _paged_pool(max_len=0)
        with pytest.raises(ValueError, match="cannot hold even one"):
            _paged_pool(max_len=64, num_blocks=4)

    def test_max_len_beyond_position_embeddings_rejected(
            self, served_model):
        """Every jit is deferred, so this must fail at CONSTRUCTION —
        past mpe the wpe gather clamps and the engine would stream
        silently wrong tokens."""
        with pytest.raises(ValueError, match="max_position_embeddings"):
            GenerationEngine(served_model, num_slots=2, max_len=128,
                             block_size=8)

    def test_stats_snapshot(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=32,
                               block_size=8)
        s0 = eng.stats()
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


# ---------------------------------------------------------------------------
# cache groups: a window-0 group and a sliding-window group in ONE manager
# ---------------------------------------------------------------------------

def _grouped_pool(window=16, window_blocks=8, **kw):
    """Two groups over blocks of 8: the first keeps the whole context,
    the second a window of ``window`` positions."""
    kw.setdefault("num_blocks", 16)
    return _paged_pool(more_groups=[dict(
        num_layers=2, num_heads=2, lanes=4, window=window,
        num_blocks=window_blocks)], **kw)


def _check_group_lists(pool):
    for grp in pool.groups[1:]:
        free = set(grp.free)
        assert len(free) == len(grp.free), "free list holds duplicates"
        held = {b for b, rc in grp.ref.items() if rc > 0}
        assert not free & held and 0 not in free | held
        assert len(free) + len(held) == grp.num_blocks
        named = [b for st in pool._slots.values()
                 for b in st.tables[grp.index] if b]
        assert sorted(named) == sorted(held)      # each named exactly once


class TestCacheGroups:
    def test_the_one_group_pools_numbers_are_what_they_were(self):
        pool = _paged_pool(num_blocks=16, head_dim=2)
        assert len(pool.groups) == 1 and pool.groups[0].window == 0
        assert pool.shape == (1, 17, 1, 8, 4)
        assert pool.capacity_bytes == 17 * 8 * 4 * 4
        assert pool.block_bytes == 8 * 4 * 4
        s = pool.alloc()
        pool.admit_fresh(s, 20)
        pool.set_slot(s, pos=0, lo=0)
        assert pool.slot_table(s) == [1, 2, 3] and pool.blocks_in_use == 3
        assert pool.bytes_in_use == pool.live_bytes == 3 * pool.block_bytes
        pool.advance(s, 20)
        assert pool.slot_lo(s) == 0 and pool.window_blocks_freed == 0
        assert pool.live_tokens == 20
        assert pool.table_array(4, [s]).tolist()[s] == [1, 2, 3, 0]
        _check_free_list(pool)
        pool.free(s)
        assert pool.blocks_in_use == 0 and pool.blocks_available == 16

    @pytest.mark.parametrize("window,chunk", [(16, 1), (16, 5), (10, 8),
                                              (8, 16), (1, 3)],
                             ids=["decode", "chunks-of-5", "w10-chunks-of-8",
                                  "w8-chunks-of-16", "w1"])
    def test_a_window_group_frees_exactly_the_blocks_behind_the_window(
            self, window, chunk):
        """Walk 60 positions in launches of ``chunk`` rows: before a
        launch every position its rows may attend to is held, after it
        exactly the blocks wholly behind ``pos - W + 1`` are gone (table
        entry 0), nothing freed is handed out while held, and the
        window-0 group keeps everything."""
        pool = _grouped_pool(window=window)
        s = pool.alloc()
        pool.admit_fresh(s, 24)
        pool.set_slot(s, pos=0, lo=0)
        assert pool.slot_table(s, 1) == []        # window blocks come later
        freed = 0
        while pool.slot_pos(s) + chunk <= 60:
            pos = pool.slot_pos(s)
            assert pool.ensure_writable_range(s, pos + chunk - 1) == []
            wt = pool.slot_table(s, 1)
            lo = pool.slot_lo(s, 1)
            assert len(wt) == len(pool.slot_table(s)) \
                or len(wt) == (pos + chunk - 1) // 8 + 1
            for p in range(max(0, pos - window + 1), pos + chunk):
                assert wt[p // 8] != 0 and p >= lo, (pos, p)
            assert len(set(b for b in wt if b)) == sum(b != 0 for b in wt)
            pool.advance(s, chunk)
            pos = pool.slot_pos(s)
            wt = pool.slot_table(s, 1)
            gone = max(0, pos - window + 1) // 8
            assert [b == 0 for b in wt] == \
                [vb < gone for vb in range(len(wt))]
            assert pool.slot_lo(s, 1) == gone * 8
            assert pool.window_blocks_freed == gone >= freed
            freed = gone
            assert pool.group_blocks_in_use(1) == len(wt) - gone
            assert 0 not in pool.slot_table(s)    # the global group: all
            _check_free_list(pool)
            _check_group_lists(pool)
        assert freed >= (60 - window - 7) // 8
        assert pool.table_array(8, [s], group=1)[s].tolist()[:freed] \
            == [0] * freed

    def test_a_held_block_is_never_handed_out(self):
        """Four slots decode side by side in a window group with exactly
        the blocks the sizing rule gives (slots x (ceil(W / bs) + 2)): no
        launch finds it short, and a block freed by one slot serves
        another only once its table entry is 0."""
        pool = _grouped_pool(window=16, window_blocks=4 * 4, num_blocks=40,
                             max_len=80)
        slots = [pool.alloc() for _ in range(4)]
        for i, s in enumerate(slots):
            pool.admit_fresh(s, 10 + i)
            pool.set_slot(s, pos=0, lo=0)
            pool.ensure_writable_range(s, 9 + i)
            pool.advance(s, 10 + i)
        for _ in range(60):
            for s in slots:
                pool.ensure_writable_range(s, pool.slot_pos(s))
            for s in slots:
                pool.advance(s, 1)
            named = [b for s in slots for b in pool.slot_table(s, 1) if b]
            assert len(named) == len(set(named))
            _check_group_lists(pool)
        assert pool.window_blocks_freed >= 4 * 6

    def test_admission_gates_on_every_group_and_exhaustion_is_named(self):
        pool = _grouped_pool(window=16, window_blocks=3, num_blocks=8,
                             num_slots=2)
        assert pool.can_admit(40)                 # 5 global, 3 of window
        a = pool.alloc()
        pool.admit_fresh(a, 24)
        pool.set_slot(a, pos=0, lo=0)
        pool.ensure_writable_range(a, 23)         # three window blocks
        assert not pool.can_admit(8)              # the window group is out
        assert pool.blocks_available == 5         # the global one is not
        b = pool.alloc()
        pool.admit_fresh(b, 8)
        pool.set_slot(b, pos=0, lo=0)
        with pytest.raises(PoolExhaustedError, match="cache group 1"):
            pool.ensure_writable_range(b, 0)
        pool.free(b)
        pool.advance(a, 24)                       # frees one behind pos 9
        assert pool.can_admit(8) and pool.window_blocks_freed == 1
        _check_group_lists(pool)

    def test_preemption_returns_both_groups_blocks(self):
        pool = _grouped_pool(window=16)
        s = pool.alloc()
        pool.admit_fresh(s, 30)
        pool.set_slot(s, pos=0, lo=0)
        pool.ensure_writable_range(s, 29)
        pool.advance(s, 30)
        assert pool.blocks_in_use == 4 and pool.group_blocks_in_use(1) == 3
        assert pool.live_bytes == 4 * pool.group_block_bytes(0) \
            + 3 * pool.group_block_bytes(1)
        assert pool.group_block_bytes(1) == 2 * 2 * 8 * 4 * 4
        pool.free(s)                              # what a preemption does
        assert pool.blocks_in_use == 0 and pool.group_blocks_in_use(1) == 0
        assert pool.live_bytes == 0 and pool.live_tokens == 0
        _check_free_list(pool)
        _check_group_lists(pool)
        s = pool.alloc()                          # and the slot starts anew
        assert pool.slot_lo(s, 1) == 0 and pool.slot_table(s, 1) == []

    def test_no_block_is_offered_or_matched_with_a_window_group(self):
        pool = _grouped_pool()
        toks = list(range(1, 25))
        s = pool.alloc()
        pool.admit_fresh(s, 24)
        pool.set_slot(s, pos=0, lo=0)
        pool.ensure_writable_range(s, 23)
        pool.advance(s, 23)
        pool.register_prefix(s, toks)
        assert pool.cached_blocks == 0 and pool.match_prefix(toks) == []
        pool.free(s)
        assert pool.blocks_available == 16        # nothing waits in an LRU

    def test_reset_and_refusals(self):
        pool = _grouped_pool()
        s = pool.alloc()
        pool.admit_fresh(s, 8)
        pool.set_slot(s, pos=0, lo=0)
        pool.ensure_writable_range(s, 7)
        pool.free(s)
        pool.reset_data()
        assert [g.data.shape for g in pool.groups] == [(1, 17, 1, 8, 2),
                                                       (2, 9, 2, 8, 4)]
        assert len(pool.groups[1].free) == 8
        assert pool.capacity_bytes == (17 * 8 * 2 + 2 * 9 * 2 * 8 * 4) * 4
        with pytest.raises(ValueError, match="more than one cache group"):
            _grouped_pool(dtype="int8")
        with pytest.raises(ValueError, match="cannot hold even one"):
            _grouped_pool(window=16, window_blocks=2)
