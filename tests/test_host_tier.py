"""Hierarchical KV cache: host-DRAM spill tier (paddle_tpu/serving/host_tier.py).

Four layers of guarantees:

* **exactness** — a demoted block's host copy is bit-identical to the
  device block it came from, and a promoted block lands bit-identical
  back on the device, for fp32 pools AND int8 pools (block + per-block
  scales demoted/promoted together);
* **isolation** — a promoted-then-shared block COWs exactly like a
  never-evicted cached block (writer gets a private copy, the trie node
  and the other reader are untouched);
* **degradation** — every pressure path (full spill queue, tier LRU
  capacity, promoter shed, adoption exhaustion, in-flight races with
  republish/teardown) degrades to plain-eviction behaviour, never to an
  error on the serving path; named errors fire only on API misuse;
* **liveness** — decode never blocks on an in-flight promotion (a
  fresh request completes while a promotion-waiter is parked), and
  engine ``close()`` drains and joins both tier threads.
"""
import threading

import numpy as np
import pytest

from paddle_tpu.models import generate
from paddle_tpu.serving import (GenerationEngine, HostBlockPool,
                                HostTierError, HostTierFullError,
                                PagedKVPool, PromotionTicket)

VOCAB = 96


def _paged_pool(**kw):
    kw.setdefault("num_layers", 1)
    kw.setdefault("num_slots", 4)
    kw.setdefault("num_heads", 1)
    kw.setdefault("max_len", 64)
    kw.setdefault("head_dim", 2)
    kw.setdefault("block_size", 8)
    return PagedKVPool(**kw)


def _tiered_pool(tier_blocks=16, **kw):
    pool = _paged_pool(**kw)
    tier = HostBlockPool(
        tier_blocks * (pool.host_block_nbytes + pool.host_scale_nbytes),
        pool.host_block_nbytes, scale_nbytes=pool.host_scale_nbytes)
    pool.attach_host_tier(tier)
    return pool, tier


def _publish(pool, toks, values):
    """Prefill stand-in: alloc a slot, fill each of its blocks with a
    distinct constant, publish the prefix, free the slot. Returns the
    physical block ids the prefix was published under."""
    slot = pool.alloc()
    blocks = pool.admit_fresh(slot, len(toks))
    for b, v in zip(blocks, values):
        pool.data = pool.data.at[:, b].set(v)
        if pool.quantized:
            pool.scales = pool.scales.at[:, :, b].set(abs(v) / 127.0)
    pool.register_prefix(slot, toks)
    pool.free(slot)
    return blocks


def _demote(pool, tier):
    pool.tier_tick()
    tier.drain()


def _evict_all(pool):
    while pool._lru:
        pool._evict_one()


def _promote(pool, tier, probe):
    """Full promotion round-trip for ``probe`` (a token list whose
    proper-prefix blocks are host-resident). Returns the ticket."""
    host_keys, covered = pool.tier_match(probe)
    assert host_keys, "expected a host-tier chain to promote"
    tk = tier.request_promotion(host_keys)
    assert tk is not None
    assert tk.ready.wait(20), "promoter thread never staged the chain"
    assert pool.adopt_promotion(tk)
    return tk


# ---------------------------------------------------------------------------
# host store unit behaviour (no engine)
# ---------------------------------------------------------------------------

class TestHostStore:
    def test_oversized_entry_rejected_at_ctor(self):
        with pytest.raises(HostTierFullError):
            HostBlockPool(100, 512)

    def test_capacity_pressure_evicts_host_lru_silently(self):
        tier = HostBlockPool(2 * 64, 64)
        try:
            for k in range(3):
                tier.put((k,), np.full(16, float(k), np.float32))
            assert tier.blocks == 2
            assert tier.tier_evictions == 1
            assert not tier.has((0,))          # oldest fell off
            assert tier.has((1,)) and tier.has((2,))
            assert tier.bytes_in_use == 2 * 64
        finally:
            tier.close()

    def test_get_missing_and_closed_put_raise_named_errors(self):
        tier = HostBlockPool(1 << 12, 64)
        with pytest.raises(HostTierError):
            tier.get((1, 2, 3))
        tier.close()
        with pytest.raises(HostTierError):
            tier.put((1,), np.zeros(4, np.float32))
        assert tier.spill([(1,)], np.zeros(4)) is False  # degrade, no raise

    def test_close_is_idempotent_and_joins_threads(self):
        tier = HostBlockPool(1 << 12, 64)
        tier.close()
        tier.close()
        assert not tier._spiller.is_alive()
        assert not tier._promoter.is_alive()


# ---------------------------------------------------------------------------
# demotion / promotion exactness (pool-level, no engine)
# ---------------------------------------------------------------------------

class TestTierExactness:
    def test_fp32_demotion_is_bit_identical(self):
        pool, tier = _tiered_pool()
        try:
            toks = tuple(range(100, 116))     # 2 full blocks
            blocks = _publish(pool, toks, (3.0, 5.0))
            assert pool._tier_pending
            _demote(pool, tier)
            assert tier.demoted_blocks == 2
            for i, b in enumerate(blocks):
                host, scale = tier.get(toks[:(i + 1) * 8])
                assert scale is None
                np.testing.assert_array_equal(
                    host, np.asarray(pool.data[:, b]))
        finally:
            tier.close()

    def test_fp32_promotion_is_bit_identical(self):
        pool, tier = _tiered_pool()
        try:
            toks = tuple(range(100, 116))
            _publish(pool, toks, (3.0, 5.0))
            _demote(pool, tier)
            _evict_all(pool)
            probe = list(toks) + [1]
            assert pool.match_prefix(probe) == []
            host_keys, covered = pool.tier_match(probe)
            assert covered == 16 and len(host_keys) == 2
            _promote(pool, tier, probe)
            got = pool.match_prefix(probe)
            assert len(got) == 2
            for i, b in enumerate(got):
                host, _ = tier.get(toks[:(i + 1) * 8])  # host copy kept
                np.testing.assert_array_equal(
                    np.asarray(pool.data[:, b]), host)
            assert tier.promoted_blocks == 2
            assert tier.stats()["promotion_ms"]["count"] == 1
        finally:
            tier.close()

    def test_int8_round_trip_carries_scales(self):
        pool, tier = _tiered_pool(dtype="int8")
        try:
            toks = tuple(range(40, 56))
            blocks = _publish(pool, toks, (17, 33))
            want = [(np.asarray(pool.data[:, b]),
                     np.asarray(pool.scales[:, :, b])) for b in blocks]
            _demote(pool, tier)
            for i in range(2):
                host, scale = tier.get(toks[:(i + 1) * 8])
                np.testing.assert_array_equal(host, want[i][0])
                np.testing.assert_array_equal(scale, want[i][1])
            _evict_all(pool)
            probe = list(toks) + [1]
            _promote(pool, tier, probe)
            got = pool.match_prefix(probe)
            for i, b in enumerate(got):
                np.testing.assert_array_equal(
                    np.asarray(pool.data[:, b]), want[i][0])
                np.testing.assert_array_equal(
                    np.asarray(pool.scales[:, :, b]), want[i][1])
        finally:
            tier.close()

    def test_promoted_block_cows_on_shared_append(self):
        pool, tier = _tiered_pool()
        try:
            toks = tuple(range(100, 116))
            _publish(pool, toks, (3.0, 5.0))
            _demote(pool, tier)
            _evict_all(pool)
            probe = list(toks) + [1]
            _promote(pool, tier, probe)
            got = pool.match_prefix(probe)
            shared = got[-1]
            a, b = pool.alloc(), pool.alloc()
            pool.admit_cached(a, got)
            pool.admit_cached(b, got)
            assert pool._ref[shared] == 2
            # writer appends into the shared tail block -> COW
            pool.set_slot(a, pos=8, lo=0)
            (cow,) = pool.ensure_writable_range(a, 8)
            dst, src = cow
            assert src == shared and dst != shared
            assert pool.slot_table(a)[1] == dst
            assert pool.slot_table(b)[1] == shared     # reader untouched
            assert pool._trie[toks].block == shared    # trie untouched
        finally:
            tier.close()


# ---------------------------------------------------------------------------
# races + degradation (satellite: eviction/promotion races, teardown)
# ---------------------------------------------------------------------------

class TestTierRaces:
    def test_demotion_in_flight_while_prefix_republished(self):
        """The content-canonical invariant in action: the spiller is
        mid-copy when the SAME prefix is re-published on the device.
        Both copies are identical bytes; nothing corrupts, and
        tier_match stays device-first."""
        pool, tier = _tiered_pool()
        toks = tuple(range(100, 116))
        gate, entered = threading.Event(), threading.Event()
        orig = tier._fetch
        def gated(dev):
            entered.set()
            assert gate.wait(20)
            return orig(dev)
        tier._fetch = gated
        try:
            _publish(pool, toks, (3.0, 5.0))
            pool.tier_tick()
            assert entered.wait(20)           # spiller holds the copy
            _evict_all(pool)
            again = _publish(pool, toks, (3.0, 5.0))  # republish mid-flight
            gate.set()
            tier.drain()
            assert tier.demoted_blocks == 2
            # device wins the walk; the host copy is a warm spare
            host_keys, _ = pool.tier_match(list(toks) + [1])
            assert host_keys == []
            for i, b in enumerate(again):
                host, _ = tier.get(toks[:(i + 1) * 8])
                np.testing.assert_array_equal(
                    host, np.asarray(pool.data[:, b]))
        finally:
            tier._fetch = orig
            tier.close()

    def test_full_spill_queue_degrades_to_plain_eviction(self):
        pool, tier = _tiered_pool()
        gate, entered = threading.Event(), threading.Event()
        orig = tier._fetch
        def gated(dev):
            entered.set()
            assert gate.wait(20)
            return orig(dev)
        tier._fetch = gated
        try:
            blk = np.zeros((1, 2, 1, 1, 8, 2), np.float32)
            assert tier.spill([(0,)], blk)
            assert entered.wait(20)           # worker busy on item 0
            for i in range(1, 5):             # fill the depth-4 queue
                assert tier.spill([(i,)], blk)
            assert tier.spill([(9, 9)], blk) is False   # full -> degrade
            assert tier.dropped_blocks == 1
            gate.set()
            tier.drain()
            assert tier.demoted_blocks == 5   # queued ones still landed
        finally:
            tier._fetch = orig
            tier.close()

    def test_failed_fetch_is_dropped_not_raised(self):
        pool, tier = _tiered_pool()
        orig = tier._fetch
        def boom(dev):
            raise RuntimeError("device tore down mid-copy")
        tier._fetch = boom
        try:
            blk = np.zeros((1, 2, 2, 1, 8, 2), np.float32)
            assert tier.spill([(1,), (2,)], blk)
            tier.drain()                      # spiller survives the error
            assert tier.demoted_blocks == 0
            assert tier.dropped_blocks == 2
            assert tier._spiller.is_alive()
        finally:
            tier._fetch = orig
            tier.close()

    def test_promotion_coalesces_and_adoption_skips_republished(self):
        pool, tier = _tiered_pool()
        try:
            toks = tuple(range(100, 116))
            _publish(pool, toks, (3.0, 5.0))
            _demote(pool, tier)
            _evict_all(pool)
            probe = list(toks) + [1]
            host_keys, _ = pool.tier_match(probe)
            t1 = tier.request_promotion(host_keys)
            t2 = tier.request_promotion(host_keys)
            assert t1 is t2                   # coalesced per chain
            assert t1.ready.wait(20)
            # race: the whole chain republishes while the copy staged
            _publish(pool, toks, (3.0, 5.0))
            before = len(pool._free)
            assert pool.adopt_promotion(t1)   # success: nothing to land
            assert len(pool._free) == before  # no blocks allocated
            assert tier.promoted_blocks == 0
        finally:
            tier.close()

    def test_adoption_under_exhaustion_degrades_to_miss(self):
        pool, tier = _tiered_pool(num_slots=2, num_blocks=8)
        try:
            toks = tuple(range(100, 116))
            _publish(pool, toks, (3.0, 5.0))
            _demote(pool, tier)
            _evict_all(pool)
            tk = tier.request_promotion(
                pool.tier_match(list(toks) + [1])[0])
            assert tk.ready.wait(20)
            # pin every block so adoption cannot allocate
            slot = pool.alloc()
            pool.admit_fresh(slot, 64)
            assert not pool._free and not pool._lru
            assert pool.adopt_promotion(tk) is False
            assert pool.tier_degraded == 1
            assert tk not in tier._tickets.values()   # released
        finally:
            tier.close()

    def test_dead_waiter_releases_its_ticket(self, served_model):
        """A cancelled promotion-waiter must not leak its ticket (the
        staged device buffers would otherwise pin memory forever)."""
        eng = _mk_engine(served_model, host_tier_bytes=4 << 20)
        try:
            _seed_host_prefix(eng)
            tier = eng._pool.host_tier
            tk = PromotionTicket([(1, 2)])             # never becomes ready
            tier._tickets[(1, 2)] = tk
            orig = tier.request_promotion
            tier.request_promotion = lambda keys: tk
            h = eng.submit(np.concatenate([_SYSTEM, [50]]),
                           max_new_tokens=4)
            # the scheduler parked the request on the held ticket
            assert _wait_for(lambda: h._promo_ticket is tk, 15)
            h.cancel()
            assert _wait_for(h.done, 30)
            tier.request_promotion = orig
            # the sweep released the dead waiter's ticket
            assert _wait_for(lambda: (1, 2) not in tier._tickets, 10)
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# engine-level: tiered serving behaviour
# ---------------------------------------------------------------------------

_SYSTEM = np.arange(2, 18, dtype=np.int32)        # 2 full 8-token blocks


def _wait_for(cond, timeout):
    import time
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _mk_engine(model, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 8)
    return GenerationEngine(model, **kw)


def _seed_host_prefix(eng):
    """Run the system prompt once, then churn unrelated prefixes until
    the system blocks are evicted from the 8-block device pool — with a
    host tier attached they demote instead of dying."""
    eng.submit(np.concatenate([_SYSTEM, [40]]),
               max_new_tokens=4).result(timeout=300)
    for j in range(3):
        eng.submit(np.arange(60 + 20 * j, 76 + 20 * j, dtype=np.int32),
                   max_new_tokens=4).result(timeout=300)
    tier = getattr(eng._pool, "host_tier", None)
    if tier is not None:
        eng._pool.tier_tick()
        tier.drain()


def _churn_outputs(eng, system=_SYSTEM, fillers=None):
    if fillers is None:
        fillers = [np.arange(60 + 20 * j, 76 + 20 * j, dtype=np.int32)
                   for j in range(3)]
    outs = [eng.submit(np.concatenate([system, [40]]),
                       max_new_tokens=4).result(timeout=300)]
    for f in fillers:
        outs.append(eng.submit(f, max_new_tokens=4).result(timeout=300))
    tier = getattr(eng._pool, "host_tier", None)
    if tier is not None:
        eng._pool.tier_tick()
        tier.drain()
    outs.append(eng.submit(np.concatenate([system, [40]]),
                           max_new_tokens=4).result(timeout=300))
    return outs


class TestTieredEngine:
    def test_host_hit_with_token_parity_and_stats(self, served_model):
        tiered = _mk_engine(served_model, host_tier_bytes=4 << 20)
        try:
            got = _churn_outputs(tiered)
            s = tiered.stats()
            assert s["tier_hits"]["host"] >= 1
            assert s["host_tier"]["demoted_blocks"] >= 2
            assert s["host_tier"]["promoted_blocks"] >= 2
            assert s["host_tier"]["promotion_ms"]["count"] >= 1
            # split ratios sum to 1 and the old aggregate key survives
            assert s["prefix_hit_hbm"] + s["prefix_hit_host"] \
                + s["prefix_miss"] == pytest.approx(1.0)
            assert "prefix_hit_ratio" in s
        finally:
            tiered.close()
        untiered = _mk_engine(served_model)
        try:
            want = _churn_outputs(untiered)
            s = untiered.stats()
            assert s["tier_hits"]["host"] == 0   # split exists untiered
        finally:
            untiered.close()
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_int8_tiered_parity(self, served_model):
        # int8 blocks at the size their tile needs (32 tokens): a
        # one-block system prompt, three two-block fillers through a
        # three-block pool — the system block is evicted, demoted with
        # its scales, and promoted back for the repeat
        kw = dict(kv_dtype="int8", block_size=32, max_len=64, num_blocks=3)
        system = np.arange(2, 34, dtype=np.int32)
        fillers = [((np.arange(33) * 3 + 7 + 11 * j) % 90 + 1)
                   .astype(np.int32) for j in range(3)]
        tiered = _mk_engine(served_model, host_tier_bytes=4 << 20, **kw)
        try:
            got = _churn_outputs(tiered, system, fillers)
            assert tiered.stats()["tier_hits"]["host"] >= 1
        finally:
            tiered.close()
        untiered = _mk_engine(served_model, **kw)
        try:
            want = _churn_outputs(untiered, system, fillers)
        finally:
            untiered.close()
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_a_promotion_lands_into_a_chunked_feed(self, served_model):
        """The host tier over the chunked cycle: a request whose prefix
        is host-resident waits for the H2D copy, adopts the promoted
        blocks as a prefix hit, and its uncovered tail — three times the
        chunk budget — goes in as chunks of the cycles' launches. The
        hit is classed ``host`` and the output matches ``generate``."""
        eng = _mk_engine(served_model, host_tier_bytes=4 << 20,
                         prefill_budget=4)
        try:
            _seed_host_prefix(eng)
            assert eng._pool.match_prefix(list(_SYSTEM) + [1]) == []
            host0 = eng.stats()["tier_hits"]["host"]
            prompt = np.concatenate(
                [_SYSTEM, np.arange(30, 42, dtype=np.int32)])
            h = eng.submit(prompt, max_new_tokens=4)
            out = h.result(timeout=300)
            s = eng.stats()
        finally:
            eng.close()
        assert s["tier_hits"]["host"] == host0 + 1
        assert s["host_tier"]["promoted_blocks"] >= 2
        hit = [m for n, _, m in h.trace.events if n == "prefix_hit"]
        assert hit == [{"tokens_saved": 16, "pending": 12}]
        chunks = [m["tokens"] for n, _, m in h.trace.events
                  if n == "prefill_chunk"]
        assert chunks == [4, 4, 4]
        ref = generate(served_model, prompt[None, :], max_new_tokens=4)
        np.testing.assert_array_equal(out, ref.numpy()[0])

    def test_decode_never_blocks_on_inflight_promotion(self, served_model):
        eng = _mk_engine(served_model, host_tier_bytes=4 << 20)
        try:
            _seed_host_prefix(eng)
            tier = eng._pool.host_tier
            held = PromotionTicket([(1, 2)])          # never becomes ready
            orig = tier.request_promotion
            tier.request_promotion = lambda keys: held
            waiter = eng.submit(np.concatenate([_SYSTEM, [50]]),
                                max_new_tokens=4)
            fresh = eng.submit(np.arange(5, 17, dtype=np.int32),
                               max_new_tokens=4)
            out = fresh.result(timeout=300)           # completes while parked
            assert out.size == 12 + 4
            assert not waiter.done()
            tier.request_promotion = orig
            held.failed = True                        # release -> plain miss
            held.ready.set()
            tier._progress.set()
            out = waiter.result(timeout=300)
            assert out.size == _SYSTEM.size + 1 + 4
        finally:
            eng.close()

    def test_tiny_host_tier_degrades_never_errors(self, served_model):
        # capacity = ONE entry: every demotion evicts the previous one
        probe = _mk_engine(served_model, host_tier_bytes=4 << 20)
        entry = probe._pool.host_block_nbytes + probe._pool.host_scale_nbytes
        probe.close()
        eng = _mk_engine(served_model, host_tier_bytes=entry)
        try:
            outs = _churn_outputs(eng)
            assert all(o.size > 0 for o in outs)
            assert eng._pool.host_tier.tier_evictions >= 1
        finally:
            eng.close()

    def test_close_drains_and_joins_tier_threads(self, served_model):
        eng = _mk_engine(served_model, host_tier_bytes=4 << 20)
        tier = eng._pool.host_tier
        _seed_host_prefix(eng)
        eng.close()
        assert not tier._spiller.is_alive()
        assert not tier._promoter.is_alive()
        assert tier.demoted_blocks >= 2

    def test_host_tier_requires_no_mesh(self, served_model):
        with pytest.raises(ValueError, match="does not compose with mesh"):
            GenerationEngine(served_model, num_slots=2, max_len=48,
                             mesh="a mesh", host_tier_bytes=1 << 20)

    def test_ledger_splits_host_bytes_out_of_device_crosscheck(
            self, served_model):
        from paddle_tpu.profiler import memory as prof_memory
        eng = _mk_engine(served_model, host_tier_bytes=4 << 20)
        try:
            _seed_host_prefix(eng)
            cc = prof_memory.crosscheck()
            assert "host_ledger_bytes" in cc
            assert cc["host_ledger_bytes"] >= 4 << 20   # capacity entry
            led = prof_memory.ledger()
            host_keys = [k for k in led if k.startswith("host/")]
            assert any(k.endswith("/capacity") for k in host_keys)
            assert any(k.endswith("/in_use") for k in host_keys)
        finally:
            eng.close()

    def test_plan_replica_does_not_bill_host_tier(self, served_model):
        eng = _mk_engine(served_model, host_tier_bytes=4 << 20)
        try:
            plan = eng.plan_replica()
            assert plan["host_tier_bytes"] == 4 << 20
            assert plan["static_peak_bytes"] < 4 << 20  # tiny model + pool
        finally:
            eng.close()
