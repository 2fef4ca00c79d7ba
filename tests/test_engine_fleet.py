"""EngineFleet (serving/fleet.py): aggregated fleet stats over N
GenerationEngine replicas — summed counters, histogram-merge latency
percentiles vs pooled raw samples, per-replica gauges, poisoned-replica
fault isolation, round-robin spill-over dispatch — plus the
flight-recorder dump-collision satellite and the engine's metrics-
registry/statusz wiring."""
import json
import math
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import metrics as M
from paddle_tpu.models import GPTConfig, GPTForPretraining, generate
from paddle_tpu.serving import (EngineFleet, FlightRecorder,
                                GenerationEngine, QueueFullError)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.framework.random.seed(0)
    model = GPTForPretraining(GPTConfig.tiny())
    model.eval()
    return model


# ---------------------------------------------------------------------------
# stub replicas: aggregation logic without paying two engines' compiles
# ---------------------------------------------------------------------------

class _StubRecorder:
    def __init__(self, ttft, tpot=()):
        self._ttft, self._tpot = list(ttft), list(tpot)

    def latency_samples(self):
        return {"ttft_ms": list(self._ttft), "tpot_ms": list(self._tpot)}


class _StubEngine:
    def __init__(self, ttft=(), retired=0, queue=0, slots=(1, 4),
                 blocks=None, fail_stats=False, refuse=None):
        self._ttft = ttft
        self._retired = retired
        self._queue = queue
        self._slots = slots
        self._blocks = blocks
        self._fail_stats = fail_stats
        self._refuse = refuse
        self.submitted = []
        self.closed = False
        self.flight_recorder = _StubRecorder(ttft)

    def submit(self, prompt_ids, max_new_tokens=32, **kw):
        if self._refuse is not None:
            raise self._refuse
        self.submitted.append(np.asarray(prompt_ids))
        return f"handle{len(self.submitted)}"

    def stats(self):
        if self._fail_stats:
            raise RuntimeError("scheduler thread is dead")
        s = {"queue_depth": self._queue, "active_requests": 1,
             "num_slots": self._slots[1], "slots_in_use": self._slots[0],
             "slot_utilization": self._slots[0] / self._slots[1],
             "preempts": 1, "requests_retired": self._retired,
             "nonfinite_cycles": 0, "kv_pool_capacity_bytes": 1000,
             "kv_bytes_in_use": 100}
        if self._blocks is not None:
            used, total = self._blocks
            s.update({"num_blocks": total, "kv_blocks_in_use": used,
                      "prefix_hits": 6, "prefix_misses": 2,
                      "prefill_tokens_saved": 48, "prefix_evictions": 0,
                      "cached_blocks": 1,
                      "prefix_hit_ratio": 0.75, "block_size": 8})
        return s

    def close(self, cancel_pending=False):
        self.closed = True


class TestAggregation:
    def test_counters_sum_and_ratios_derive(self):
        f = EngineFleet([_StubEngine(retired=10, queue=2, blocks=(3, 10)),
                         _StubEngine(retired=5, queue=1, blocks=(1, 10))])
        s = f.stats()
        assert s["requests_retired"] == 15
        assert s["queue_depth"] == 3
        assert s["kv_blocks_in_use"] == 4 and s["num_blocks"] == 20
        assert s["block_utilization"] == pytest.approx(0.2)
        assert s["prefix_hits"] == 12 and s["prefix_misses"] == 4
        assert s["prefix_hit_ratio"] == pytest.approx(0.75)
        assert s["replicas_healthy"] == 2 and s["replicas_total"] == 2
        f.close()

    def test_pooled_percentiles_match_raw_within_bin(self):
        rng = np.random.RandomState(3)
        a = rng.lognormal(2.5, 0.5, 300).tolist()    # fast replica
        b = rng.lognormal(4.0, 0.3, 60).tolist()     # slow replica
        f = EngineFleet([_StubEngine(ttft=a), _StubEngine(ttft=b)])
        s = f.stats()
        pooled = sorted(a + b)
        assert s["ttft_ms"]["count"] == 360
        h = M.HistValue.from_samples(a + b)
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            raw = pooled[min(len(pooled) - 1,
                             max(0, math.ceil(q * len(pooled)) - 1))]
            est = s["ttft_ms"][key]
            # within one bucket of the raw pooled percentile
            lo = 0.0
            for le in h.buckets:
                if est <= le:
                    hi = le
                    break
                lo = le
            assert lo <= raw <= hi or abs(est - raw) <= (hi - lo), \
                (key, est, raw, lo, hi)
        f.close()

    def test_poisoned_replica_isolated(self):
        good = _StubEngine(retired=7, ttft=[10.0, 20.0])
        bad = _StubEngine(fail_stats=True)
        f = EngineFleet([good, bad])
        s = f.stats()
        assert s["replicas_total"] == 2
        assert s["replicas_healthy"] == 1
        assert s["requests_retired"] == 7       # healthy replica only
        assert s["ttft_ms"]["count"] == 2
        rep = {r["replica"]: r for r in s["replicas"]}
        assert rep[0]["healthy"] is True
        assert rep[1]["healthy"] is False
        assert "scheduler thread is dead" in rep[1]["error"]
        f.close()

    def test_per_replica_gauges(self):
        f = EngineFleet([_StubEngine(slots=(3, 4), blocks=(2, 8)),
                         _StubEngine(slots=(1, 4), blocks=(7, 8))])
        reps = f.stats()["replicas"]
        assert [r["free_slots"] for r in reps] == [1, 3]
        assert [r["free_blocks"] for r in reps] == [6, 1]
        f.close()


class TestDispatch:
    def test_round_robin_rotates(self):
        e1, e2 = _StubEngine(), _StubEngine()
        f = EngineFleet([e1, e2])
        for i in range(4):
            f.submit([1, 2, 3])
        assert len(e1.submitted) == 2 and len(e2.submitted) == 2
        f.close()

    def test_backpressure_spills_to_next_replica(self):
        full = _StubEngine(refuse=QueueFullError("full"))
        open_ = _StubEngine()
        f = EngineFleet([full, open_])
        for _ in range(3):
            f.submit([1, 2])
        assert len(open_.submitted) == 3
        f.close()

    def test_capacity_error_spills_despite_valueerror_base(self):
        """PoolCapacityError subclasses ValueError; it must still be
        treated as backpressure (spill to the next replica), never as a
        malformed request (immediate re-raise)."""
        from paddle_tpu.serving import PoolCapacityError
        small = _StubEngine(refuse=PoolCapacityError("prompt too long"))
        big = _StubEngine()
        f = EngineFleet([small, big])
        for _ in range(3):
            f.submit([1] * 100)
        assert len(big.submitted) == 3
        f.close()

    def test_all_refusing_propagates_last_error(self):
        f = EngineFleet([_StubEngine(refuse=QueueFullError("a")),
                         _StubEngine(refuse=QueueFullError("b"))])
        with pytest.raises(QueueFullError):
            f.submit([1])
        f.close()

    def test_malformed_request_raises_immediately(self):
        counted = _StubEngine(refuse=ValueError("bad prompt"))
        other = _StubEngine()
        f = EngineFleet([counted, other])
        with pytest.raises(ValueError):
            f.submit([1])
        assert other.submitted == []    # no spill for a caller bug
        f.close()

    def test_closed_fleet_rejects(self):
        e = _StubEngine()
        f = EngineFleet([e])
        f.close()
        assert e.closed
        with pytest.raises(RuntimeError):
            f.submit([1])

    def test_empty_fleet_rejected(self):
        with pytest.raises(ValueError):
            EngineFleet([])


class TestRoutedDispatch:
    """ISSUE-15 router upgrade: load-aware and prefix-affinity
    dispatch behind the ``route=`` flag, round-robin untouched as the
    default (every TestDispatch case above runs the default)."""

    def test_bad_route_rejected(self):
        with pytest.raises(ValueError):
            EngineFleet([_StubEngine()], route="best-effort")

    def test_default_is_round_robin(self):
        f = EngineFleet([_StubEngine()])
        assert f.stats()["route"] == "rr"
        f.close()

    def test_load_route_prefers_most_free_blocks(self):
        # replica 1 has 7 of 8 blocks free vs replica 0's 2 of 8 —
        # every admission must land on replica 1 (stub stats are
        # static, so the imbalance never corrects)
        crowded = _StubEngine(blocks=(6, 8))
        free = _StubEngine(blocks=(1, 8))
        f = EngineFleet([crowded, free], route="load")
        for _ in range(4):
            f.submit([1, 2, 3])
        assert len(free.submitted) == 4
        assert len(crowded.submitted) == 0
        f.close()

    def test_load_route_falls_back_to_free_slots(self):
        # dense replicas (no block gauges): free SLOTS decide
        busy = _StubEngine(slots=(4, 4))
        idle = _StubEngine(slots=(0, 4))
        f = EngineFleet([busy, idle], route="load")
        for _ in range(3):
            f.submit([1, 2])
        assert len(idle.submitted) == 3 and len(busy.submitted) == 0
        f.close()

    def test_load_route_ties_rotate(self):
        # equal load: the round-robin rotation must still share
        # admissions (the stable-sort tie-break)
        e1, e2 = _StubEngine(blocks=(2, 8)), _StubEngine(blocks=(2, 8))
        f = EngineFleet([e1, e2], route="load")
        for _ in range(4):
            f.submit([1, 2, 3])
        assert len(e1.submitted) == 2 and len(e2.submitted) == 2
        f.close()

    def test_load_route_unhealthy_ranks_last(self):
        dead = _StubEngine(fail_stats=True, blocks=(0, 8))
        alive = _StubEngine(blocks=(7, 8))       # nearly full but alive
        f = EngineFleet([dead, alive], route="load")
        f.submit([1, 2])
        assert len(alive.submitted) == 1 and len(dead.submitted) == 0
        f.close()

    def test_affinity_pins_block_aligned_prefix(self):
        # stub block_size is 8: prompts sharing the same 8-token
        # aligned prefix must all land on ONE replica, even though
        # round-robin would alternate them
        e1, e2 = _StubEngine(blocks=(2, 8)), _StubEngine(blocks=(2, 8))
        f = EngineFleet([e1, e2], route="affinity")
        sys_prompt = list(range(1, 9))           # one full block
        for tail in ([10], [11, 12], [13], [14, 15, 16]):
            f.submit(sys_prompt + tail)
        counts = sorted([len(e1.submitted), len(e2.submitted)])
        assert counts == [0, 4], counts
        f.close()

    def test_affinity_distinct_prefixes_spread_by_load(self):
        # two different hot prefixes: the first pin goes to the freest
        # replica, whose load gauge (static stubs aside) would keep
        # attracting — but a DIFFERENT prefix consults its own pin, so
        # the mapping is per-prefix, not global
        e1, e2 = _StubEngine(blocks=(2, 8)), _StubEngine(blocks=(2, 8))
        f = EngineFleet([e1, e2], route="affinity")
        a = list(range(1, 9))
        b = list(range(20, 28))
        for _ in range(2):
            f.submit(a + [50])
            f.submit(b + [60])
        # each prefix sticks to exactly one replica across repeats
        a_rep = [e for e in (e1, e2)
                 if any(arr[0] == 1 for arr in e.submitted)]
        b_rep = [e for e in (e1, e2)
                 if any(arr[0] == 20 for arr in e.submitted)]
        assert len(a_rep) == 1 and len(b_rep) == 1
        f.close()

    def test_affinity_short_prompt_falls_back(self):
        # a prompt under one block has no cacheable prefix: routed by
        # load, and NO pin is recorded for it
        e1, e2 = _StubEngine(blocks=(6, 8)), _StubEngine(blocks=(1, 8))
        f = EngineFleet([e1, e2], route="affinity")
        f.submit([1, 2, 3])                      # 3 < block_size 8
        assert len(e2.submitted) == 1            # load picked the freer
        assert f._pins == {}
        f.close()

    def test_affinity_spills_and_repins_on_refusal(self):
        # the pinned replica starts refusing: the request must still be
        # served (spill wins over affinity) and the pin must FOLLOW the
        # accepting replica, where the cache is now warming
        e1, e2 = _StubEngine(blocks=(1, 8)), _StubEngine(blocks=(2, 8))
        f = EngineFleet([e1, e2], route="affinity")
        p = list(range(1, 9))
        f.submit(p)                              # pins the freer: e1
        assert len(e1.submitted) == 1
        e1._refuse = QueueFullError("full")
        f.submit(p)                              # spill to e2, re-pin
        assert len(e2.submitted) == 1
        e1._refuse = None
        f.submit(p)                              # stays on e2
        assert len(e2.submitted) == 2 and len(e1.submitted) == 1
        f.close()

    def test_affinity_explicit_block_override(self):
        e1, e2 = _StubEngine(), _StubEngine()    # stubs report no block_size
        f = EngineFleet([e1, e2], route="affinity", affinity_block=4)
        for _ in range(3):
            f.submit([1, 2, 3, 4, 5])
        counts = sorted([len(e1.submitted), len(e2.submitted)])
        assert counts == [0, 3], counts
        f.close()


# ---------------------------------------------------------------------------
# the real thing: two engines over one shared model (the concurrent-
# compile storm the AotSite trace lock exists for), token parity, and
# live aggregation
# ---------------------------------------------------------------------------

class TestRealFleet:
    def test_two_replica_fleet_parity_and_stats(self, tiny_model):
        e1 = GenerationEngine(tiny_model, num_slots=2, max_len=48)
        e2 = GenerationEngine(tiny_model, num_slots=2, max_len=48)
        with EngineFleet([e1, e2], name="t13") as fleet:
            prompts = [np.arange(1, 1 + n, dtype=np.int32)
                       for n in (3, 5, 7, 4)]
            # interleaved submits: both replicas trace their steps
            # CONCURRENTLY over the SHARED model — the exact storm the
            # program-registry trace lock serializes
            handles = [fleet.submit(p, max_new_tokens=5)
                       for p in prompts]
            outs = [h.result(timeout=300) for h in handles]
            for p, o in zip(prompts, outs):
                ref = generate(tiny_model, p[None, :], max_new_tokens=5)
                np.testing.assert_array_equal(o, ref.numpy()[0])
            s = fleet.stats()
            assert s["requests_retired"] == 4
            assert s["replicas_healthy"] == 2
            assert s["ttft_ms"] is not None \
                and s["ttft_ms"]["count"] == 4
            # pooled percentile within a bucket of the raw pooling
            raw = sorted(
                e1.flight_recorder.latency_samples()["ttft_ms"]
                + e2.flight_recorder.latency_samples()["ttft_ms"])
            est = s["ttft_ms"]["p50"]
            h = M.HistValue.from_samples(raw)
            lo = 0.0
            for le in h.buckets:
                if est <= le:
                    hi = le
                    break
                lo = le
            raw_p50 = raw[max(0, math.ceil(0.5 * len(raw)) - 1)]
            assert lo <= raw_p50 <= hi or abs(est - raw_p50) <= hi - lo
            # statusz + Prometheus see both replicas while live
            txt = paddle.statusz()
            assert f"engine #{e1._eid}" in txt
            assert f"engine #{e2._eid}" in txt
            assert "t13" in txt
            prom = M.to_prometheus()
            assert f'serving_queue_depth{{engine="{e1._eid}"}}' in prom
            assert 'fleet="t13"' in prom
        # closed: both replicas drained, console empties
        assert e1._closed and e2._closed
        assert f"engine #{e1._eid}" not in paddle.statusz()


# ---------------------------------------------------------------------------
# satellite: flight-recorder auto-dump collision
# ---------------------------------------------------------------------------

class TestAutoDumpCollision:
    def test_two_dumps_two_files(self, tmp_path):
        rec = FlightRecorder(max_cycles=4)
        rec.record_cycle({"cycle_ms": 1.0, "failed": "boom A"})
        p1 = rec.auto_dump("boom A")
        rec.record_cycle({"cycle_ms": 1.0, "failed": "boom B"})
        p2 = rec.auto_dump("boom B")
        assert p1 and p2 and p1 != p2, (p1, p2)
        # BOTH postmortems survive on disk with their own reasons — the
        # first (origin) dump is the one a collision used to destroy
        with open(p1) as f:
            d1 = json.load(f)
        with open(p2) as f:
            d2 = json.load(f)
        assert d1["reason"] == "boom A"
        assert d2["reason"] == "boom B"
        assert rec.last_dump_path == p2
        assert rec.dumps == 2
        for p in (p1, p2):
            os.unlink(p)
