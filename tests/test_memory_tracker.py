"""HBM memory tracker (profiler/memory.py): ring bounds, the
ledger-vs-device crosscheck with a mocked ``memory_stats``, and the OOM
postmortem dump round-trip via an injected RESOURCE_EXHAUSTED."""
import json
import time

import numpy as np

from paddle_tpu.profiler import memory
from paddle_tpu.profiler.memory import MemoryTracker


class TestRingAndLedger:
    def test_ring_bounds_hold(self):
        t = MemoryTracker(max_samples=8, stats_fn=lambda: {})
        for i in range(20):
            t.mark(f"m{i}", i=i)
        tl = t.timeline()
        assert len(tl) == 8                      # ring bound holds
        assert t.samples_recorded == 20          # monotonic keeps counting
        assert tl[0]["label"] == "m12" and tl[-1]["label"] == "m19"

    def test_mark_never_polls_sample_does(self):
        polls = []

        def stats():
            polls.append(1)
            return {"bytes_in_use": 7}

        t = MemoryTracker(stats_fn=stats)
        t.mark("host-only")
        assert polls == []                       # mark: no device query
        e = t.sample("polled")
        assert polls == [1] and e["bytes_in_use"] == 7

    def test_ledger_set_drop_total(self):
        t = MemoryTracker(stats_fn=lambda: {})
        t.ledger_set("a", 100)
        t.ledger_set("b", 250)
        assert t.ledger() == {"a": 100, "b": 250}
        assert t.ledger_total() == 350
        t.ledger_drop("a")
        assert t.ledger_total() == 250
        # timeline entries carry the ledger total of their moment
        t.mark("after-drop")
        assert t.timeline()[-1]["ledger_bytes"] == 250

    def test_crosscheck_against_mocked_device(self):
        t = MemoryTracker(stats_fn=lambda: {"bytes_in_use": 1200,
                                            "peak_bytes_in_use": 1500})
        t.ledger_set("params", 800)
        t.ledger_set("kv", 200)
        c = t.crosscheck()
        assert c["ledger_bytes"] == 1000
        assert c["device_bytes_in_use"] == 1200
        assert c["unexplained_bytes"] == 200
        assert abs(c["explained_ratio"] - 1000 / 1200) < 1e-9

    def test_crosscheck_without_device_stats(self):
        t = MemoryTracker(stats_fn=lambda: {})   # CPU: nothing reported
        t.ledger_set("x", 10)
        c = t.crosscheck()
        assert c["ledger_bytes"] == 10
        assert c["device_bytes_in_use"] is None
        assert c["explained_ratio"] is None

    def test_background_sampler(self):
        t = MemoryTracker(stats_fn=lambda: {"bytes_in_use": 1})
        t.start(interval=0.005)
        time.sleep(0.08)
        t.stop()
        labels = [e.get("label") for e in t.timeline()]
        assert "sampler" in labels
        n = t.samples_recorded
        time.sleep(0.03)
        assert t.samples_recorded == n           # stop really stops it


class TestOomPostmortem:
    def test_resource_exhausted_detection(self):
        assert memory.is_resource_exhausted(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                         "1073741824 bytes"))
        assert memory.is_resource_exhausted(
            ValueError("XlaRuntimeError: RESOURCE_EXHAUSTED"))
        assert not memory.is_resource_exhausted(ValueError("shape"))

    def test_dump_round_trip(self, tmp_path):
        t = MemoryTracker(stats_fn=lambda: {"bytes_in_use": 64})
        t.ledger_set("params", 48)
        t.sample("before-oom")
        err = RuntimeError("RESOURCE_EXHAUSTED: Out of memory while "
                           "trying to allocate 2 bytes")
        path = t.oom_postmortem(err, path=str(tmp_path / "oom.json"),
                                extra={"phase": "test"})
        assert path is not None and t.last_dump_path == path
        with open(path) as f:
            doc = json.load(f)
        assert "RESOURCE_EXHAUSTED" in doc["reason"]
        assert doc["phase"] == "test"
        assert doc["ledger"] == {"params": 48}
        assert doc["crosscheck"]["device_bytes_in_use"] == 64
        assert any(e.get("label") == "before-oom"
                   for e in doc["timeline"])
        # live arrays are a list of {shape,dtype,nbytes}, biggest first
        arrs = doc["largest_live_arrays"]
        assert isinstance(arrs, list)
        if len(arrs) >= 2:
            assert arrs[0]["nbytes"] >= arrs[1]["nbytes"]

    def test_dump_never_raises(self):
        t = MemoryTracker(stats_fn=lambda: {})
        # an unwritable path is swallowed, not raised (failure-handler
        # context: the postmortem must never mask the original error)
        assert t.oom_postmortem(
            RuntimeError("OOM"),
            path="/proc/definitely/not/writable/x.json") is None


class TestSchedulerOomIntegration:
    def test_injected_resource_exhausted_dumps(self, tmp_path,
                                               monkeypatch):
        """A scheduler step failing with RESOURCE_EXHAUSTED leaves BOTH
        postmortems behind: the flight recorder's and the memory
        tracker's (pointing at the recorder dump), without killing the
        loop or masking the request error."""
        from _mock_serving import MockDevice, mock_pool
        from paddle_tpu.serving.scheduler import (GenerationRequest,
                                                  Scheduler)

        dumps = {}
        real = memory.tracker().oom_postmortem

        def capture(error=None, path=None, extra=None):
            p = real(error,
                     path=str(tmp_path / "sched_oom.json"), extra=extra)
            dumps["path"] = p
            return p

        monkeypatch.setattr(memory.tracker(), "oom_postmortem", capture)
        pool = mock_pool(slots=2, max_len=32)

        def step(slot_requests, plan, prev=None):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating KV block")

        sched = Scheduler(pool, MockDevice(pool).do_prefill, step)
        req = sched.submit(GenerationRequest(np.ones(4, np.int32), 3))
        try:
            req.result(timeout=60)
            raised = False
        except RuntimeError as e:
            raised = "RESOURCE_EXHAUSTED" in str(e)
        sched.close()
        assert raised                        # original error reached caller
        assert dumps.get("path") is not None
        with open(dumps["path"]) as f:
            doc = json.load(f)
        assert doc["phase"] == "serving.scheduler"
        assert "flight_recorder" in doc
        # the serving cycle watermarks made it into the timeline
        assert any(e.get("label") == "serving/cycle"
                   for e in doc["timeline"])


class TestPoolLedgerIntegration:
    def test_pool_publishes_bytes(self):
        from paddle_tpu.serving.paging import PagedKVPool

        # 4 slots x 16 tokens at 8 a block: 8 blocks + the scratch one
        pool = PagedKVPool(num_layers=2, num_slots=4, num_heads=2,
                           max_len=16, head_dim=4, block_size=8,
                           dtype="float32")
        led = memory.ledger()
        cap = led[f"{pool.ledger_key}/capacity"]
        assert cap == pool.capacity_bytes == 2 * 9 * 2 * 8 * (2 * 4) * 4
        assert led[f"{pool.ledger_key}/in_use"] == 0
        s = pool.alloc()
        pool.admit_fresh(s, 16)              # a slot's worst case: 2 blocks
        assert memory.ledger()[f"{pool.ledger_key}/in_use"] \
            == 2 * (cap // 9)
        pool.free(s)
        assert memory.ledger()[f"{pool.ledger_key}/in_use"] == 0
        # alloc/free left labeled watermarks behind
        labels = [e.get("label") for e in memory.timeline()]
        assert "kv/alloc" in labels and "kv/free" in labels
        pool.drop_ledger()
        assert f"{pool.ledger_key}/capacity" not in memory.ledger()

    def test_paged_pool_block_granular(self):
        from paddle_tpu.serving.paging import PagedKVPool

        pool = PagedKVPool(num_layers=1, num_slots=2, num_heads=1,
                           max_len=32, head_dim=2, block_size=8,
                           num_blocks=8, dtype="float32")
        assert pool.block_bytes == 1 * 2 * 1 * 8 * 2 * 4
        slot = pool.alloc()
        pool.admit_fresh(slot, 12)           # 2 blocks
        assert pool.bytes_in_use == 2 * pool.block_bytes
        assert memory.ledger()[f"{pool.ledger_key}/in_use"] == \
            2 * pool.block_bytes
        pool.set_slot(slot, pos=12, lo=0)
        pool.free(slot)
        assert pool.bytes_in_use == 0
        pool.drop_ledger()
