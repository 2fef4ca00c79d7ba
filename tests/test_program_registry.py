"""Compiled-program registry (framework/program_registry.py): per-site
compile counters, cost-analysis fields tolerant of CPU backends, the MFU
math against a pinned fake peak, and the stamped build events of the
owned sites (a serving step of a plain and of a block-generation spec,
the hapi train step)."""
import json
import os
import time

import numpy as np
import pytest

import _toys
import paddle_tpu as paddle
from paddle_tpu.framework import (compile_cache, monitor,
                                  program_registry as registry)

PARTS = ("trace_ms", "lower_ms", "compile_ms")
EVENT_KEYS = {"at", *PARTS, "cache_hits", "cache_misses", "first_call_ms",
              "eqns"}


@pytest.fixture(autouse=True)
def _clean_registry():
    registry.reset()
    yield
    registry.reset()


class TestAotSite:
    def test_per_site_compile_counters(self):
        import jax.numpy as jnp

        monitor.stat_reset()

        def f(a, b):
            return a @ a + b

        site = registry.aot_site("test/matmul", f)
        x = jnp.ones((8, 8))
        site(x, x)
        site(jnp.zeros((8, 8)), x)       # same signature: no recompile
        assert site.record.compiles == 1
        site(jnp.ones((4, 4)), jnp.ones((4, 4)))   # new shape: compile
        assert site.record.compiles == 2
        assert monitor.stat_get("compile/count") == 2
        h = monitor.stat_histogram("compile/ms/test/matmul")
        assert h is not None and h["count"] == 2
        assert monitor.stat_histogram("compile/ms") is not None
        # the registry snapshot carries the same record
        assert registry.get("test/matmul").compiles == 2
        assert "test/matmul" in registry.snapshot()

    def test_cost_analysis_fields_tolerant(self):
        import jax.numpy as jnp

        site = registry.aot_site("test/cost", lambda a: (a @ a).sum())
        site(jnp.ones((16, 16)))
        rec = site.record
        # CPU provides cost analysis on this image; the contract either
        # way is "a real number or None" — never a fake -1
        assert rec.flops is None or rec.flops > 0
        assert rec.bytes_accessed is None or rec.bytes_accessed > 0
        assert rec.eqns is None or rec.eqns >= 1
        for field in ("temp_bytes", "argument_bytes", "output_bytes"):
            v = getattr(rec, field)
            assert v is None or v >= 0

    def test_static_args_select_programs(self):
        import jax.numpy as jnp

        def f(a, n):
            return a * n

        site = registry.aot_site("test/static", f, static_argnums=(1,))
        a = jnp.ones(4)
        assert float(site(a, 2)[0]) == 2.0
        assert float(site(a, 3)[0]) == 3.0   # new static: new program
        assert site.record.compiles == 2
        assert float(site(a, 2)[0]) == 2.0   # cached
        assert site.record.compiles == 2

    def test_donation_honored(self):
        import jax
        import jax.numpy as jnp

        site = registry.aot_site("test/donate", lambda a: a + 1,
                                 donate_argnums=(0,))
        x = jnp.ones(8)
        y = site(x)
        assert float(y[0]) == 2.0
        assert x.is_deleted()            # donated input consumed
        # and the site keeps serving fresh buffers
        z = site(jnp.zeros(8))
        assert float(z[0]) == 1.0
        del jax

    def test_transparent_under_tracing(self):
        import jax
        import jax.numpy as jnp

        site = registry.aot_site("test/traced", lambda a: a * 2)
        x = jnp.ones(4)
        site(x)
        before = site.record.compiles
        jaxpr = jax.make_jaxpr(lambda a: site(a) + 1)(x)
        assert len(jaxpr.jaxpr.eqns) >= 1   # pjit eqn inlined
        assert site.record.compiles == before   # tracing never compiles

    def test_note_compile_only_sites(self):
        monitor.stat_reset()
        rec = registry.note_compile("op/fake", 12.5)
        assert rec.compiles == 1 and rec.flops is None
        registry.note_compile("op/fake", 7.5, eqns=3,
                              analysis={"flops": 100.0})
        assert rec.compiles == 2 and rec.flops == 100.0 and rec.eqns == 3
        assert monitor.stat_get("compile/count") == 2


def _serve_one(eng, n=5, new=2):
    eng.submit(np.arange(1, n + 1, dtype=np.int32),
               max_new_tokens=new).result(timeout=300)
    _toys.settle(eng)


def _build_serving(model, **arguments):
    """(records, wall ms of each build as its caller saw it, call again)
    of a new engine's fused-step sites after one request: a build's
    caller is the launch's dispatch, whose record says how long it took."""
    from paddle_tpu.serving import GenerationEngine
    eng = GenerationEngine(model, **arguments)
    _serve_one(eng)
    sites = list(eng._fused_jits.values())
    assert sites and all(s.site.startswith("serving/fused[") for s in sites)
    paid = [c for c in eng.flight_recorder.snapshot()["cycles"]
            if "built_ms" in c]
    walls = {}
    for site in sites:
        at = site.record.builds[0]["at"]
        cycle, = [c for c in paid
                  if c["t"] <= at < c["t"] + c["cycle_ms"] / 1e3]
        # two sites built in one turn would share its dispatch: none do
        walls[site.site] = cycle["decode_dispatch_ms"]
    assert len({id(c) for c in paid}) == len(paid) == len(sites)
    return [s.record for s in sites], walls, lambda: _serve_one(eng), \
        eng.close


def _build_train_step():
    import paddle_tpu.nn as nn
    rng = np.random.RandomState(0)
    net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 4))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 4, (8, 1)).astype(np.int64)
    t0 = time.perf_counter()
    model.train_batch([x], [y])
    wall = (time.perf_counter() - t0) * 1e3
    rec = model._train_step_fn.record
    assert rec.site.startswith("hapi/train_step[")
    return [rec], {rec.site: wall}, lambda: model.train_batch([x], [y]), \
        lambda: None


@pytest.fixture(scope="module", params=["serving/fused of a plain spec",
                                        "serving/fused of a block spec",
                                        "hapi/train_step"])
def built(request):
    """The sites of one kind, each built once by its real caller."""
    if request.param == "hapi/train_step":
        records, walls, again, close = _build_train_step()
    elif "plain" in request.param:
        records, walls, again, close = _build_serving(
            _toys.default("gpt2"), num_slots=2, max_len=32, block_size=8,
            prefill_budget=8)
    else:
        records, walls, again, close = _build_serving(
            _toys.default("sdar"), num_slots=1, max_len=64, block_size=8,
            prefill_budget=12)
    yield records, walls, again
    close()


class TestBuildEvents:
    """What stays of a build after its spans: one stamped event a build
    in the site's record (ISSUE 52)."""

    def test_parts_are_timed_apart_and_fit_inside_the_callers_wall(
            self, built):
        records, walls, _ = built
        for rec in records:
            event, = rec.builds
            assert set(event) >= EVENT_KEYS and "fallback" not in event
            assert all(event[k] >= 0 for k in PARTS)
            assert event["first_call_ms"] >= 0
            assert event["eqns"] == rec.eqns >= 1
            assert sum(event[k] for k in PARTS) + event["first_call_ms"] \
                <= walls[rec.site]
            # on the flight recorder's clock, before now
            assert 0 < event["at"] < time.perf_counter()
            assert event["cache_hits"] >= 0 and event["cache_misses"] >= 0

    def test_the_compile_total_is_the_three_parts_sum(self, built):
        records, _, _ = built
        for rec in records:
            event, = rec.builds
            total = sum(event[k] for k in PARTS)
            assert rec.compiles == 1
            assert rec.compile_ms_total == pytest.approx(total)
            assert rec.last_compile_ms == pytest.approx(total)
            # the first call is no part of the compile histograms' wall
            assert event["first_call_ms"] is not None

    def test_a_call_that_finds_its_executable_adds_no_event(self, built):
        records, _, again = built
        before = [(rec.compiles, dict(rec.builds[0])) for rec in records]
        again()
        assert [(rec.compiles, dict(rec.builds[0])) for rec in records] \
            == before
        assert all(len(rec.builds) == 1 for rec in records)

    def test_the_snapshot_carries_the_events_as_plain_data(self, built):
        records, _, _ = built
        for rec in records:
            doc = json.loads(json.dumps(rec.as_dict()))
            assert doc["builds"] == [dict(b) for b in rec.builds]
            doc["builds"][0]["at"] = 0.0     # a copy: the record's stays
            assert rec.builds[0]["at"] > 0

    def test_the_fallback_path_has_one_wall_and_no_parts(self):
        import jax.numpy as jnp

        seen = []
        site = registry.aot_site("test/fallback", lambda a: a + 1,
                                 on_build=seen.append)
        site._fallback = True            # what a failed explicit path sets
        x = jnp.ones(4)
        assert float(site(x)[0]) == 2.0
        site(x)                          # same signature: no second event
        event, = site.record.builds
        assert seen == [event]
        assert event["fallback"] is True and event["wall_ms"] >= 0
        assert all(event[k] is None for k in
                   (*PARTS, "first_call_ms", "cache_hits", "cache_misses",
                    "eqns"))
        assert site.record.compile_ms_total == event["wall_ms"]
        site(jnp.ones(5))                # a new signature: a new event
        assert len(site.record.builds) == 2 and len(seen) == 2

    def test_a_site_keeps_its_newest_eight_events(self):
        import jax.numpy as jnp

        site = registry.aot_site("test/many", lambda a: a * 2)
        for n in range(1, 12):
            site(jnp.ones(n))
        assert site.record.compiles == 11
        assert len(site.record.builds) == 8
        stamps = [b["at"] for b in site.record.builds]
        assert stamps == sorted(stamps)
        # the newest: the last one's program is the one of 11 elements
        assert all(b["first_call_ms"] is not None
                   for b in site.record.builds)
        assert registry.snapshot()["test/many"]["compiles"] == 11
        assert len(registry.snapshot()["test/many"]["builds"]) == 8

    def test_the_owner_hears_of_a_build_once_its_first_call_returned(self):
        import jax.numpy as jnp

        seen = []

        def on_build(event):
            seen.append(dict(event))
            event["asked_by"] = "me"     # the owner's stamp stays

        site = registry.aot_site("test/owner", lambda a: a - 1,
                                 on_build=on_build)
        site(jnp.ones(3))
        site(jnp.ones(3))
        assert len(seen) == 1 and seen[0]["first_call_ms"] is not None
        assert site.record.builds[0]["asked_by"] == "me"

    def test_a_second_engine_retrieves_what_the_first_compiled(
            self, tmp_path, cache_env):
        """A fresh persistent cache: the first engine's programs are
        misses (compiled and written), the second engine's — new sites,
        the same programs — hits and nothing else."""
        from paddle_tpu.serving import GenerationEngine

        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
        assert compile_cache.enable(min_compile_time_secs=0)
        programs = []
        for _ in range(2):
            eng = GenerationEngine(_toys.default("gpt2"), num_slots=2,
                                   max_len=32, block_size=8,
                                   prefill_budget=8)
            try:
                _serve_one(eng)
                programs.append(eng.stats()["startup"]["programs"])
            finally:
                eng.close()
        cold, warm = programs
        assert len(cold) == len(warm) >= 1
        assert all(p["cache_misses"] >= 1 and p["cache_hits"] == 0
                   for p in cold)
        assert all(p["cache_hits"] >= 1 and p["cache_misses"] == 0
                   for p in warm)
        assert compile_cache.entries() >= len(cold)

    def test_lookups_counts_jaxs_own_events_once(self):
        import jax

        h0, m0 = compile_cache.lookups()
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/some/other/event")
        assert compile_cache.lookups() == (h0 + 1, m0 + 2)


class TestAnalyzeCallable:
    def test_flops_on_cpu(self):
        import jax.numpy as jnp

        res = registry.analyze_callable(lambda a: a @ a,
                                        jnp.ones((16, 16)))
        assert res is not None
        assert res["flops"] is None or res["flops"] > 0
        assert res["eqns"] is None or res["eqns"] >= 1

    def test_failure_returns_none(self):
        def broken(a):
            raise RuntimeError("cannot trace this")

        assert registry.analyze_callable(broken, np.ones(4)) is None

    def test_analyze_compiled_tolerates_stub(self):
        class _Stub:
            def cost_analysis(self):
                raise NotImplementedError

            def memory_analysis(self):
                raise NotImplementedError

        res = registry.analyze_compiled(_Stub())
        assert res["flops"] is None and res["bytes_accessed"] is None

    def test_estimate_flops_none_contract(self, monkeypatch):
        from paddle_tpu import cost_model
        import jax.numpy as jnp

        f = cost_model.estimate_flops(lambda a: a @ a, jnp.ones((8, 8)))
        assert f is None or f > 0
        # backend without analysis -> None, never -1.0
        monkeypatch.setattr(registry, "analyze_callable",
                            lambda *a, **k: {"flops": None, "eqns": 1})
        assert cost_model.estimate_flops(lambda a: a + 1,
                                         jnp.ones(4)) is None


class TestPeakFlopsAndMfu:
    def test_env_override_pins_peak(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
        assert registry.peak_flops() == 1e12
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "garbage")
        assert registry.peak_flops("cpu") is None
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS")
        assert registry.peak_flops("TPU v4") == 275e12
        assert registry.peak_flops("cpu") is None   # no honest CPU peak

    def test_fit_reports_mfu_with_pinned_peak(self, monkeypatch):
        import paddle_tpu.nn as nn
        from paddle_tpu.io import TensorDataset

        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
        monitor.stat_reset()
        rng = np.random.RandomState(0)
        net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 4))
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                            parameters=net.parameters()),
                      nn.CrossEntropyLoss())
        xs = rng.randn(32, 16).astype(np.float32)
        ys = rng.randint(0, 4, (32, 1)).astype(np.int64)
        model.fit(TensorDataset([xs, ys]), batch_size=8, epochs=1,
                  log_freq=2, shuffle=False, verbose=0)
        # the train step registered its program: compile ms + FLOPs
        rec = model._train_step_fn.record
        assert rec.compiles >= 1
        assert rec.flops is None or rec.flops > 0
        if rec.flops:
            fps = monitor.stat_histogram("hapi/flops_per_sec")
            mfu = monitor.stat_histogram("hapi/mfu")
            assert fps is not None and fps["count"] >= 1
            assert mfu is not None and mfu["count"] >= 1
            # MFU math: achieved / pinned peak, strictly positive and
            # consistent with the flops_per_sec series
            assert 0 < mfu["max"] == pytest.approx(fps["max"] / 1e12)

    def test_mfu_absent_without_peak(self, monkeypatch):
        import paddle_tpu.nn as nn
        from paddle_tpu.io import TensorDataset

        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        monitor.stat_reset()
        rng = np.random.RandomState(0)
        net = nn.Linear(8, 4)
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
                      nn.CrossEntropyLoss())
        xs = rng.randn(16, 8).astype(np.float32)
        ys = rng.randint(0, 4, (16, 1)).astype(np.int64)
        model.fit(TensorDataset([xs, ys]), batch_size=8, epochs=1,
                  shuffle=False, verbose=0)
        # CPU has no honest peak: FLOP/s may be present, MFU must not
        assert monitor.stat_histogram("hapi/mfu") is None


class TestServingFlopsPerToken:
    def test_engine_stats_compute_figures(self, monkeypatch):
        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
        from paddle_tpu.serving import GenerationEngine

        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
        paddle.framework.random.seed(0)
        model = GPTForPretraining(GPTConfig.tiny())
        model.eval()
        eng = GenerationEngine(model, num_slots=2, max_len=32,
                               min_bucket=8)
        try:
            h = eng.submit(np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=4)
            h.result(timeout=300)
            stats = eng.stats()
        finally:
            eng.close()
        assert stats.get("model_flops_per_token", 0) > 0
        assert stats.get("decode_bytes_per_token", 0) > 0
        assert stats.get("decode_tokens_per_sec", 0) > 0
        assert stats.get("serving_flops_per_sec", 0) > 0
        assert stats.get("serving_mfu", 0) > 0
        # kv bytes ride along from the ledger (satellite contract)
        assert stats["kv_pool_capacity_bytes"] > 0
        assert stats["kv_bytes_in_use"] == 0    # request retired
