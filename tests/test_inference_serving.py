"""Serve-path hardening: batched serving engine + loud inert knobs.

Reference: paddle/fluid/inference/api/analysis_predictor.cc (the serve
loop), analysis_config.cc (the GPU/TRT knob surface, inert on TPU).
"""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import BatchingEngine, Config, create_predictor


class _EchoPredictor:
    """Predictor stand-in recording the batch sizes it was run with."""

    def __init__(self):
        self.batches = []
        self.lock = threading.Lock()

    def run(self, feeds):
        with self.lock:
            self.batches.append(feeds[0].shape[0])
        return [feeds[0] * 2.0]


class TestBatchingEngine:
    def test_single_request_roundtrip(self):
        eng = BatchingEngine(_EchoPredictor(), max_delay_ms=0)
        x = np.arange(6, dtype="float32").reshape(2, 3)
        (out,) = eng.infer(x)
        np.testing.assert_allclose(out, x * 2)
        eng.close()

    def test_concurrent_requests_are_batched(self):
        pred = _EchoPredictor()
        eng = BatchingEngine(pred, max_batch_size=16, max_delay_ms=50)
        results = {}

        def client(i):
            x = np.full((1, 4), float(i), "float32")
            (out,) = eng.infer(x)
            results[i] = out

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.close()
        for i in range(8):
            np.testing.assert_allclose(results[i], 2.0 * i)
        # at least one multi-request batch formed, and every run used a
        # power-of-two bucket (one compile per bucket)
        assert max(pred.batches) > 1, pred.batches
        assert all(b & (b - 1) == 0 for b in pred.batches), pred.batches

    def test_padding_rows_are_dropped(self):
        pred = _EchoPredictor()
        eng = BatchingEngine(pred, max_batch_size=8, max_delay_ms=0)
        x = np.ones((3, 2), "float32")     # pads to bucket 4
        (out,) = eng.infer(x)
        assert out.shape == (3, 2)
        assert pred.batches == [4]
        eng.close()

    def test_error_propagates_to_caller(self):
        class _Boom:
            def run(self, feeds):
                raise RuntimeError("kaboom")

        eng = BatchingEngine(_Boom(), max_delay_ms=0)
        with pytest.raises(RuntimeError, match="kaboom"):
            eng.infer(np.ones((1, 2), "float32"))
        eng.close()

    def test_closed_engine_rejects(self):
        eng = BatchingEngine(_EchoPredictor(), max_delay_ms=0)
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.infer(np.ones((1, 1), "float32"))

    def test_end_to_end_with_real_predictor(self, tmp_path):
        """jit.save -> create_predictor -> BatchingEngine round-trip."""
        from paddle_tpu import inference, jit
        from paddle_tpu.static import InputSpec

        paddle.framework.random.seed(0)
        net = paddle.nn.Linear(4, 2)
        net.eval()
        path = str(tmp_path / "m")
        jit.save(net, path, input_spec=[InputSpec([None, 4], "float32")])
        pred = inference.create_predictor(Config(path + ".pdmodel"))
        eng = BatchingEngine(pred, max_batch_size=8, max_delay_ms=0)
        x = np.random.RandomState(0).randn(3, 4).astype("float32")
        (out,) = eng.infer(x)
        expect = net(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)
        eng.close()


class TestRuntimeKeyedSamplingExport:
    """save_for_serving(runtime_key=True): the PRNG key is a RUNTIME
    input of the exported decode artifact, so served sampling
    re-randomizes per request — two calls on the same prompt can
    differ (the standing per-request-sampling VERDICT item; also the
    property spec-decode rejection sampling relies on)."""

    def _model(self):
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        paddle.framework.random.seed(0)
        m = GPTForPretraining(GPTConfig.tiny())
        m.eval()
        return m

    def test_validation_is_independent_of_export_backend(self, tmp_path):
        from paddle_tpu.models import save_for_serving
        m = self._model()
        with pytest.raises(ValueError, match="do_sample"):
            save_for_serving(m, str(tmp_path / "a"), batch=1,
                             prompt_len=4, runtime_key=True)
        with pytest.raises(ValueError, match="seed"):
            save_for_serving(m, str(tmp_path / "b"), batch=1,
                             prompt_len=4, runtime_key=True,
                             do_sample=True, seed=3)
        with pytest.raises(ValueError, match="num_beams"):
            save_for_serving(m, str(tmp_path / "c"), batch=1,
                             prompt_len=4, runtime_key=True,
                             do_sample=True, num_beams=2)
        with pytest.raises(ValueError, match="unsupported"):
            save_for_serving(m, str(tmp_path / "d"), batch=1,
                             prompt_len=4, runtime_key=True,
                             do_sample=True, bogus_kwarg=1)
        # the baked-constant path still demands an explicit choice,
        # and now names the runtime_key alternative
        with pytest.raises(ValueError, match="runtime_key"):
            save_for_serving(m, str(tmp_path / "e"), batch=1,
                             prompt_len=4, do_sample=True)

    def test_two_calls_same_prompt_differ(self, tmp_path):
        import jax
        if not hasattr(jax, "export"):
            pytest.skip("jit.save needs jax.export (known jax-version "
                        "drift on this image)")
        from paddle_tpu import jit
        from paddle_tpu.models import generate, save_for_serving
        m = self._model()
        path = str(tmp_path / "keyed")
        save_for_serving(m, path, batch=2, prompt_len=8,
                         max_new_tokens=5, do_sample=True,
                         temperature=0.8, runtime_key=True)
        loaded = jit.load(path)
        ids = np.random.RandomState(0).randint(
            1, 256, (2, 8)).astype(np.int32)
        k1 = np.asarray(jax.random.PRNGKey(1))
        k2 = np.asarray(jax.random.PRNGKey(2))
        o1 = loaded(paddle.to_tensor(ids), paddle.to_tensor(k1)).numpy()
        o1b = loaded(paddle.to_tensor(ids), paddle.to_tensor(k1)).numpy()
        o2 = loaded(paddle.to_tensor(ids), paddle.to_tensor(k2)).numpy()
        # same key reproduces; different keys re-randomize
        np.testing.assert_array_equal(o1, o1b)
        assert not np.array_equal(o1, o2)
        # the runtime key is the live path's seed: key=PRNGKey(s)
        # matches generate(seed=s) token for token
        ref = generate(m, ids, max_new_tokens=5, do_sample=True,
                       temperature=0.8, seed=1).numpy()
        np.testing.assert_array_equal(o1, ref)
        # the C-API-compatible Predictor serves the two-input artifact
        pred = create_predictor(Config(path + ".pdmodel"))
        np.testing.assert_array_equal(
            np.asarray(pred.run([ids, k1])[0]), o1)


class TestInertKnobsWarn:
    def test_trt_and_gpu_knobs_warn(self):
        cfg = Config()
        with pytest.warns(UserWarning, match="no effect"):
            cfg.enable_tensorrt_engine(workspace_size=1 << 30)
        with pytest.warns(UserWarning, match="no effect"):
            cfg.enable_use_gpu(100, 0)
        with pytest.warns(UserWarning, match="no effect"):
            cfg.switch_ir_optim(False)
        with pytest.warns(UserWarning, match="no effect"):
            cfg.enable_memory_optim()

    def test_disable_gpu_is_silent(self):
        import warnings
        cfg = Config()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            cfg.disable_gpu()     # already the TPU truth: no warning


class TestEngineRobustness:
    def test_malformed_request_fails_cleanly_engine_survives(self):
        eng = BatchingEngine(_EchoPredictor(), max_delay_ms=0)
        with pytest.raises(ValueError, match="batch dimension"):
            eng.infer(np.float32(1.0))          # 0-d array
        # the worker is still alive and serving
        (out,) = eng.infer(np.ones((2, 2), "float32"))
        np.testing.assert_allclose(out, 2.0)
        eng.close()

    def test_oversize_batches_use_pow2_buckets(self):
        pred = _EchoPredictor()
        eng = BatchingEngine(pred, max_batch_size=8, max_delay_ms=0)
        for n in (33, 47):
            eng.infer(np.ones((n, 2), "float32"))
        eng.close()
        assert pred.batches == [64, 64]   # one compile bucket, not two

    def test_poisoned_request_does_not_fail_its_batch(self):
        """One request the predictor chokes on must fail ALONE: its
        co-riders are retried as singles and succeed."""

        class _NaNAllergic:
            def __init__(self):
                self.calls = []

            def run(self, feeds):
                self.calls.append(feeds[0].shape[0])
                if np.isnan(feeds[0]).any():
                    raise RuntimeError("poisoned input")
                return [feeds[0] * 2.0]

        pred = _NaNAllergic()
        eng = BatchingEngine(pred, max_batch_size=16, max_delay_ms=100)
        results, errors = {}, {}
        barrier = threading.Barrier(4)

        def client(i):
            x = np.full((1, 4), float(i), "float32")
            if i == 2:
                x[:] = np.nan            # the poisoned rider
            barrier.wait()               # force one gathered batch
            try:
                (out,) = eng.infer(x)
                results[i] = out
            except RuntimeError as e:
                errors[i] = e

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.close()
        assert set(errors) == {2}
        assert "poisoned" in str(errors[2])
        for i in (0, 1, 3):
            np.testing.assert_allclose(results[i], 2.0 * i)

    def test_close_drains_in_flight_requests(self):
        """close() must serve everything already submitted, not abandon
        it — the sentinel queues behind the work."""

        class _Slow:
            def run(self, feeds):
                import time
                time.sleep(0.15)
                return [feeds[0] * 2.0]

        eng = BatchingEngine(_Slow(), max_batch_size=1, max_delay_ms=0)
        results = {}

        def client(i):
            (out,) = eng.infer(np.full((1, 2), float(i), "float32"))
            results[i] = out

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        import time
        time.sleep(0.05)         # requests are queued, first is running
        eng.close()              # untimed close = graceful drain
        for t in threads:
            t.join()
        assert len(results) == 3
        for i in range(3):
            np.testing.assert_allclose(results[i], 2.0 * i)
