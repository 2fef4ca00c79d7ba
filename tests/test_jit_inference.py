"""paddle.jit + inference path tests (r1 verdict item 4).

Covers: to_static compile+call, jit.save -> StableHLO artifact on disk,
jit.load predictor parity, load in a FRESH PROCESS (no model code), the
inference Config/Predictor facade, and static.save/load_inference_model."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.static import InputSpec

rng = np.random.RandomState(0)


def _small_model():
    paddle.framework.random.seed(0)
    return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))


class TestToStatic:
    def test_function_wrap_and_call(self):
        import paddle_tpu.nn.functional as F

        @paddle.jit.to_static
        def f(x, y):
            return F.relu(x) + y * 2.0

        x = paddle.to_tensor(rng.randn(4, 4).astype(np.float32))
        y = paddle.to_tensor(rng.randn(4, 4).astype(np.float32))
        out = f(x, y)
        ref = np.maximum(x.numpy(), 0) + y.numpy() * 2.0
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-6)

    def test_layer_decoration(self):
        model = _small_model()
        x = paddle.to_tensor(rng.randn(2, 8).astype(np.float32))
        ref = model(x).numpy()
        model = paddle.jit.to_static(
            model, input_spec=[InputSpec([-1, 8], "float32", "x")])
        out = model(x).numpy()
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_layer_trainable_and_not_stale(self):
        # to_static layer must (a) train through the tape, (b) reflect
        # weight updates in later inference calls (r2 review finding)
        import paddle_tpu.nn.functional as F
        model = paddle.jit.to_static(_small_model())
        x = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
        y = paddle.to_tensor(rng.randn(4, 4).astype(np.float32))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        before = None
        with paddle.no_grad():
            before = model(x).numpy()
        loss = F.mse_loss(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        with paddle.no_grad():
            after = model(x).numpy()
        assert not np.allclose(before, after), "stale weights after step"

    def test_tuple_outputs(self):
        @paddle.jit.to_static
        def f(x):
            return x + 1.0, x * 2.0

        x = paddle.to_tensor(np.ones((3,), np.float32))
        a, b = f(x)
        np.testing.assert_allclose(a.numpy(), np.full(3, 2.0))
        np.testing.assert_allclose(b.numpy(), np.full(3, 2.0))


class TestJitSaveLoad:
    def test_round_trip_same_process(self, tmp_path):
        model = _small_model()
        x = rng.randn(4, 8).astype(np.float32)
        ref = model(paddle.to_tensor(x)).numpy()
        prefix = str(tmp_path / "m")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([4, 8], "float32", "x")])
        assert os.path.exists(prefix + ".pdmodel")
        assert os.path.exists(prefix + ".pdiparams")
        loaded = paddle.jit.load(prefix)
        out = loaded(x)
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-6)
        # weights round-trip too
        sd = loaded.state_dict()
        assert any("weight" in k for k in sd)

    def test_dynamic_batch_export(self, tmp_path):
        model = _small_model()
        prefix = str(tmp_path / "dyn")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([-1, 8], "float32", "x")])
        loaded = paddle.jit.load(prefix)
        for bs in (1, 3, 16):
            x = rng.randn(bs, 8).astype(np.float32)
            ref = model(paddle.to_tensor(x)).numpy()
            np.testing.assert_allclose(loaded(x).numpy(), ref,
                                       rtol=1e-5, atol=1e-6)

    def test_load_in_fresh_process(self, tmp_path):
        model = _small_model()
        x = rng.randn(2, 8).astype(np.float32)
        ref = model(paddle.to_tensor(x)).numpy()
        prefix = str(tmp_path / "m")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([2, 8], "float32", "x")])
        np.save(str(tmp_path / "x.npy"), x)
        code = (
            "import numpy as np\n"
            "import paddle_tpu as paddle\n"
            f"x = np.load({str(tmp_path / 'x.npy')!r})\n"
            f"layer = paddle.jit.load({prefix!r})\n"
            "out = layer(x)\n"
            f"np.save({str(tmp_path / 'out.npy')!r}, out.numpy())\n")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=110)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = np.load(str(tmp_path / "out.npy"))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_training_mode_restored(self, tmp_path):
        model = _small_model()
        model.train()
        paddle.jit.save(model, str(tmp_path / "m"),
                        input_spec=[InputSpec([1, 8], "float32")])
        assert model.training  # save flips to eval only for the trace


class TestInferencePredictor:
    def test_config_predictor_run(self, tmp_path):
        model = _small_model()
        x = rng.randn(3, 8).astype(np.float32)
        ref = model(paddle.to_tensor(x)).numpy()
        prefix = str(tmp_path / "m")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([3, 8], "float32", "input")])
        from paddle_tpu.inference import Config, create_predictor
        cfg = Config(prefix + ".pdmodel")
        pred = create_predictor(cfg)
        assert pred.get_input_names() == ["input"]
        h = pred.get_input_handle("input")
        h.copy_from_cpu(x)
        outs = pred.run()
        np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-6)
        oh = pred.get_output_handle(pred.get_output_names()[0])
        np.testing.assert_allclose(oh.copy_to_cpu(), ref, rtol=1e-5,
                                   atol=1e-6)


class TestStaticInferenceModel:
    def test_save_load_inference_model(self, tmp_path):
        model = _small_model()
        x = rng.randn(2, 8).astype(np.float32)
        ref = model(paddle.to_tensor(x)).numpy()
        prefix = str(tmp_path / "inf")
        paddle.static.save_inference_model(
            prefix, [InputSpec([2, 8], "float32", "x")], model)
        layer, feed_names, _ = paddle.static.load_inference_model(prefix)
        assert feed_names == ["x"]
        np.testing.assert_allclose(layer(x).numpy(), ref, rtol=1e-5,
                                   atol=1e-6)


def test_traced_layer_roundtrip(tmp_path):
    """Legacy TracedLayer.trace -> save_inference_model -> jit.load
    (reference fluid/dygraph/jit.py TracedLayer)."""
    from paddle_tpu import jit

    paddle.framework.random.seed(0)
    net = paddle.nn.Linear(4, 2)
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 4)
                         .astype("float32"))
    out, traced = jit.TracedLayer.trace(net, [x])
    np.testing.assert_allclose(out.numpy(), net(x).numpy())
    path = str(tmp_path / "traced")
    traced.save_inference_model(path)
    loaded = jit.load(path)
    net.eval()
    np.testing.assert_allclose(np.asarray(loaded(x).numpy()),
                               net(x).numpy(), rtol=1e-5, atol=1e-5)
    jit.set_verbosity(1)
    jit.set_code_level(100)
