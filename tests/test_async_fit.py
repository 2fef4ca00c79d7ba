"""Async fast-path training loop (PR 2).

Four legs, each asserted rather than assumed:

* **buffer donation** — the jitted train step passes params/opt_state/
  buffers with ``donate_argnums``, so XLA aliases the weight update
  in-place: the OLD param buffer must be deleted after one step, while
  every downstream consumer (``save``/``load``/``train_batch``/
  ``Model.parameters``) keeps working off the rebound state;
* **windowed host sync** — ``fit()`` flushes device loss/metrics every
  ``log_freq`` steps, so the ``hapi/host_sync`` counter is
  O(steps/log_freq), not O(steps);
* **device prefetch in fit** — input batches ride through
  ``io.device_prefetch`` by default (``prefetch_batches`` counter), with
  the ``prefetch=False`` / ``FLAGS_hapi_prefetch`` escape hatch;
* **persistent compile cache** — ``framework.compile_cache.enable()``
  populates serialized-executable entries (skips cleanly when the
  installed jax lacks the knob).
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework import monitor
from paddle_tpu.io import TensorDataset
from paddle_tpu.metric import Accuracy

rng = np.random.RandomState(0)


def _data(n=64, d=16, classes=4):
    xs = rng.randn(n, d).astype(np.float32)
    ys = rng.randint(0, classes, (n, 1)).astype(np.int64)
    return xs, ys


def _model(lr=1e-2, metrics=None, d=16, classes=4):
    net = nn.Sequential(nn.Linear(d, 8), nn.ReLU(), nn.Linear(8, classes))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=lr,
                                parameters=net.parameters())
    model.prepare(opt, nn.CrossEntropyLoss(), metrics)
    return model


class TestDonatedTrainStep:
    def test_old_param_buffer_is_deleted_after_step(self):
        xs, ys = _data()
        model = _model()
        model.network.train()
        model._sync_state_from_network()
        model._build_train_step()
        name = next(iter(model._params))
        old_param = model._params[name]
        old_moment = model._opt_state["slots"][name]["moment1"]
        loss = model.train_batch([xs[:8]], [ys[:8]], return_numpy=True)
        assert np.isfinite(loss)
        # donation proof: the pre-step buffers were consumed in-place
        assert old_param.is_deleted()
        assert old_moment.is_deleted()
        # the rebound state is live and usable
        assert not model._params[name].is_deleted()

    def test_train_batch_sequence_and_parameters_access(self):
        xs, ys = _data()
        model = _model()
        l1 = model.train_batch([xs[:16]], [ys[:16]])
        for _ in range(10):
            l2 = model.train_batch([xs[:16]], [ys[:16]])
        assert l2 < l1  # same batch repeatedly: loss must drop
        # Model.parameters() syncs the functional state back into the
        # network, so the returned Tensors are live (not donated husks)
        for p in model.parameters():
            assert np.all(np.isfinite(p.numpy()))

    def test_save_load_roundtrips_optimizer_state(self, tmp_path):
        xs, ys = _data()
        model = _model()
        ds = TensorDataset([xs, ys])
        model.fit(ds, epochs=1, batch_size=8, verbose=0)
        path = str(tmp_path / "ckpt" / "m")
        model.save(path)
        assert os.path.exists(path + ".pdopt")

        model2 = _model()
        model2.load(path)
        # loaded Adam moments survive the functional re-init: a fresh
        # init would zero them, so assert a nonzero restored moment
        model2.network.train()
        model2._sync_state_from_network()
        name = next(iter(model2._params))
        m1 = np.asarray(model2._opt_state["slots"][name]["moment1"])
        assert np.abs(m1).max() > 0
        assert int(model2._opt_state["step"]) == 8  # 64/8 steps
        # and training continues from the checkpoint without error
        assert np.isfinite(model2.train_batch([xs[:8]], [ys[:8]]))

    def test_eager_trained_moments_carry_into_functional_state(self):
        """Eager opt.step() keys slots by Parameter.name; the functional
        state keys by tree name. The overlay must bridge the namespaces —
        zeroed moments under a carried step count would silently
        mis-scale Adam's bias correction."""
        xs, ys = _data()
        net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=net.parameters())
        loss_fn = nn.CrossEntropyLoss()
        for _ in range(3):  # eager training fills p.name-keyed slots
            loss = loss_fn(net(paddle.to_tensor(xs[:8])),
                           paddle.to_tensor(ys[:8]))
            loss.backward()
            opt.step()
            opt.clear_grad()
        model = paddle.Model(net)
        model.prepare(opt, loss_fn)
        model.network.train()
        model._sync_state_from_network()
        name = next(iter(model._opt_state["slots"]))
        m1 = np.asarray(model._opt_state["slots"][name]["moment1"])
        assert np.abs(m1).max() > 0, "eager moments were zeroed"
        assert int(model._opt_state["step"]) == 3

    def test_eager_step_after_fit_adopts_mirrored_slots(self):
        """After fit() mirrors tree-named slots into the optimizer, a
        raw eager opt.step() must adopt them (migrate to Parameter.name)
        — not restart from zeros at the inflated step count, and not
        leave two key families in state_dict()."""
        xs, ys = _data()
        net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=net.parameters())
        loss_fn = nn.CrossEntropyLoss()
        model = paddle.Model(net)
        model.prepare(opt, loss_fn)
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)
        loss = loss_fn(net(paddle.to_tensor(xs[:8])),
                       paddle.to_tensor(ys[:8]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        # single key family: every slot now lives under Parameter.name
        pnames = {p.name for p in net.parameters()}
        assert set(opt._slots) == pnames, set(opt._slots)
        m1 = np.asarray(next(iter(opt._slots.values()))["moment1"])
        assert np.abs(m1).max() > 0  # fit's moments survived adoption

    def test_unfreeze_uses_per_param_step_offset(self):
        """Progressive unfreezing: a newly-trainable param's Adam bias
        correction must run from its own birth step (_t0), not the
        global step history accumulated while it was frozen."""
        xs, ys = _data()
        model = _model()
        for name, p in model.network.named_parameters():
            if name.startswith("0."):
                p.stop_gradient = True
        model.fit(TensorDataset([xs, ys]), epochs=2, batch_size=8,
                  verbose=0)
        for _, p in model.network.named_parameters():
            p.stop_gradient = False
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)
        name = next(n for n in model._opt_state["slots"]
                    if n.startswith("0."))
        slots = model._opt_state["slots"][name]
        assert "_t0" in slots
        assert int(slots["_t0"]) == 16  # born after 2 epochs x 8 steps
        assert np.abs(np.asarray(slots["moment1"])).max() > 0

    def test_t0_survives_save_load(self, tmp_path):
        """The birth-step marker must round-trip through the .pdopt
        checkpoint — losing it would re-introduce the mis-scaled bias
        correction after a resume."""
        xs, ys = _data()
        model = _model()
        for name, p in model.network.named_parameters():
            if name.startswith("0."):
                p.stop_gradient = True
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)
        for _, p in model.network.named_parameters():
            p.stop_gradient = False
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)
        path = str(tmp_path / "ck")
        model.save(path)
        model2 = _model()
        model2.load(path)
        model2.network.train()
        model2._sync_state_from_network()
        name = next(n for n in model2._opt_state["slots"]
                    if n.startswith("0."))
        assert int(model2._opt_state["slots"][name]["_t0"]) == 8

    def test_train_batch_honors_stop_gradient_flip(self):
        """Freezing a param BETWEEN raw train_batch calls must re-trace
        the step: the frozen split is baked into the jit, so a stale
        split would silently keep training the frozen param."""
        xs, ys = _data()
        model = _model()
        model.train_batch([xs[:8]], [ys[:8]])
        target_name, target = next(iter(model.network.named_parameters()))
        target.stop_gradient = True
        before = np.asarray(model._params[target_name]).copy()
        model.train_batch([xs[:8]], [ys[:8]])
        after = np.asarray(model._params[target_name])
        np.testing.assert_array_equal(before, after)
        # and flipping back resumes training it
        target.stop_gradient = False
        model.train_batch([xs[:8]], [ys[:8]])
        assert not np.array_equal(
            before, np.asarray(model._params[target_name]))

    def test_metric_window_is_capped(self):
        """With metrics attached and a huge log_freq, the window still
        flushes every _METRIC_WINDOW steps so device memory pinned by
        buffered outputs stays bounded."""
        xs, ys = _data(n=128)
        model = _model(metrics=Accuracy())
        monitor.stat_reset()
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  log_freq=1000, shuffle=False, verbose=0)
        syncs = monitor.stat_get("hapi/host_sync")
        steps = 128 // 8
        assert 0 < syncs <= steps / paddle.Model._METRIC_WINDOW + 2, syncs

    def test_eager_step_right_after_load_adopts_slots(self):
        """A checkpoint written after fit() holds tree-named slots;
        load() must arm the adoption bridge so a raw eager opt.step()
        migrates them instead of zero-restarting at the carried step."""
        xs, ys = _data()
        model = _model()
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)
        import tempfile, os as _os
        d = tempfile.mkdtemp()
        model.save(_os.path.join(d, "ck"))
        model2 = _model()
        model2.load(_os.path.join(d, "ck"))
        net2, opt2 = model2.network, model2._optimizer
        loss_fn = nn.CrossEntropyLoss()
        loss = loss_fn(net2(paddle.to_tensor(xs[:8])),
                       paddle.to_tensor(ys[:8]))
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        pnames = {p.name for p in net2.parameters()}
        assert set(opt2._slots) == pnames, set(opt2._slots)

    def test_eager_steps_between_fits_are_kept(self):
        """Eager opt.step() progress between two fits must carry into
        the second fit's functional state, not be reverted."""
        xs, ys = _data()
        model = _model()
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)  # 8 steps
        net, opt = model.network, model._optimizer
        loss_fn = nn.CrossEntropyLoss()
        for _ in range(3):
            loss = loss_fn(net(paddle.to_tensor(xs[:8])),
                           paddle.to_tensor(ys[:8]))
            loss.backward()
            opt.step()
            opt.clear_grad()
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)  # 8 more
        assert int(model._opt_state["step"]) == 19  # 8 + 3 + 8

    def test_fit_after_train_batch_handles_stale_network_handles(self):
        """A donated step leaves the network Tensors holding deleted
        arrays until the next sync; the following fit() must pick up the
        functional state, not crash on the husks."""
        xs, ys = _data()
        model = _model()
        model.train_batch([xs[:8]], [ys[:8]])
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=16,
                  verbose=0)
        res = model.evaluate(TensorDataset([xs, ys]), batch_size=16,
                             verbose=0)
        assert np.isfinite(res["loss"])


class TestWindowedSync:
    def test_host_sync_counter_is_windowed(self):
        xs, ys = _data(n=128)
        ds = TensorDataset([xs, ys])
        model = _model()
        monitor.stat_reset()
        log_freq = 4
        model.fit(ds, epochs=1, batch_size=8, log_freq=log_freq,
                  shuffle=False, verbose=0)
        steps = 128 // 8
        syncs = monitor.stat_get("hapi/host_sync")
        assert 0 < syncs <= steps / log_freq + 2, syncs
        # the flush duration distribution exists for the profiler
        assert monitor.stat_histogram("hapi/host_sync_ms") is not None

    def test_metrics_accumulate_exactly_across_windows(self):
        """Windowed flushing defers metric updates but must not drop or
        double-count batches: accumulate() over fit equals a manual
        per-batch accumulation on the same weights' predictions."""
        xs, ys = _data(n=64)
        ds = TensorDataset([xs, ys])
        acc = Accuracy()
        model = _model(lr=0.0, metrics=acc)  # lr=0: weights frozen
        model.fit(ds, epochs=1, batch_size=8, log_freq=3, shuffle=False,
                  verbose=0)
        fit_acc = acc.accumulate()
        assert acc.count == 64  # every batch reached the metric once
        ref = Accuracy()
        out = model.predict(TensorDataset([xs]), batch_size=8,
                            stack_outputs=True)[0]
        ref.update(ref.compute(paddle.to_tensor(out),
                               paddle.to_tensor(ys)))
        assert abs(fit_acc - ref.accumulate()) < 1e-6

    def test_epoch_tail_is_flushed(self):
        """Steps after the last log_freq boundary still land in the
        epoch-end logs (History callback sees a fresh loss)."""
        from paddle_tpu.hapi.callbacks import History
        xs, ys = _data(n=56)  # 7 batches of 8: tail of 3 past step 4
        hist = History()
        model = _model()
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  log_freq=4, shuffle=False, verbose=0, callbacks=[hist])
        assert "loss" in hist.history
        assert np.isfinite(hist.history["loss"][0])

    def test_fit_still_learns(self):
        xs = rng.randn(128, 16).astype(np.float32)
        w = rng.randn(16, 4).astype(np.float32)
        ys = (xs @ w).argmax(-1).astype(np.int64).reshape(-1, 1)
        ds = TensorDataset([xs, ys])
        acc = Accuracy()
        model = _model(lr=5e-2, metrics=acc)
        model.fit(ds, epochs=8, batch_size=16, log_freq=2, verbose=0)
        res = model.evaluate(ds, batch_size=16, verbose=0)
        assert res["acc"] > 0.8, res


class TestPrefetchInFit:
    def test_fit_routes_through_device_prefetch(self):
        xs, ys = _data()
        model = _model()
        monitor.stat_reset()
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0)
        assert monitor.stat_get("prefetch_batches") >= 8
        assert monitor.stat_histogram("prefetch_put_ms") is not None
        assert monitor.stat_histogram("prefetch_wait_ms") is not None

    def test_prefetch_false_escape_hatch(self):
        xs, ys = _data()
        model = _model()
        monitor.stat_reset()
        model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                  verbose=0, prefetch=False)
        assert monitor.stat_get("prefetch_batches") == 0

    def test_flag_escape_hatch(self):
        xs, ys = _data()
        model = _model()
        monitor.stat_reset()
        paddle.set_flags({"FLAGS_hapi_prefetch": False})
        try:
            model.fit(TensorDataset([xs, ys]), epochs=1, batch_size=8,
                      verbose=0)
            assert monitor.stat_get("prefetch_batches") == 0
        finally:
            paddle.set_flags({"FLAGS_hapi_prefetch": True})

    def test_evaluate_prefetches_too(self):
        xs, ys = _data()
        model = _model()
        model.train_batch([xs[:8]], [ys[:8]])
        monitor.stat_reset()
        model.evaluate(TensorDataset([xs, ys]), batch_size=8, verbose=0)
        assert monitor.stat_get("prefetch_batches") >= 8


class TestCompileCache:
    def test_env_dir_is_used_and_no_other(self, tmp_path, cache_env):
        """JAX_COMPILATION_CACHE_DIR set -> the cache is that directory:
        entries land there, the in-checkout default gains none."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.framework import compile_cache

        d = str(tmp_path / "xla")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
        fixed = os.path.join(compile_cache.cache_root(), "xla")
        n_fixed = compile_cache.entries(fixed)
        assert compile_cache.default_dir() == d
        assert compile_cache.enable(min_compile_time_secs=0)
        assert compile_cache.status() == {"enabled": True, "dir": d,
                                          "reason": None}
        assert jax.config.jax_compilation_cache_dir == d
        # a shape this process has definitely not compiled yet
        f = jax.jit(lambda a: (a @ a.T).sum() * 3.5)
        float(f(jnp.ones((13, 7))))
        n1 = compile_cache.entries(d)
        assert n1 > 0
        assert compile_cache.entries(fixed) == n_fixed
        # second build of the same program adds no new entries
        g = jax.jit(lambda a: (a @ a.T).sum() * 3.5)
        float(g(jnp.ones((13, 7))))
        assert compile_cache.entries(d) == n1

    def test_unset_env_means_fixed_in_checkout_dir(self, cache_env):
        """No JAX_COMPILATION_CACHE_DIR -> <checkout>/.cache/xla: a
        fixed path (it is part of the cache key), never the home
        directory, a temp dir, a pid or the time."""
        import tempfile

        import jax
        from paddle_tpu.framework import compile_cache

        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".cache", "xla")
        assert compile_cache.default_dir() == want
        assert compile_cache.enable()
        assert compile_cache.status()["dir"] == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)
        assert not want.startswith(os.path.expanduser("~") + os.sep) \
            or repo.startswith(os.path.expanduser("~") + os.sep)
        assert not want.startswith(tempfile.gettempdir() + os.sep)

    def test_import_hook_arms_unless_switched_off_or_cpu_pinned(
            self, cache_env, monkeypatch):
        """On by default — except in a process pinned to the CPU, where
        XLA:CPU warns on every reload and there is little to save."""
        from paddle_tpu.framework import compile_cache
        from paddle_tpu.framework.flags import flag_value
        assert flag_value("FLAGS_compile_cache") is True
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert compile_cache.maybe_enable() is False
        assert "JAX_PLATFORMS=cpu" in compile_cache.status()["reason"]
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert compile_cache.maybe_enable() is True
        assert compile_cache.status()["enabled"] is True
        paddle.set_flags({"FLAGS_compile_cache": False})
        try:
            compile_cache.disable()
            assert compile_cache.maybe_enable() is False
            assert compile_cache.status()["enabled"] is False
        finally:
            paddle.set_flags({"FLAGS_compile_cache": True})

    def test_disable_is_reversible(self, cache_env):
        import jax
        from paddle_tpu.framework import compile_cache
        compile_cache.disable()
        assert compile_cache.status()["enabled"] is False
        assert jax.config.jax_enable_compilation_cache is False
        assert compile_cache.enable()
        assert jax.config.jax_enable_compilation_cache is True

    def test_autotune_cache_shares_the_root(self):
        from paddle_tpu.framework import compile_cache
        from paddle_tpu.ops import autotune_cache
        root = compile_cache.cache_root()
        if "PADDLE_AUTOTUNE_CACHE_DIR" not in os.environ:
            assert autotune_cache.cache_path().startswith(root)


class TestSatellites:
    def test_matrix_nms_no_runtime_warning_on_duplicates(self):
        """Duplicate boxes drive the linear decay to 0/0 and x/0; the
        values resolve correctly and must no longer warn."""
        import warnings
        from paddle_tpu.vision.ops import matrix_nms
        boxes = np.array([[[0, 0, 10, 10], [0, 0, 10, 10],
                           [0, 0, 10, 10]]], np.float32)
        scores = np.array([[[0.9, 0.8, 0.7]]], np.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            out, rois_num = matrix_nms(
                boxes, scores, score_threshold=0.0, post_threshold=0.0,
                nms_top_k=-1, keep_top_k=-1, background_label=-1)
        assert rois_num.numpy().sum() >= 1

    def test_cached_attention_mask_capacity_mismatch_raises(self):
        from paddle_tpu.incubate.nn import FusedMultiHeadAttention
        paddle.framework.random.seed(0)
        attn = FusedMultiHeadAttention(embed_dim=16, num_heads=2)
        attn.eval()
        x = paddle.to_tensor(rng.randn(1, 4, 16).astype(np.float32))
        cache = paddle.to_tensor(np.zeros((2, 1, 2, 8, 8), np.float32))
        bad_mask = paddle.to_tensor(
            np.zeros((1, 1, 4, 4), np.float32))  # prompt-len, not max_len
        with pytest.raises(ValueError, match="cache capacity"):
            attn(x, attn_mask=bad_mask, cache=cache)
        # a correctly padded mask (last dim == max_len) passes, and so
        # does a per-query broadcast mask (last dim 1)
        for shape in ((1, 1, 4, 8), (1, 1, 4, 1)):
            ok_mask = paddle.to_tensor(np.zeros(shape, np.float32))
            out, new_cache = attn(x, attn_mask=ok_mask, cache=cache)
            assert tuple(out.shape) == (1, 4, 16)

    def test_generate_explicit_default_conflicts_with_config(self):
        """An explicitly passed kwarg must conflict with config= even
        when its value equals the signature default (sentinel check,
        not value comparison)."""
        from paddle_tpu.models.generation import (GenerationConfig,
                                                  generate)
        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
        paddle.framework.random.seed(0)
        model = GPTForPretraining(GPTConfig.tiny())
        model.eval()
        ids = rng.randint(0, 32, (1, 4)).astype(np.int32)
        cfg = GenerationConfig(max_new_tokens=2)
        with pytest.raises(ValueError, match="not both"):
            generate(model, ids, config=cfg, temperature=1.0)  # = default
        with pytest.raises(ValueError, match="not both"):
            generate(model, ids, config=cfg, max_new_tokens=32)
        # config alone still works
        out = generate(model, ids, config=cfg)
        assert out.numpy().shape == (1, 6)
