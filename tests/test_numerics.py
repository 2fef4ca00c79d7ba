"""Training numerics health (profiler/numerics.py + the fit wiring).

The contract under test (ISSUE 10): the NaN/Inf audit is COMPILED INTO
the donated train step and fetched only at the existing flush windows —
``hapi/host_sync`` is IDENTICAL with numerics on or off and a warm
re-fit compiles zero additional programs; injected nonfinite gradients
are detected at the exact step with the blamed layer group in every
mode; ``halt`` raises :class:`NumericsError` AFTER the anomaly
postmortem lands and ``on_train_abort`` runs; the robust-z loss-spike
detector fires on a seeded spike and stays quiet on a noisy-but-healthy
run; the serving twin (per-cycle logits-finite sentinel riding the one
windowed fetch) trips on a bad decode without killing the scheduler
loop; and the flight-recorder rings stay bounded while their monotonic
counters keep counting.
"""
import json
import os
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework import monitor
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.io import TensorDataset
from paddle_tpu.profiler import NumericsError, numerics

N_BATCHES, LOG_FREQ, BATCH = 8, 4, 8


def _make_model(clip=None, seed=0):
    paddle.framework.random.seed(seed)
    net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 4))
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.Adam(learning_rate=1e-3,
                              parameters=net.parameters(),
                              grad_clip=clip),
        nn.CrossEntropyLoss())
    return model


def _data(seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(BATCH * N_BATCHES, 16).astype(np.float32)
    ys = rng.randint(0, 4, (BATCH * N_BATCHES, 1)).astype(np.int64)
    return TensorDataset([xs, ys])


def _fit(model, data, mode, **kw):
    kw.setdefault("log_freq", LOG_FREQ)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model.fit(data, batch_size=BATCH, epochs=1, shuffle=False,
                  verbose=0, numerics=mode, **kw)


# ---------------------------------------------------------------------------
# the device audit itself (unit: exact blame, layout, grouping)
# ---------------------------------------------------------------------------

class TestAudit:
    def test_blames_exactly_the_nonfinite_group(self):
        import jax.numpy as jnp
        layout = numerics.AuditLayout.build(
            ["a.weight", "a.bias", "b.weight"])
        grads = {"a.weight": jnp.ones((2, 2)), "a.bias": jnp.ones(2),
                 "b.weight": jnp.array([1.0, np.nan, np.inf])}
        params = {k: jnp.ones_like(v) for k, v in grads.items()}
        new = {k: v * 0.9 for k, v in params.items()}
        vec = numerics.build_audit(jnp.float32(1.5), grads, params, new,
                                   layout)
        rec = numerics.decode_audit(np.asarray(vec), layout)
        assert rec["nonfinite_groups"] == {"b": 2}
        assert rec["loss_finite"] and rec["update_finite"]
        assert not rec["grads_finite"] and not rec["finite"]
        # finite norms still report (param/update side is healthy):
        # 9 unit params -> norm 3
        assert rec["param_norm"] == pytest.approx(3.0, rel=1e-5)
        assert rec["update_ratio"] == pytest.approx(0.1, rel=1e-4)

    def test_clean_audit_and_clip_reuse_values(self):
        import jax.numpy as jnp
        layout = numerics.AuditLayout.build(["w"])
        grads = {"w": jnp.asarray([3.0, 4.0])}      # norm 5
        params = {"w": jnp.asarray([1.0, 0.0])}
        new = {"w": jnp.asarray([0.9, -0.1])}
        vec = numerics.build_audit(
            jnp.float32(0.25), grads, params, new, layout,
            grad_norm=jnp.float32(5.0), clipped_norm=jnp.float32(1.0))
        rec = numerics.decode_audit(np.asarray(vec), layout)
        assert rec["finite"] and rec["finite_bits"] == numerics.FINITE_ALL
        assert rec["grad_norm"] == 5.0
        assert rec["clip_ratio"] == pytest.approx(0.2)
        assert rec["loss"] == 0.25
        assert rec["nonfinite_groups"] == {}

    def test_group_params_coarsens_to_cap(self):
        # parent-path grouping first...
        g = numerics.group_params(["0.weight", "0.bias", "2.weight"])
        assert set(g) == {"0", "2"}
        # ...coarsening kicks in past the cap (first component wins)
        many = [f"blocks.{i}.attn.{p}" for i in range(40)
                for p in ("q.weight", "k.weight")]
        g = numerics.group_params(many, max_groups=8)
        assert len(g) <= 8
        assert sum(len(v) for v in g.values()) == len(many)
        # a FLAT net defeats every prefix keyfn — the cap is a hard
        # bound on the audit vector's size, enforced by range-merging
        flat = [f"{i}.{p}" for i in range(40) for p in ("weight", "bias")]
        g = numerics.group_params(flat, max_groups=8)
        assert len(g) <= 8
        assert sum(len(v) for v in g.values()) == len(flat)
        assert any(".." in k for k in g)     # span labels, not opaque


# ---------------------------------------------------------------------------
# detection across modes (e2e through fit, injected inf)
# ---------------------------------------------------------------------------

class TestDetection:
    def test_record_mode_detects_at_exact_step(self):
        model, data = _make_model(), _data()
        monitor.stat_reset()
        _fit(model, data, "record")          # warm + build recorder
        rec = model._numerics_recorder
        assert rec.anomalies_recorded == 0
        before = monitor.stat_get("hapi/nonfinite_steps")
        inject_at = model._step_counter + 3
        model._numerics_inject_inf_at = inject_at
        _fit(model, data, "record")
        model._numerics_inject_inf_at = None
        anoms = [a for a in rec.anomaly_list() if a["kind"] == "nonfinite"]
        assert anoms, rec.anomaly_list()
        assert anoms[0]["step"] == inject_at
        assert anoms[0]["blamed_groups"], anoms[0]
        assert monitor.stat_get("hapi/nonfinite_steps") > before
        # record mode never dumps or raises
        assert rec.dumps == 0

    def test_warn_mode_dumps_postmortem_and_survives(self):
        model, data = _make_model(), _data()
        _fit(model, data, "record")
        inject_at = model._step_counter + 2
        model._numerics_inject_inf_at = inject_at
        with pytest.warns(RuntimeWarning, match="numerics anomaly"):
            model.fit(data, batch_size=BATCH, epochs=1, log_freq=LOG_FREQ,
                      shuffle=False, verbose=0, numerics="warn")
        model._numerics_inject_inf_at = None
        rec = model._numerics_recorder
        assert rec.dumps > 0 and rec.last_dump_path
        with open(rec.last_dump_path) as f:
            doc = json.load(f)
        assert doc["anomaly"]["kind"] == "nonfinite"
        # NaN propagates, so later windows re-dump with THEIR anomaly —
        # the artifact's anomaly ring still pins the ORIGIN step
        assert doc["anomalies"][0]["kind"] == "nonfinite"
        assert doc["anomalies"][0]["step"] == inject_at
        assert doc["blamed_groups"]
        assert doc["ring"] and doc["ring"][-1]["step"] >= inject_at
        # the PR-7 memory postmortem rode along, path included
        assert doc["memory_postmortem"] and \
            os.path.exists(doc["memory_postmortem"])
        assert "hapi/grad_norm" in doc["monitor"]["histograms"]

    def test_halt_raises_after_postmortem_and_abort_runs(self):
        model, data = _make_model(), _data()
        _fit(model, data, "record")
        inject_at = model._step_counter + 2

        aborted = []

        class Probe(Callback):
            def on_train_abort(self):
                aborted.append(True)

        model._numerics_inject_inf_at = inject_at
        with pytest.raises(NumericsError, match=f"step {inject_at}"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                model.fit(data, batch_size=BATCH, epochs=1,
                          log_freq=LOG_FREQ, shuffle=False, verbose=0,
                          numerics="halt", callbacks=[Probe()])
        model._numerics_inject_inf_at = None
        assert aborted == [True]
        rec = model._numerics_recorder
        # the postmortem landed BEFORE the raise
        assert rec.last_dump_path and os.path.exists(rec.last_dump_path)
        anoms = [a for a in rec.anomaly_list() if a["kind"] == "nonfinite"]
        assert anoms[0]["step"] == inject_at

    def test_policy_switch_reuses_the_program(self):
        # record/warn/halt share ONE compiled program per signature —
        # the policy is host-side at the flush window
        model, data = _make_model(), _data()
        _fit(model, data, "record")
        c0 = monitor.stat_get("compile/count")
        _fit(model, data, "warn")
        _fit(model, data, "halt")
        assert monitor.stat_get("compile/count") == c0

    def test_invalid_mode_rejected(self):
        model, data = _make_model(), _data()
        with pytest.raises(ValueError, match="numerics"):
            model.fit(data, batch_size=BATCH, verbose=0,
                      numerics="loudly")


# ---------------------------------------------------------------------------
# the zero-cost contract: identical sync budget, no extra programs
# ---------------------------------------------------------------------------

class TestZeroCost:
    def test_host_sync_identical_on_vs_off(self):
        data = _data()
        m_off, m_on = _make_model(seed=0), _make_model(seed=0)
        s0 = monitor.stat_get("hapi/host_sync")
        _fit(m_off, data, "off")
        off_syncs = monitor.stat_get("hapi/host_sync") - s0
        s1 = monitor.stat_get("hapi/host_sync")
        _fit(m_on, data, "record")
        on_syncs = monitor.stat_get("hapi/host_sync") - s1
        assert on_syncs == off_syncs
        assert 0 < on_syncs <= N_BATCHES / LOG_FREQ + 2
        # the audit never changes the training math: identical init +
        # identical batches -> identical trained params
        for (n, a), (_, b) in zip(
                sorted(m_off._params.items()),
                sorted(m_on._params.items())):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, err_msg=n)

    def test_warm_refit_compiles_nothing(self):
        model, data = _make_model(), _data()
        _fit(model, data, "record")
        c0 = monitor.stat_get("compile/count")
        _fit(model, data, "record")
        assert monitor.stat_get("compile/count") == c0

    def test_telemetry_live_and_clip_ratio_saturates(self):
        # a tight global-norm clip: hapi/grad_clip_ratio exposes the
        # silent saturation (ratio well below 1), and the unclipped
        # norm comes from the clip path's own reduction
        monitor.stat_reset()
        model = _make_model(clip=nn.ClipGradByGlobalNorm(1e-3))
        _fit(model, _data(), "record")
        gn = monitor.stat_histogram("hapi/grad_norm")
        cr = monitor.stat_histogram("hapi/grad_clip_ratio")
        ur = monitor.stat_histogram("hapi/update_ratio")
        assert gn is not None and gn["count"] == N_BATCHES
        assert ur is not None and ur["min"] > 0
        assert cr is not None and cr["max"] < 1.0   # always clipping
        recs = model._numerics_recorder.snapshot()["records"]
        assert len(recs) == N_BATCHES
        last = recs[-1]
        assert last["clipped_grad_norm"] == pytest.approx(
            min(last["grad_norm"], 1e-3), rel=1e-4)
        assert last["retrace_delta"] >= 0 and "ledger_bytes" in last

    def test_progbar_prints_grad_norm(self, capsys):
        model, data = _make_model(), _data()
        _fit(model, data, "record", )
        # second epoch-style run with verbose on, warm program
        from paddle_tpu.amp import GradScaler
        scaler = GradScaler(enable=True, init_loss_scaling=8.0)
        model.fit(data, batch_size=BATCH, epochs=1, log_freq=LOG_FREQ,
                  shuffle=False, verbose=2, numerics="record")
        out = capsys.readouterr().out
        assert "grad_norm:" in out
        assert "loss_scale:" in out   # active scaler state rides along
        recs = model._numerics_recorder.snapshot()["records"]
        assert recs[-1]["scaler"]["scale"] == 8.0
        del scaler


# ---------------------------------------------------------------------------
# loss-spike detector (robust z over the ring)
# ---------------------------------------------------------------------------

def _vec(loss, layout, gnorm=1.0, bits=numerics.FINITE_ALL):
    v = np.zeros(layout.size, np.float32)
    v[numerics.IDX_BITS] = bits
    v[numerics.IDX_LOSS] = loss
    v[numerics.IDX_GRAD_NORM] = gnorm
    v[numerics.IDX_CLIPPED_NORM] = gnorm
    v[numerics.IDX_PARAM_NORM] = 1.0
    v[numerics.IDX_UPDATE_NORM] = 1e-3
    return v


class TestSpikeDetector:
    def test_fires_on_seeded_spike_and_dumps_without_killing(self):
        layout = numerics.AuditLayout.build([])
        rec = numerics.NumericsRecorder(spike_min_history=8)
        rng = np.random.RandomState(7)
        losses = list(1.0 + 0.05 * rng.randn(16))
        step = 0
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for loss in losses:
                step += 1
                rec.record_window([(step, _vec(loss, layout))], layout,
                                  mode="warn")
        assert rec.anomalies_recorded == 0
        # the seeded spike: fires in warn AND halt mode, never raises
        with pytest.warns(RuntimeWarning, match="loss_spike"):
            rec.record_window([(step + 1, _vec(50.0, layout))], layout,
                              mode="halt")
        anoms = rec.anomaly_list()
        assert anoms[-1]["kind"] == "loss_spike"
        assert anoms[-1]["step"] == step + 1
        assert anoms[-1]["zscore"] >= 8.0
        assert rec.dumps > 0 and rec.last_dump_path
        assert monitor.stat_get("hapi/loss_spikes") > 0

    def test_quiet_on_noisy_but_healthy_run(self):
        layout = numerics.AuditLayout.build([])
        rec = numerics.NumericsRecorder(spike_min_history=8)
        rng = np.random.RandomState(3)
        # noisy but healthy: ~3-sigma excursions stay under the z=8 bar
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            for step in range(1, 65):
                loss = 1.0 + 0.2 * rng.randn()
                rec.record_window([(step, _vec(loss, layout))], layout,
                                  mode="warn")
        assert rec.anomalies_recorded == 0
        assert rec.dumps == 0

    def test_baseline_resets_per_run(self):
        # a new fit's healthy-but-different starting loss must not
        # z-score against the PREVIOUS run's converged median — the
        # ring persists (flight-recorder continuity), the baseline
        # does not
        layout = numerics.AuditLayout.build([])
        rec = numerics.NumericsRecorder(spike_min_history=8)
        rec.new_run()
        for step in range(1, 17):
            rec.record_window([(step, _vec(0.1, layout))], layout,
                              mode="warn")
        rec.new_run()                        # new fit: loss ~5.0 now
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            for step in range(17, 29):
                rec.record_window([(step, _vec(5.0, layout))], layout,
                                  mode="warn")
        assert rec.anomalies_recorded == 0
        assert len(rec.snapshot()["records"]) == 28   # ring kept both

    def test_clip_ratio_honest_for_value_clip(self):
        # a non-global-norm clip has no norm to reuse, but the audit
        # still reduces the CLIPPED grads — a biting ClipGradByValue
        # must not report ratio 1.0
        monitor.stat_reset()
        model = _make_model(clip=nn.ClipGradByValue(max=1e-4))
        _fit(model, _data(), "record")
        cr = monitor.stat_histogram("hapi/grad_clip_ratio")
        assert cr is not None and cr["max"] < 1.0

    def test_spike_off_a_flat_plateau_still_registers(self):
        layout = numerics.AuditLayout.build([])
        rec = numerics.NumericsRecorder(spike_min_history=8)
        for step in range(1, 12):
            rec.record_window([(step, _vec(1.0, layout))], layout,
                              mode="record")
        rec.record_window([(12, _vec(25.0, layout))], layout,
                          mode="record")
        assert rec.anomaly_list()[-1]["kind"] == "loss_spike"


# ---------------------------------------------------------------------------
# flight-recorder bounds + monotonic counters
# ---------------------------------------------------------------------------

class TestRecorderBounds:
    def test_ring_bounds_hold_counters_keep_counting(self):
        layout = numerics.AuditLayout.build(["w"])
        rec = numerics.NumericsRecorder(max_steps=8, max_anomalies=4)
        for step in range(1, 51):
            bits = 0 if step % 10 == 0 else numerics.FINITE_ALL
            v = _vec(1.0, layout, bits=bits)
            rec.record_window([(step, v)], layout, mode="record")
        snap = rec.snapshot()
        assert len(snap["records"]) == 8 == snap["ring_capacity"]
        assert snap["steps_recorded"] == 50
        assert len(snap["anomalies"]) == 4       # ring dropped the rest
        assert snap["anomalies_recorded"] == 5   # ...the counter didn't
        # the ring holds the TAIL
        assert [r["step"] for r in snap["records"]] == list(range(43, 51))

    def test_audit_window_bounded_with_epoch_tail_flush(self):
        # log_freq=0 means epoch-tail flushes only: the audit buffer
        # must stay a bounded ring (newest survive, drops counted) —
        # never O(steps-per-epoch) pinned device vectors
        model, data = _make_model(), _data()
        model._AUDIT_WINDOW = 4            # shrink the ring for the test
        before = monitor.stat_get("hapi/audit_window_dropped")
        _fit(model, data, "record", log_freq=0)
        assert monitor.stat_get("hapi/audit_window_dropped") - before \
            == N_BATCHES - 4
        recs = model._numerics_recorder.snapshot()["records"]
        # the NEWEST 4 of the epoch's 8 steps reached the recorder
        assert [r["step"] for r in recs[-4:]] == \
            [model._step_counter - 3 + i for i in range(4)]

    def test_mid_fit_freeze_decodes_against_the_right_layout(self):
        # a callback flips stop_gradient mid-epoch: the staleness probe
        # rebuilds the step (new group schema) while the window still
        # buffers old-layout vectors — each vector must decode against
        # ITS layout, so an injected inf AFTER the flip blames only the
        # still-trainable group
        model, data = _make_model(), _data()
        _fit(model, data, "record")        # warm, steps 1..8
        freeze_at_step = model._step_counter + 3
        inject_at = model._step_counter + 5

        class Freezer(Callback):
            def on_train_batch_end(self, step, logs=None):
                if self.model._step_counter == freeze_at_step:
                    for name, p in self.model.network.named_parameters():
                        if name.startswith("0."):
                            p.stop_gradient = True

        model._numerics_inject_inf_at = inject_at
        # log_freq=0: ONE epoch-tail flush spans both layouts
        _fit(model, data, "record", log_freq=0, callbacks=[Freezer()])
        model._numerics_inject_inf_at = None
        anoms = [a for a in model._numerics_recorder.anomaly_list()
                 if a["kind"] == "nonfinite"]
        assert anoms and anoms[0]["step"] == inject_at
        # layer 0 was frozen before the inject: post-flip layout has no
        # group "0", and the blame must say so
        assert anoms[0]["blamed_groups"] == ["2"], anoms[0]
        for name, p in model.network.named_parameters():
            p.stop_gradient = False

    def test_aborted_fit_leftovers_not_drained_by_off_fit(self):
        # an abort between flushes leaves un-drained vectors in the
        # window; a later numerics-OFF fit must discard them, not feed
        # them to the recorder as if they belonged to the new run
        model, data = _make_model(), _data()
        _fit(model, data, "record")
        rec = model._numerics_recorder

        class Abort(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 2:
                    raise KeyboardInterrupt

        with pytest.raises(KeyboardInterrupt):
            model.fit(data, batch_size=BATCH, epochs=1, log_freq=0,
                      shuffle=False, verbose=0, numerics="record",
                      callbacks=[Abort()])
        assert len(model._audit_window) > 0     # leftovers exist
        n = rec.steps_recorded
        _fit(model, data, "off", log_freq=0)
        assert rec.steps_recorded == n          # nothing drained
        assert len(model._audit_window) == 0    # ...and they are gone

    def test_dump_numerics_on_demand(self, tmp_path):
        model, data = _make_model(), _data()
        assert model.dump_numerics() is None     # never armed
        _fit(model, data, "record")
        p = model.dump_numerics(str(tmp_path / "num.json"))
        with open(p) as f:
            doc = json.load(f)
        assert doc["reason"] == "requested"
        assert len(doc["ring"]) == N_BATCHES
        assert doc["context"]["site"].startswith("hapi/train_step")


# ---------------------------------------------------------------------------
# serving: the per-cycle logits-finite sentinel
# ---------------------------------------------------------------------------

class TestServingSentinel:
    def test_injected_bad_decode_trips_flag_and_loop_survives(self):
        from _mock_serving import MockDevice, mock_pool
        from paddle_tpu.serving.scheduler import GenerationRequest

        # the step's token row with the sentinel element tripped —
        # exactly what a NaN-logits program emits
        dev = MockDevice(mock_pool(slots=2), tail=(1,))
        before = monitor.stat_get("serving/nonfinite_cycles")
        sched = dev.scheduler()
        handles = [sched.submit(GenerationRequest(
            np.ones(4, np.int32), 3)) for _ in range(2)]
        for h in handles:
            out = h.result(timeout=60)           # loop survives: tokens
            assert out.shape == (4 + 3,)         # still flow to callers
        assert sched.nonfinite_cycles == len(dev.launches) > 0
        assert monitor.stat_get("serving/nonfinite_cycles") - before \
            == len(dev.launches)
        cycles = sched.recorder.snapshot()["cycles"]
        assert any(c.get("nonfinite") for c in cycles)
        sched.close()

    def test_legacy_mock_decode_without_flag_still_works(self):
        # a mock step returning exactly [num_slots] tokens: no
        # sentinel, no false nonfinite count
        from _mock_serving import MockDevice, mock_pool
        from paddle_tpu.serving.scheduler import GenerationRequest

        sched = MockDevice(mock_pool(slots=2)).scheduler()
        h = sched.submit(GenerationRequest(np.ones(4, np.int32), 3))
        h.result(timeout=60)
        assert sched.nonfinite_cycles == 0
        sched.close()

    def test_poisoned_engine_counts_nonfinite_cycles(self):
        import jax.numpy as jnp

        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
        from paddle_tpu.serving import GenerationEngine

        paddle.framework.random.seed(0)
        m = GPTForPretraining(GPTConfig.tiny())
        m.eval()
        p = m.parameters()[0]
        p._data = jnp.full(p.shape, jnp.nan, p._data.dtype)
        eng = GenerationEngine(m, num_slots=2, max_len=32, min_bucket=8)
        out = eng.submit(np.arange(1, 6, dtype=np.int32),
                         max_new_tokens=4).result(timeout=300)
        stats = eng.stats()
        eng.close()
        assert out.shape == (9,)                 # the loop served on
        assert stats["nonfinite_cycles"] > 0


# ---------------------------------------------------------------------------
# flag seeding (FLAGS_numerics / FLAGS_check_nan_inf migration)
# ---------------------------------------------------------------------------

class TestFlagSeeding:
    def test_flag_mode_lenient_normalization(self):
        from paddle_tpu.framework.flags import set_flags
        try:
            assert numerics.flag_mode() == "off"
            set_flags({"FLAGS_numerics": "halt"})
            assert numerics.flag_mode() == "halt"
            set_flags({"FLAGS_numerics": "ON"})     # lenient -> warn
            assert numerics.flag_mode() == "warn"
            set_flags({"FLAGS_numerics": "bogus"})  # bad value: off,
            assert numerics.flag_mode() == "off"    # never a crash
            # the reference flag's abort-on-NaN maps to 'halt'
            set_flags({"FLAGS_numerics": "",
                       "FLAGS_check_nan_inf": True})
            assert numerics.flag_mode() == "halt"
        finally:
            set_flags({"FLAGS_numerics": "",
                       "FLAGS_check_nan_inf": False})
