"""The jaxpr program linter (paddle_tpu/analysis): each of the five
passes must catch its seeded bug class, the integration surfaces
(Model.fit analyze=, Executor pre-flight, CLI) must work, and the zoo
train steps + examples entry points must come back with a clean bill
(zero error-severity findings)."""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import analysis
from paddle_tpu.framework import monitor, trace_probe
from paddle_tpu.io import TensorDataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _findings(report, pass_id, severity=None):
    return [f for f in report.findings if f.pass_id == pass_id
            and (severity is None or f.severity == severity)]


def _small_model(net=None):
    net = net or nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
    m = paddle.Model(net)
    m.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters()),
              nn.CrossEntropyLoss())
    return m


def _batch(n=8, d=8, c=4, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, d).astype("float32"),
            rng.randint(0, c, (n, 1)).astype("int64"))


# ---------------------------------------------------------------------------
# pass 1: host-sync
# ---------------------------------------------------------------------------

def test_host_sync_catches_hidden_numpy():
    import jax.numpy as jnp

    def step_with_hidden_sync(x):
        h = x * 2.0
        scale = float(np.asarray(h).mean())  # the seeded bug
        return h * scale

    r = analysis.analyze(step_with_hidden_sync,
                         jnp.ones((4,), jnp.float32))
    errs = _findings(r, "host-sync", "error")
    assert len(errs) == 1
    # diagnosed with the offending source line, not a raw
    # ConcretizationError deep inside jax
    assert "test_analysis.py" in (errs[0].source or "")
    assert not r.ok()


def test_host_sync_catches_tensor_numpy_inside_layer():
    class SyncNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)

        def forward(self, x):
            h = self.fc(x)
            return h * float(h.numpy().mean())  # hidden host sync

    m = _small_model(SyncNet())
    x, y = _batch()
    r = analysis.analyze_model(m, [x], [y])
    assert not r.ok()
    assert _findings(r, "host-sync", "error")


def test_host_sync_flags_callbacks():
    t = paddle.to_tensor(np.eye(4, dtype="float32"))
    r = analysis.analyze(lambda x: paddle.linalg.eig(x)[0], t)
    warns = _findings(r, "host-sync", "warning")
    assert warns and warns[0].primitive == "pure_callback"
    assert r.ok()  # a callback is a cost warning, not an error


# ---------------------------------------------------------------------------
# pass 2: donation-safety
# ---------------------------------------------------------------------------

def test_donation_catches_missing_rebind_target():
    import jax
    import jax.numpy as jnp

    # the seeded PR-2 bug class: buffers donated but never returned —
    # the caller's rebind target does not exist after dispatch
    f = jax.jit(lambda params, x: (params * 0.9 + x).sum(),
                donate_argnums=(0,))
    r = analysis.analyze(f, jnp.ones((4, 4), jnp.float32),
                         jnp.ones((4, 4), jnp.float32))
    errs = _findings(r, "donation-safety", "error")
    assert len(errs) == 1 and "no matching output" in errs[0].message


def test_donation_clean_when_outputs_cover_donated():
    import jax.numpy as jnp

    def step(params, x):
        new_params = {k: v - 0.1 * x.mean() for k, v in params.items()}
        return new_params, (x * 2).sum()

    params = {"w": jnp.ones((3, 3), jnp.float32)}
    r = analysis.analyze(step, params, jnp.ones((3,), jnp.float32),
                         donate_argnums=(0,))
    assert not _findings(r, "donation-safety")


def test_donation_real_train_step_is_clean():
    m = _small_model()
    x, y = _batch()
    r = analysis.analyze_model(m, [x], [y])
    assert not _findings(r, "donation-safety"), r.table()
    assert r.ok(), r.table()


def _dp_mesh(n=4):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def test_donation_threads_through_shard_map():
    """The ZeRO-shaped contract: a donated dp-sharded state whose
    updated value comes back through the shard_map eqn must be
    recognized as covered — and one that is dropped must still be the
    no-rebind-target error."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _dp_mesh()
    state = jax.device_put(jnp.zeros(8, jnp.float32),
                           NamedSharding(mesh, P("dp")))
    x = jnp.ones(8, jnp.float32)

    def good(s, v):
        g = jax.lax.psum_scatter(v, "dp", scatter_dimension=0,
                                 tiled=True)
        s2 = s + g
        return s2, jax.lax.all_gather(s2, "dp", axis=0, tiled=True)

    fn = jax.shard_map(good, mesh=mesh, in_specs=(P("dp"), P()),
                       out_specs=(P("dp"), P()), check_vma=False)
    r = analysis.analyze(fn, state, x, donate_argnums=(0,))
    assert not _findings(r, "donation-safety"), r.table()

    def bad(s, v):
        # donated state read but never returned: the caller's rebind
        # target does not exist (output is a scalar, not s's aval)
        return jax.lax.psum(jnp.sum(s) + jnp.sum(v), "dp")

    fn2 = jax.shard_map(bad, mesh=mesh, in_specs=(P("dp"), P()),
                        out_specs=P(), check_vma=False)
    r2 = analysis.analyze(fn2, state, x, donate_argnums=(0,))
    errs = _findings(r2, "donation-safety", "error")
    assert errs and "no matching output" in errs[0].message


# ---------------------------------------------------------------------------
# pass: collective-pairing (seeded both directions)
# ---------------------------------------------------------------------------

def test_collective_pairing_clean_when_paired():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh()

    def body(x):
        s = jax.lax.psum_scatter(x, "dp", scatter_dimension=0,
                                 tiled=True)
        return jax.lax.all_gather(s * 2.0, "dp", axis=0, tiled=True)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    r = analysis.analyze(fn, jnp.ones(8, jnp.float32))
    assert not _findings(r, "collective-pairing"), r.table()


def test_collective_pairing_catches_unpaired_reduce_scatter():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh()

    def body(x):
        return jax.lax.psum_scatter(x, "dp", scatter_dimension=0,
                                    tiled=True)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(),
                       out_specs=P("dp"), check_vma=False)
    r = analysis.analyze(fn, jnp.ones(8, jnp.float32))
    errs = _findings(r, "collective-pairing", "error")
    assert errs and "no closing all-gather" in errs[0].message


def test_collective_pairing_catches_mismatched_dimension():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh()

    def body(x):
        s = jax.lax.psum_scatter(x, "dp", scatter_dimension=0,
                                 tiled=True)
        # closes on the WRONG dimension: stripes re-assemble permuted
        return jax.lax.all_gather(s, "dp", axis=1, tiled=True)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    r = analysis.analyze(fn, jnp.ones((8, 2), jnp.float32))
    errs = _findings(r, "collective-pairing", "error")
    assert errs and "does not match its closing" in errs[0].message


def test_collective_pairing_respects_program_order():
    """An all-gather BEFORE the reduce-scatter (e.g. gathering some
    other value at the top of the step) cannot be its closing gather —
    the scatter below it is still unpaired."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh()

    def body(a, x):
        g = jax.lax.all_gather(a, "dp", axis=0, tiled=True)  # unrelated
        s = jax.lax.psum_scatter(x * jnp.sum(g), "dp",
                                 scatter_dimension=0, tiled=True)
        return s  # never gathered back

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                       out_specs=P("dp"), check_vma=False)
    r = analysis.analyze(fn, jnp.ones(8, jnp.float32),
                         jnp.ones(8, jnp.float32))
    errs = _findings(r, "collective-pairing", "error")
    assert errs and "no closing all-gather" in errs[0].message


def test_collective_pairing_silent_on_psum_only_programs():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh()

    def body(x):
        return jax.lax.psum(x, "dp")  # plain DP grad sync: fine

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                       check_vma=False)
    r = analysis.analyze(fn, jnp.ones(8, jnp.float32))
    assert not _findings(r, "collective-pairing")


# ---------------------------------------------------------------------------
# pass 3: dead/frozen-grad
# ---------------------------------------------------------------------------

class _PartlyDeadNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.used = nn.Linear(8, 4)
        self.unused = nn.Linear(8, 4)  # the seeded frozen-param bug

    def forward(self, x):
        return self.used(x)


def test_dead_grad_catches_trainable_param_without_grad():
    m = _small_model(_PartlyDeadNet())
    x, y = _batch()
    r = analysis.analyze_model(m, [x], [y])
    errs = _findings(r, "dead-grad", "error")
    names = {e.message.split("'")[1] for e in errs}
    assert names == {"unused.weight", "unused.bias"}
    assert not r.ok()


def test_dead_grad_silent_when_properly_frozen():
    net = _PartlyDeadNet()
    net.unused.weight.stop_gradient = True
    net.unused.bias.stop_gradient = True
    m = _small_model(net)
    x, y = _batch()
    r = analysis.analyze_model(m, [x], [y])
    # the frozen split bakes them out of the grad jaxpr entirely
    assert not _findings(r, "dead-grad"), r.table()
    assert r.ok(), r.table()


def test_dead_grad_catches_detached_path():
    class DetachNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)
            self.gate = nn.Linear(8, 4)

        def forward(self, x):
            # .detach() severs the grad path while gate stays trainable
            return self.fc(x) + self.gate(x).detach()

    m = _small_model(DetachNet())
    x, y = _batch()
    r = analysis.analyze_model(m, [x], [y])
    names = {e.message.split("'")[1]
             for e in _findings(r, "dead-grad", "error")}
    assert names == {"gate.weight", "gate.bias"}


# ---------------------------------------------------------------------------
# pass 4: dtype-hygiene
# ---------------------------------------------------------------------------

def test_dtype_catches_f64_input_leak():
    bad_batch = np.random.RandomState(0).randn(4, 8)  # float64!
    r = analysis.analyze(lambda a: (a * 2).sum(), bad_batch)
    warns = _findings(r, "dtype-hygiene", "warning")
    assert any("float64 host input" in f.message for f in warns)


def test_dtype_catches_bf16_upcast():
    import jax.numpy as jnp

    def fn(x):
        h = x * 2  # bf16 work
        return h.astype(jnp.float32).sum()  # silent upcast

    r = analysis.analyze(fn, jnp.ones((4, 4), jnp.bfloat16))
    infos = _findings(r, "dtype-hygiene", "info")
    assert any("bf16->f32 upcast" in f.message for f in infos)


def test_dtype_clean_on_f32():
    import jax.numpy as jnp
    r = analysis.analyze(lambda a: (a @ a).sum(),
                         jnp.ones((4, 4), jnp.float32))
    assert not _findings(r, "dtype-hygiene")


# ---------------------------------------------------------------------------
# pass 5: recompile-churn
# ---------------------------------------------------------------------------

def test_recompile_churn_classifies_shape_retraces():
    trace_probe.reset()
    monitor.stat_reset()
    # the seeded churn: one op dispatched at many distinct shapes
    for n in range(3, 13):
        t = paddle.to_tensor(np.ones((n, 2), "float32"))
        (t * 1.5).numpy()
    assert monitor.stat_get("dispatch/retrace_cause/shape") >= 8
    r = analysis.analyze(None)
    churn = _findings(r, "recompile-churn")
    assert any("shape classes" in f.message for f in churn)


def test_recompile_churn_step_level_warning():
    trace_probe.reset()
    m = _small_model()
    # batch-shape flapping re-traces the whole donated step each time
    for n in (8, 9, 10):
        x, y = _batch(n=n)
        m.train_batch([x], [y])
    r = analysis.analyze(None)
    warns = [f for f in _findings(r, "recompile-churn", "warning")
             if "train_step" in f.message]
    assert warns, r.table()


def test_frozen_set_flip_is_classified():
    trace_probe.reset()
    monitor.stat_reset()
    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
    m = _small_model(net)
    x, y = _batch()
    m.train_batch([x], [y])
    net[0].weight.stop_gradient = True  # progressive-freezing flip
    m.train_batch([x], [y])
    assert monitor.stat_get("dispatch/retrace_cause/frozen_set") >= 1


# ---------------------------------------------------------------------------
# integration: Model.fit(analyze=...), Executor pre-flight, CLI, counters
# ---------------------------------------------------------------------------

def test_fit_analyze_error_mode_raises():
    m = _small_model(_PartlyDeadNet())
    x, y = _batch(n=16)
    with pytest.raises(analysis.AnalysisError) as ei:
        m.fit(TensorDataset([x, y]), batch_size=8, epochs=1, verbose=0,
              analyze="error")
    assert "dead-grad" in str(ei.value)


def test_fit_analyze_warn_mode_trains_and_reports():
    m = _small_model(_PartlyDeadNet())
    x, y = _batch(n=16)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        m.fit(TensorDataset([x, y]), batch_size=8, epochs=1, verbose=0,
              analyze="warn")
    assert any("dead-grad" in str(x.message) for x in w)
    assert m._analysis_report is not None
    assert not m._analysis_report.ok()


def test_fit_analyze_off_by_default():
    monitor.stat_reset()
    m = _small_model()
    x, y = _batch(n=16)
    m.fit(TensorDataset([x, y]), batch_size=8, epochs=1, verbose=0)
    assert monitor.stat_get("analysis/runs") == 0


def test_fit_analyze_flag_seeded():
    from paddle_tpu.framework.flags import set_flags
    monitor.stat_reset()
    m = _small_model()
    x, y = _batch(n=16)
    set_flags({"FLAGS_static_analysis": "warn"})
    try:
        m.fit(TensorDataset([x, y]), batch_size=8, epochs=1, verbose=0)
    finally:
        set_flags({"FLAGS_static_analysis": "off"})
    assert monitor.stat_get("analysis/runs") == 1


def test_fit_analyze_rejects_bad_mode():
    m = _small_model()
    with pytest.raises(ValueError):
        m.fit(TensorDataset(list(_batch())), batch_size=8, verbose=0,
              analyze="loud")


def test_executor_preflight_over_captured_program():
    from paddle_tpu import static
    from paddle_tpu.framework.flags import set_flags

    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [None, 8], "float32")
            h = static.nn.fc(x, size=4)
        exe = static.Executor()
        exe.run(startup)
        set_flags({"FLAGS_static_analysis": "warn"})
        try:
            out = exe.run(main, feed={"x": np.ones((2, 8), "float32")},
                          fetch_list=[h])
        finally:
            set_flags({"FLAGS_static_analysis": "off"})
        assert out[0].shape == (2, 4)
        report = main._analysis_report
        assert report is not None and report.ok()
        # cached: a second run() does not re-analyze
        runs = monitor.stat_get("analysis/runs")
        exe.run(main, feed={"x": np.ones((2, 8), "float32")},
                fetch_list=[h])
        assert monitor.stat_get("analysis/runs") == runs
    finally:
        paddle.disable_static()


def test_counters_and_histograms_populated():
    import jax.numpy as jnp
    monitor.stat_reset()
    analysis.analyze(lambda a: a + 1, jnp.ones((2,), jnp.float32))
    assert monitor.stat_get("analysis/runs") == 1
    assert "analysis/findings" in monitor.all_stats()
    for pid in analysis.all_passes():
        assert monitor.stat_histogram(f"analysis/pass_ms/{pid}"), pid


def test_cli_module_target_and_selflint():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    res = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis",
         "__graft_entry__:entry"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=110)
    assert res.returncode == 0, res.stderr[-1500:]
    assert "clean" in res.stdout or "0 error(s)" in res.stdout
    res = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--selflint"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=110)
    assert res.returncode == 0, res.stdout[-1500:]


def test_donation_mapping_with_static_argnums():
    import jax.numpy as jnp

    # a static argnum BEFORE the donated one: the donation mask must
    # land on `params`, whose missing output is then caught
    def step(cfg, params, x):
        return (params * cfg + x).sum()

    r = analysis.analyze(step, 2, jnp.ones((3, 3), jnp.float32),
                         jnp.ones((3, 3), jnp.float32),
                         static_argnums=(0,), donate_argnums=(1,))
    assert _findings(r, "donation-safety", "error")


def test_executor_error_mode_keeps_gating_on_rerun():
    from paddle_tpu import static
    from paddle_tpu.framework.flags import set_flags

    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [None, 4], "float32")
            h = static.nn.fc(x, size=2)
        exe = static.Executor()
        exe.run(startup)
        # simulate a cached error-carrying report: error mode must keep
        # raising on EVERY run, not just the analyzing one
        main._analysis_report = analysis.Report(
            target="seeded", findings=[analysis.Finding(
                pass_id="host-sync", severity="error", message="seeded")])
        set_flags({"FLAGS_static_analysis": "error"})
        try:
            with pytest.raises(analysis.AnalysisError):
                exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                        fetch_list=[h])
        finally:
            set_flags({"FLAGS_static_analysis": "off"})
    finally:
        paddle.disable_static()


def test_flag_mode_is_lenient_on_boolean_style_values():
    from paddle_tpu.framework.flags import set_flags
    for raw, want in (("1", "warn"), ("on", "warn"), ("true", "warn"),
                      ("error", "error"), ("strict", "error"),
                      ("0", "off"), ("nonsense", "off"), ("off", "off")):
        set_flags({"FLAGS_static_analysis": raw})
        try:
            assert analysis.flag_mode() == want, raw
        finally:
            set_flags({"FLAGS_static_analysis": "off"})
    # a boolean-style env value must not crash fit()
    set_flags({"FLAGS_static_analysis": "1"})
    try:
        monitor.stat_reset()
        m = _small_model()
        x, y = _batch(n=16)
        m.fit(TensorDataset([x, y]), batch_size=8, epochs=1, verbose=0)
        assert monitor.stat_get("analysis/runs") == 1
    finally:
        set_flags({"FLAGS_static_analysis": "off"})


def test_tp_decode_capability_classifier():
    import __graft_entry__ as g
    assert g._is_capability_error(ImportError("no module"))
    assert g._is_capability_error(
        ValueError("compiling computation requires at least 8 devices"))
    assert g._is_capability_error(
        RuntimeError("UNIMPLEMENTED: PartitionId instruction is not "
                     "supported for SPMD partitioning"))
    # python-level bugs NEVER skip, even when their message contains
    # marker-like words
    assert not g._is_capability_error(
        TypeError("unsupported operand type(s) for +: 'int' and 'None'"))
    assert not g._is_capability_error(AssertionError("shape mismatch"))
    assert not g._is_capability_error(ValueError("shapes do not match"))


# ---------------------------------------------------------------------------
# clean bill: zoo train steps + examples entry points
# ---------------------------------------------------------------------------

def test_gpt2_donated_train_step_clean():
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.framework.random.seed(0)
    cfg = GPTConfig.tiny()
    net = GPTForPretraining(cfg)
    m = paddle.Model(net)
    m.prepare(paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=net.parameters()),
              lambda logits, lbl: F.cross_entropy(
                  logits.reshape([-1, cfg.vocab_size]),
                  lbl.reshape([-1])))
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 16)).astype(np.int32)
    r = analysis.analyze_model(m, [ids], [ids.astype(np.int64)])
    assert r.ok(), r.table()
    # the donated contract on the REAL step: every donated leaf rebinds
    assert not _findings(r, "donation-safety"), r.table()
    assert not _findings(r, "dead-grad"), r.table()


def test_resnet_donated_train_step_clean():
    from paddle_tpu.vision.models import resnet18

    paddle.framework.random.seed(0)
    net = resnet18(num_classes=10)
    m = paddle.Model(net)
    m.prepare(paddle.optimizer.Momentum(learning_rate=0.1,
                                        parameters=net.parameters()),
              nn.CrossEntropyLoss())
    x = np.random.RandomState(0).randn(2, 3, 32, 32).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 10, (2, 1)).astype(np.int64)
    r = analysis.analyze_model(m, [x], [y])
    assert r.ok(), r.table()


def test_examples_entry_points_clean():
    """The computations the examples/ scripts run, analyzed at their
    smoke scale: train_vision's hapi vision fit step (LeNet; the resnet
    variant is covered by test_resnet_donated_train_step_clean and the
    bench dry-run), generate_text's GPT train step, train_gpt2_sharded's
    ParallelEngine donated step, and the static_graph Program replay.
    All must carry zero error-severity findings."""
    from paddle_tpu.vision.models import LeNet

    # train_vision.py: Model(LeNet).fit
    paddle.framework.random.seed(0)
    m = paddle.Model(LeNet())
    m.prepare(paddle.optimizer.Adam(
        learning_rate=1e-3, parameters=m.network.parameters()),
        nn.CrossEntropyLoss())
    x = np.random.RandomState(0).randn(2, 1, 28, 28).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 10, (2, 1)).astype(np.int64)
    r = analysis.analyze_model(m, [x], [y], name="examples/train_vision")
    assert r.ok(), r.table()

    # generate_text.py: char-GPT train step (tiny config)
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, intermediate_size=64,
                    max_position_embeddings=32)
    net = GPTForPretraining(cfg)
    gm = paddle.Model(net)
    gm.prepare(paddle.optimizer.AdamW(learning_rate=1e-3,
                                      parameters=net.parameters()),
               lambda logits, lbl: F.cross_entropy(
                   logits.reshape([-1, cfg.vocab_size]),
                   lbl.reshape([-1])))
    ids = np.random.RandomState(0).randint(0, 64, (2, 16)).astype(np.int32)
    r = analysis.analyze_model(gm, [ids], [ids.astype(np.int64)],
                               name="examples/generate_text")
    assert r.ok(), r.table()

    # train_gpt2_sharded.py: the ParallelEngine donated sharded step
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.distributed.spmd import ParallelEngine
    paddle.framework.random.seed(0)
    net2 = GPTForPretraining(GPTConfig.tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=net2.parameters())
    denv.build_mesh({"data": 1})
    eng = ParallelEngine(net2, opt, loss_fn=None, mesh=denv.get_mesh())
    ids2 = np.random.RandomState(0).randint(
        0, GPTConfig.tiny().vocab_size, (2, 16)).astype(np.int32)
    eng.train_step_async([ids2], [ids2])  # builds eng._train_step
    key = jax.random.key(0)
    lr = jnp.asarray(1e-4, jnp.float32)
    r = analysis.analyze(eng._train_step, eng.params, eng.opt_state,
                         eng.buffers, key, lr, ids2, ids2,
                         name="examples/train_gpt2_sharded")
    assert r.ok(), r.table()
    denv.set_mesh(None)

    # static_graph.py: captured Program replay (fc + fc + loss)
    from paddle_tpu import static
    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            xv = static.data("x", [None, 8], "float32")
            yv = static.data("y", [None, 1], "float32")
            h = static.nn.fc(xv, size=16)
            pred = static.nn.fc(h, size=1)
            paddle.mean(paddle.nn.functional.square_error_cost(pred, yv))
        r = analysis.analyze(main, name="examples/static_graph")
        assert r.ok(), r.table()
    finally:
        paddle.disable_static()


# ---------------------------------------------------------------------------
# ISSUE 18: liveness core + static-memory / donation-miss /
# sharding-consistency passes + the --budget / --json CLI surface
# ---------------------------------------------------------------------------

def test_liveness_known_byte_math():
    """Hand-checkable program: two pinned 4 KiB args, a 4 KiB
    intermediate and a 4 KiB product live together at the mul — the
    peak is exactly 16 KiB, blamed on the mul."""
    import jax.numpy as jnp
    from paddle_tpu.analysis import liveness

    def f(a, b):
        c = a + b
        return (c * 2.0).sum()

    rep = liveness.callable_liveness(f, jnp.ones((32, 32), jnp.float32),
                                     jnp.ones((32, 32), jnp.float32))
    assert rep.arg_bytes == 2 * 4096
    assert rep.static_peak_bytes == 4 * 4096
    assert rep.peak.primitive == "mul"
    assert rep.timeline[0].live_bytes == rep.static_peak_bytes
    d = rep.as_dict()
    assert d["static_peak_bytes"] == rep.static_peak_bytes
    assert d["peak"]["primitive"] == "mul"


def test_liveness_donation_frees_after_last_use():
    """A donated 2 MiB state must stop being charged past its last
    use: the donated trace peaks one full buffer lower."""
    import jax.numpy as jnp
    from paddle_tpu.analysis import liveness

    def step(s, x):
        s2 = s + x.sum()
        return s2 * 2.0          # s is dead here; s2 and out live

    big = jnp.ones((512, 1024), jnp.float32)          # 2 MiB
    x = jnp.ones((4,), jnp.float32)
    big_bytes = big.size * big.dtype.itemsize
    r0 = liveness.callable_liveness(step, big, x)
    r1 = liveness.callable_liveness(step, big, x, donate_argnums=(0,))
    # the peak moves to a different eqn once s is freed, so the saving
    # is one full buffer give or take the scalar sum
    assert big_bytes - 64 <= r0.static_peak_bytes - r1.static_peak_bytes \
        <= big_bytes
    assert r1.donated_bytes == big_bytes


def test_liveness_update_of_a_dying_operand_is_in_place():
    """A scatter / dynamic_update_slice into a DONATED pool reuses the
    pool's buffer (what XLA does): the peak is one pool, not two — the
    two-pool figure refused, on a 16 GB chip, every serving engine whose
    KV pool took more than half the device. A pool the caller still owns
    (not donated) keeps both copies charged."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.analysis import liveness

    pool = jnp.zeros((64, 16, 128), jnp.float32)          # 512 KiB
    rows = jnp.ones((4, 128), jnp.float32)
    nbytes = pool.size * pool.dtype.itemsize

    def scatter(pool, rows):
        return pool.at[jnp.arange(4), 3, :].set(rows)

    def dus(pool, rows):
        return jax.lax.dynamic_update_slice(pool, rows[None], (5, 0, 0))

    for fn in (scatter, dus):
        kept = liveness.callable_liveness(fn, pool, rows)
        donated = liveness.callable_liveness(fn, pool, rows,
                                             donate_argnums=(0,))
        assert kept.static_peak_bytes >= 2 * nbytes
        assert nbytes <= donated.static_peak_bytes < nbytes + 64 * 1024


def test_liveness_crosscheck_contract():
    from paddle_tpu.analysis import liveness

    # backend silent -> None, never a fake verdict
    assert liveness.crosscheck(100, 10, 10, None) is None
    assert liveness.crosscheck(None, 10, 10, 10) is None
    cc = liveness.crosscheck(100, 50, 25, 25)
    assert cc["ok"] and cc["ratio"] == 1.0 and cc["xla_bytes"] == 100
    assert not liveness.crosscheck(100, 1, 1, 1)["ok"]


def test_static_memory_pass_reports_peak():
    import jax.numpy as jnp

    def f(a):
        return (a * 2.0).sum()

    r = analysis.analyze(f, jnp.ones((64, 64), jnp.float32))
    infos = _findings(r, "static-memory")
    assert len(infos) == 1 and infos[0].severity == "info"
    assert infos[0].data["static_peak_bytes"] > 0
    assert "static peak" in infos[0].message
    assert "fattest point" in infos[0].message
    assert r.ok()                     # info never fails the bill


def test_donation_miss_catches_undonated_dying_state():
    import jax.numpy as jnp

    def step(s, x):
        s2 = s + x.sum()
        return s2 * 2.0

    big = jnp.ones((512, 1024), jnp.float32)          # 2 MiB, dies early
    x = jnp.ones((4,), jnp.float32)
    r = analysis.analyze(step, big, x)
    warns = _findings(r, "donation-miss", "warning")
    assert len(warns) == 1, r.table()
    assert warns[0].data["argnum"] == 0
    assert warns[0].data["saving_bytes"] > 0
    assert "not donated" in warns[0].message
    assert "donate_argnums" in warns[0].fix_hint
    # donated: the miss disappears
    r2 = analysis.analyze(step, big, x, donate_argnums=(0,))
    assert not _findings(r2, "donation-miss"), r2.table()


def test_donation_miss_prices_dead_donation():
    """The old donation-safety boolean dead-donation warning now lives
    here, priced in bytes."""
    import jax.numpy as jnp

    def step(dead, x):
        return x * 2.0            # donated input never read

    big = jnp.ones((512, 1024), jnp.float32)
    r = analysis.analyze(step, big, jnp.ones((8,), jnp.float32),
                         donate_argnums=(0,))
    warns = _findings(r, "donation-miss", "warning")
    assert warns and "never read" in warns[0].message
    assert warns[0].data["kind"] == "dead"
    assert warns[0].data["bytes"] == big.size * big.dtype.itemsize
    # small invars below the floor stay unflagged both ways
    r2 = analysis.analyze(step, jnp.ones((8,), jnp.float32),
                          jnp.ones((8,), jnp.float32))
    assert not _findings(r2, "donation-miss")


def test_donation_miss_silent_when_lifetime_spans_peak():
    """An invar that stays live to the end (it IS an output) cannot be
    freed by donation — the honest re-scan must not flag it."""
    import jax.numpy as jnp

    def step(s, x):
        return s + x              # s's aval is the output's aval

    big = jnp.ones((512, 1024), jnp.float32)
    r = analysis.analyze(step, big, big)
    misses = [f for f in _findings(r, "donation-miss")
              if f.data and f.data.get("kind") == "miss"
              and f.data.get("saving_bytes", 0) <= 0]
    assert not misses, r.table()


def test_sharding_consistency_flags_large_replicated_operand():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh()
    table = jnp.ones((512, 1024), jnp.float32)        # 2 MiB replicated

    def body(x, t):
        return x + t.sum()

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                       out_specs=P("dp"), check_vma=False)
    r = analysis.analyze(fn, jnp.ones((8,), jnp.float32), table)
    warns = _findings(r, "sharding-consistency", "warning")
    assert len(warns) == 1, r.table()
    assert warns[0].data["bytes"] == 2 * 1024 * 1024
    assert warns[0].data["per_device_sharded_bytes"] \
        == warns[0].data["bytes"] // 4
    assert "fully replicated" in warns[0].message
    # sharding the table silences it
    fn2 = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                        out_specs=P("dp"), check_vma=False)
    r2 = analysis.analyze(fn2, jnp.ones((8,), jnp.float32), table)
    assert not _findings(r2, "sharding-consistency"), r2.table()


def test_sharding_consistency_scoped_rs_ag_pairing():
    """The PR-10 rs/ag pairing contract enforced INSIDE the shard_map
    body: a scatter closed on the wrong dimension is an error naming
    the mesh; the properly-paired body is clean."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh()

    def bad(x):
        s = jax.lax.psum_scatter(x, "dp", scatter_dimension=0,
                                 tiled=True)
        return jax.lax.all_gather(s, "dp", axis=1, tiled=True)

    fn = jax.shard_map(bad, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    r = analysis.analyze(fn, jnp.ones((8, 2), jnp.float32))
    errs = _findings(r, "sharding-consistency", "error")
    assert errs and "PR-10 pairing contract" in errs[0].message
    assert errs[0].primitive == "reduce_scatter"

    def good(x):
        s = jax.lax.psum_scatter(x, "dp", scatter_dimension=0,
                                 tiled=True)
        return jax.lax.all_gather(s * 2.0, "dp", axis=0, tiled=True)

    fn2 = jax.shard_map(good, mesh=mesh, in_specs=P(), out_specs=P(),
                        check_vma=False)
    r2 = analysis.analyze(fn2, jnp.ones((8, 2), jnp.float32))
    assert not _findings(r2, "sharding-consistency", "error"), r2.table()


def test_spec_verify_bucket_analyzes_clean():
    """Satellite: the clean-bill contract extended to the speculative
    verify program (largest built (q, table) bucket)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.ops.ragged_paged_attention import BLOCK_Q
    from paddle_tpu.serving import GenerationEngine

    paddle.framework.random.seed(0)
    model = GPTForPretraining(GPTConfig.tiny())
    model.eval()
    eng = GenerationEngine(model, num_slots=4, max_len=64,
                           block_size=8,
                           spec_draft=model, spec_k=3)
    try:
        eng._spec_step_fn(BLOCK_Q, 2)     # seed one verify bucket
        r = eng.analyze()
        assert "spec_verify" in r.target
        assert r.ok(), r.table()
        assert _findings(r, "static-memory")
    finally:
        eng.close()


def test_sharded_fused_step_analyzes_clean():
    """Satellite: the clean-bill contract extended to the mesh=
    sharded fused step — the sharding-consistency pass included (the
    head-sharded pool must NOT be flagged as replicated)."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import GenerationEngine

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    paddle.framework.random.seed(0)
    model = GPTForPretraining(GPTConfig.tiny())
    model.eval()
    eng = GenerationEngine(model, num_slots=4, max_len=64,
                           block_size=8,
                           mesh=mesh)
    try:
        r = eng.analyze()
        assert "fused_step" in r.target
        assert r.ok(), r.table()
    finally:
        eng.close()


def test_aot_site_records_static_peak():
    """Every AotSite compile records the donation-aware liveness figure
    NEXT TO the XLA memory figures, and the two bracket each other
    within the documented tolerance."""
    import jax.numpy as jnp
    from paddle_tpu.analysis import liveness
    from paddle_tpu.framework import program_registry

    site = program_registry.aot_site(
        "test/static_peak_site",
        lambda s, x: (s + x, (s * x).sum()),
        donate_argnums=(0,))
    site(jnp.ones((64, 64), jnp.float32), jnp.ones((64, 64), jnp.float32))
    rec = program_registry.get("test/static_peak_site")
    assert rec.static_peak_bytes is not None and rec.static_peak_bytes > 0
    cc = liveness.crosscheck(rec.static_peak_bytes, rec.argument_bytes,
                             rec.output_bytes, rec.temp_bytes)
    if cc is not None:                # CPU reports; other backends may not
        assert cc["ok"], cc


def test_cli_json_and_budget_gate():
    """Satellites: --json machine-readable findings and the --budget
    fit-before-compile gate's documented exit-code contract."""
    import json as _json

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    base = [sys.executable, "-m", "paddle_tpu.analysis",
            "__graft_entry__:entry"]
    res = subprocess.run(base + ["--json"], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=110)
    assert res.returncode == 0, res.stderr[-1500:]
    doc = _json.loads(res.stdout)
    assert doc["ok"] is True
    assert doc["static_peak_bytes"] > 0
    assert doc["budget_bytes"] is None and doc["fits_budget"] is None
    assert any(f["pass"] == "static-memory" and f["data"]
               for f in doc["findings"])

    # over budget: exit 1, --json unchanged in shape, fits_budget False
    res = subprocess.run(base + ["--json", "--budget", "1"], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=110)
    assert res.returncode == 1, res.stdout
    doc = _json.loads(res.stdout)
    assert doc["fits_budget"] is False and doc["ok"] is False
    assert doc["budget_bytes"] == 1

    # generous budget: exit 0 with the human-readable verdict
    res = subprocess.run(base + ["--budget", str(1 << 40)], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=110)
    assert res.returncode == 0, res.stdout
    assert "fits" in res.stdout


def test_liveness_aliased_kernel_output_is_in_place():
    """A ``pallas_call`` whose output aliases an operand
    (``ops/kv_append.py``: the pool, through a jitted call of its own)
    overwrites a DONATED pool: one pool at the peak, as with the scatter
    it replaced — two refused GPT-2 large's 13 GB pool on a 16 GB chip.
    A pool the caller keeps is still charged twice, and the kernel's
    body (refs, VMEM tiles) is no program point."""
    import jax.numpy as jnp
    from paddle_tpu.analysis import liveness
    from paddle_tpu.ops.kv_append import kv_append

    pool = jnp.zeros((2, 9, 4, 16, 128), jnp.float32)     # 576 KiB
    rows = jnp.ones((8, 4, 128), jnp.float32)
    wb = jnp.asarray([3, 0, 0, 0, 0, 0, 0, 0], jnp.int32)
    nbytes = pool.size * pool.dtype.itemsize

    def two_layers(pool, wb, rows):
        for li in range(2):
            pool = kv_append(pool, li, wb, wb * 0, rows)
        return pool

    kept = liveness.callable_liveness(two_layers, pool, wb, rows)
    donated = liveness.callable_liveness(two_layers, pool, wb, rows,
                                         donate_argnums=(0,))
    assert kept.static_peak_bytes >= 2 * nbytes
    assert nbytes <= donated.static_peak_bytes < nbytes + 64 * 1024
    assert not [p for p in donated.timeline
                if (p.source or "").find("_append_kernel") >= 0]
