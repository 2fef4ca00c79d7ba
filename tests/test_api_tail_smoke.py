"""Value-pinned smoke for the unexercised API tail: top-level tensor
functions, LR schedulers, Precision/Recall metrics, and device/dtype
utilities. Oracles are numpy (or the documented reference formula)."""
import numpy as np
import pytest

import paddle_tpu as paddle

rng = np.random.RandomState(0)
A = rng.randn(3, 4).astype("float32")
B = rng.randn(3, 4).astype("float32")
P = np.abs(A) + 0.5
I1 = rng.randint(0, 5, (3, 4)).astype(np.int64)
I2 = rng.randint(0, 5, (3, 4)).astype(np.int64)


def t(x):
    return paddle.to_tensor(np.asarray(x))


# (paddle name, args (numpy), numpy oracle) — applied positionally
ELEMENTWISE = [
    ("amax", (A,), lambda a: a.max()),
    ("amin", (A,), lambda a: a.min()),
    ("argmin", (A,), lambda a: a.argmin()),
    ("angle", (A,), lambda a: np.angle(a)),
    ("bitwise_and", (I1, I2), np.bitwise_and),
    ("bitwise_or", (I1, I2), np.bitwise_or),
    ("bitwise_xor", (I1, I2), np.bitwise_xor),
    ("bitwise_not", (I1,), np.bitwise_not),
    ("conj", (A,), np.conj),
    ("copysign", (A, B), np.copysign),
    ("count_nonzero", (I1,), np.count_nonzero),
    ("cumprod", (A, 1), lambda a, d: np.cumprod(a, d)),
    ("diagflat", (A[0],), np.diagflat),
    ("diagonal", (A,), lambda a: np.diagonal(a)),
    ("equal_all", (A, A.copy()), lambda a, b: np.array_equal(a, b)),
    ("floor_divide", (I1 + 1, I2 + 1), np.floor_divide),
    ("floor_mod", (I1 + 1, I2 + 1), np.mod),
    ("fmax", (A, B), np.fmax),
    ("fmin", (A, B), np.fmin),
    ("frac", (A,), lambda a: a - np.trunc(a)),
    ("greater_equal", (A, B), np.greater_equal),
    ("heaviside", (A, B), np.heaviside),
    ("hypot", (A, B), np.hypot),
    ("i0", (A,), lambda a: np.vectorize(
        lambda v: float(np.i0(v)))(a).astype(np.float32)),
    ("imag", (A,), np.imag),
    ("isinf", (A,), np.isinf),
    ("isnan", (A,), np.isnan),
    ("kron", (A, B), np.kron),
    ("ldexp", (A, I1), lambda a, e: np.ldexp(a, e)),
    ("less_equal", (A, B), np.less_equal),
    ("less_than", (A, B), np.less),
    ("logaddexp", (A, B), np.logaddexp),
    ("logical_not", (I1 % 2,), np.logical_not),
    ("logical_xor", (I1 % 2, I2 % 2), np.logical_xor),
    ("median", (A,), np.median),
    ("moveaxis", (A, 0, 1), np.moveaxis),
    ("nanmean", (A,), np.nanmean),
    ("nansum", (A,), np.nansum),
    ("nextafter", (A, B), np.nextafter),
    ("not_equal", (A, B), np.not_equal),
    ("numel", (A,), lambda a: a.size),
    ("quantile", (A, 0.25), lambda a, q: np.quantile(a, q)),
    ("repeat_interleave", (A, 2), lambda a, r: np.repeat(a, r)),
    ("rint", (A,), np.rint),
    ("rot90", (A,), np.rot90),
    ("swapaxes", (A, 0, 1), lambda a, i, j: np.swapaxes(a, i, j)),
    ("trunc", (A,), np.trunc),
    ("cummax", (A, 1), None),  # returns (values, indices)
    ("cummin", (A, 1), None),
]


@pytest.mark.parametrize("name,args,oracle", ELEMENTWISE,
                         ids=[c[0] for c in ELEMENTWISE])
def test_top_level_matches_numpy(name, args, oracle):
    fn = getattr(paddle, name)
    targs = [t(a) if isinstance(a, np.ndarray) else a for a in args]
    out = fn(*targs)
    if name in ("cummax", "cummin"):
        # repo extension (absent from reference v2.3): returns values
        vals = out.numpy()
        ref = (np.maximum if name == "cummax" else
               np.minimum).accumulate(args[0], axis=args[1])
        np.testing.assert_allclose(vals, ref, rtol=1e-6)
        return
    res = out.numpy() if hasattr(out, "numpy") else np.asarray(out)
    ref = oracle(*args)
    np.testing.assert_allclose(np.asarray(res, dtype=np.float64),
                               np.asarray(ref, dtype=np.float64),
                               rtol=1e-4, atol=1e-6)


def test_structural_functions():
    np.testing.assert_allclose(
        paddle.addmm(t(np.ones((2, 2), "float32")),
                     t(A[:2, :2]), t(B[:2, :2].T),
                     beta=0.5, alpha=2.0).numpy(),
        0.5 * np.ones((2, 2)) + 2.0 * (A[:2, :2] @ B[:2, :2].T),
        rtol=1e-5)
    parts = paddle.chunk(t(A), 2, axis=1)
    assert [tuple(p.shape) for p in parts] == [(3, 2), (3, 2)]
    np.testing.assert_array_equal(
        paddle.expand_as(t(A[0]), t(A)).numpy(), np.tile(A[0], (3, 1)))
    assert tuple(paddle.empty_like(t(A)).shape) == (3, 4)
    np.testing.assert_array_equal(paddle.full_like(t(A), 7).numpy(),
                                  np.full((3, 4), 7.0, "float32"))
    g = paddle.meshgrid(t(np.arange(2)), t(np.arange(3)))
    assert tuple(g[0].shape) == (2, 3)
    np.testing.assert_allclose(
        paddle.logspace(0, 2, 3).numpy(), [1, 10, 100], rtol=1e-5)
    np.testing.assert_array_equal(
        paddle.index_select(t(A), t(np.array([2, 0])), axis=0).numpy(),
        A[[2, 0]])
    idx = np.array([[0, 1], [1, 0], [2, 3]])
    np.testing.assert_array_equal(
        paddle.index_sample(t(A), t(idx)).numpy(),
        np.take_along_axis(A, idx, axis=1))
    np.testing.assert_array_equal(
        paddle.kthvalue(t(A), 2, axis=1)[0].numpy(),
        np.sort(A, axis=1)[:, 1])
    h = paddle.histogram(t(A), bins=4, min=-2, max=2)
    assert int(np.asarray(h.numpy()).sum()) == ((A >= -2) & (A <= 2)).sum()
    np.testing.assert_array_equal(
        paddle.bucketize(t(A), t(np.array([-1.0, 0.0, 1.0]))).numpy(),
        np.searchsorted([-1.0, 0.0, 1.0], A))
    td = paddle.tensordot(t(A), t(B.T), axes=1)
    np.testing.assert_allclose(td.numpy(), A @ B.T, rtol=1e-5)
    u = paddle.unique_consecutive(t(np.array([1, 1, 2, 2, 3, 1])))
    np.testing.assert_array_equal(np.asarray(u.numpy()), [1, 2, 3, 1])
    rows = paddle.unstack(t(A), axis=0)
    assert len(rows) == 3
    np.testing.assert_array_equal(rows[1].numpy(), A[1])
    np.testing.assert_array_equal(
        paddle.strided_slice(t(A), axes=[1], starts=[0], ends=[4],
                             strides=[2]).numpy(), A[:, ::2])


def test_scatter_family():
    x = np.zeros((4, 3), "float32")
    updates = np.ones((2, 3), "float32")
    out = paddle.scatter_nd_add(t(x), t(np.array([[1], [3]])), t(updates))
    np.testing.assert_array_equal(out.numpy()[[1, 3]], updates)
    snd = paddle.scatter_nd(t(np.array([[0], [2]])), t(updates), [4, 3])
    np.testing.assert_array_equal(snd.numpy()[[0, 2]], updates)
    pa = paddle.put_along_axis(t(A), t(I1 % 4), 9.0, 1)
    assert (pa.numpy() == 9.0).any()


def test_random_families_run():
    paddle.seed(0)
    assert tuple(paddle.bernoulli(t(np.full((3, 3), 0.5,
                                            "float32"))).shape) == (3, 3)
    assert tuple(paddle.poisson(t(P)).shape) == (3, 4)
    assert tuple(paddle.standard_normal([2, 3]).shape) == (2, 3)
    assert tuple(paddle.standard_gamma(t(P)).shape) == (3, 4)
    assert tuple(paddle.normal(0.0, 1.0, [4]).shape) == (4,)
    st = paddle.get_rng_state()
    a = paddle.standard_normal([4]).numpy()
    paddle.set_rng_state(st)
    b = paddle.standard_normal([4]).numpy()
    np.testing.assert_array_equal(a, b)


def test_dtype_device_utilities():
    assert paddle.finfo(paddle.float32).bits == 32
    assert paddle.iinfo(paddle.int32).max == 2**31 - 1
    assert paddle.get_default_dtype() == "float32"
    paddle.set_default_dtype("float32")
    assert "cpu" in paddle.get_device() or "tpu" in paddle.get_device()
    assert paddle.is_compiled_with_tpu() in (True, False)
    assert paddle.is_grad_enabled() in (True, False)
    paddle.set_printoptions(precision=4)
    flags = paddle.get_flags(["FLAGS_check_nan_inf"])
    assert "FLAGS_check_nan_inf" in flags
    # place objects exist and stringify
    for place in (paddle.TPUPlace(0), paddle.CUDAPlace(0),
                  paddle.CUDAPinnedPlace(), paddle.NPUPlace(0)):
        assert repr(place)
    # a place whose platform has no device here RAISES: a request for
    # the TPU is never answered with the CPU
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        paddle.TPUPlace(0).jax_device()
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"
    with pytest.raises(RuntimeError, match="only 8 'cpu' device"):
        paddle.CPUPlace(8).jax_device()
    x = t(A)
    assert paddle.assign(x).numpy() is not None
    y = x.clone()
    y.tanh_()
    np.testing.assert_allclose(y.numpy(), np.tanh(A), rtol=1e-5)
    np.testing.assert_allclose(paddle.stanh(t(A)).numpy(),
                               1.7159 * np.tanh(0.67 * A), rtol=1e-4)


# -- LR schedulers: reference decay formulas -------------------------------

def _lrs(sched, n=5):
    out = []
    for _ in range(n):
        out.append(sched())
        sched.step()
    return np.asarray(out)


def test_lr_decay_formulas():
    lr = paddle.optimizer.lr
    np.testing.assert_allclose(
        _lrs(lr.ExponentialDecay(0.1, gamma=0.5)),
        0.1 * 0.5 ** np.arange(5), rtol=1e-6)
    np.testing.assert_allclose(
        _lrs(lr.NaturalExpDecay(0.1, gamma=0.3)),
        0.1 * np.exp(-0.3 * np.arange(5)), rtol=1e-6)
    np.testing.assert_allclose(
        _lrs(lr.InverseTimeDecay(0.1, gamma=2.0)),
        0.1 / (1 + 2.0 * np.arange(5)), rtol=1e-6)
    np.testing.assert_allclose(
        _lrs(lr.PolynomialDecay(0.1, decay_steps=4, end_lr=0.01,
                                power=1.0)),
        [0.1, 0.0775, 0.055, 0.0325, 0.01], rtol=1e-6)
    np.testing.assert_allclose(
        _lrs(lr.MultiStepDecay(0.1, milestones=[2, 4], gamma=0.1)),
        [0.1, 0.1, 0.01, 0.01, 0.001], rtol=1e-6)
    np.testing.assert_allclose(
        _lrs(lr.PiecewiseDecay(boundaries=[1, 3], values=[1.0, 0.5, 0.1])),
        [1.0, 0.5, 0.5, 0.1, 0.1], rtol=1e-6)
    np.testing.assert_allclose(
        _lrs(lr.LambdaDecay(0.1, lr_lambda=lambda e: 1.0 / (e + 1))),
        0.1 / (np.arange(5) + 1), rtol=1e-6)
    np.testing.assert_allclose(
        _lrs(lr.MultiplicativeDecay(0.1, lr_lambda=lambda e: 0.9)),
        0.1 * 0.9 ** np.arange(5), rtol=1e-6)


def test_cyclic_and_onecycle_bounds():
    lr = paddle.optimizer.lr
    cyc = _lrs(lr.CyclicLR(base_learning_rate=0.01, max_learning_rate=0.1,
                           step_size_up=4), n=16)
    assert cyc.min() >= 0.01 - 1e-9 and cyc.max() <= 0.1 + 1e-9
    assert cyc.max() > 0.05  # actually climbs
    one = _lrs(lr.OneCycleLR(max_learning_rate=0.1, total_steps=10), n=10)
    assert one.max() <= 0.1 + 1e-9 and one.argmax() not in (0, 9)


def test_precision_recall_metrics():
    m = paddle.metric.Precision()
    # preds > 0.5 -> positive; one false positive out of two predicted
    m.update(np.array([0.9, 0.8, 0.2]), np.array([1, 0, 1]))
    np.testing.assert_allclose(m.accumulate(), 0.5)
    r = paddle.metric.Recall()
    r.update(np.array([0.9, 0.8, 0.2]), np.array([1, 0, 1]))
    np.testing.assert_allclose(r.accumulate(), 0.5)  # 1 of 2 true found
    assert isinstance(m.name(), str)
    m.reset()
    assert np.isnan(m.accumulate()) or m.accumulate() in (0.0,)


def test_incubate_fused_matmul_bias():
    import paddle_tpu.incubate.nn.functional as incf
    x, w = A[:2], B.T[:, :2]
    b = np.float32([0.5, -0.5])
    out = incf.fused_matmul_bias(t(x), t(w), t(b))
    np.testing.assert_allclose(out.numpy(), x @ w + b, rtol=1e-5)


def test_fluid_sequence_tail():
    import paddle_tpu.static as static
    x = t(np.arange(6, dtype=np.float32).reshape(2, 3, 1))
    y = t(np.zeros((2, 3, 1), np.float32))
    out = static.nn.sequence_expand_as(x, y)
    # each row's sequence tiled once per y-row timestep: [B, Ty, Tx, D]
    ref = np.tile(np.arange(6, dtype=np.float32).reshape(2, 1, 3, 1),
                  (1, 3, 1, 1))
    np.testing.assert_allclose(np.asarray(out.numpy()), ref)
    upd = t(np.ones((2, 2, 1), np.float32))
    idx = t(np.array([[0, 2], [1, 0]]))
    sc = static.nn.sequence_scatter(x, idx, upd)
    ref = np.arange(6, dtype=np.float32).reshape(2, 3, 1).copy()
    ref[0, 0] += 1; ref[0, 2] += 1; ref[1, 1] += 1; ref[1, 0] += 1
    np.testing.assert_allclose(np.asarray(sc.numpy()), ref)


def test_static_nn_tail_builders():
    paddle.enable_static()
    try:
        import paddle_tpu.static as static
        with static.program_guard(static.Program()):
            x = static.data("x", [2, 6, 4, 4], "float32")
            g = static.nn.group_norm(x, groups=2)
            assert list(g.shape) == [2, 6, 4, 4]
    finally:
        paddle.disable_static()


def test_lookahead_alpha_extremes():
    """alpha=0: every k-boundary snaps the fast weights BACK to the
    initial slow copy; alpha=1: the sync is a no-op (pure inner SGD)."""
    def run(alpha, k=2, steps=2):
        paddle.seed(0)
        lin = paddle.nn.Linear(2, 1)
        w0 = lin.weight.numpy().copy()
        inner = paddle.optimizer.SGD(learning_rate=0.5,
                                     parameters=lin.parameters())
        la = paddle.incubate.optimizer.LookAhead(inner, alpha=alpha, k=k)
        x = t(np.array([[1.0, 2.0], [3.0, -1.0]], "float32"))
        for _ in range(steps):
            loss = (lin(x) ** 2).mean()
            loss.backward()
            la.step()
            la.clear_grad()
        return w0, lin.weight.numpy().copy()

    w0, w = run(alpha=0.0)
    np.testing.assert_allclose(w, w0, rtol=1e-6)   # snapped back

    paddle.seed(0)
    ref = paddle.nn.Linear(2, 1)
    sgd = paddle.optimizer.SGD(learning_rate=0.5,
                               parameters=ref.parameters())
    x = t(np.array([[1.0, 2.0], [3.0, -1.0]], "float32"))
    for _ in range(2):
        loss = (ref(x) ** 2).mean()
        loss.backward()
        sgd.step()
        sgd.clear_grad()
    _, w1 = run(alpha=1.0)
    np.testing.assert_allclose(w1, ref.weight.numpy(), rtol=1e-6)


def test_model_average_context_manager():
    paddle.seed(1)
    lin = paddle.nn.Linear(3, 1)
    ps = lin.parameters()
    ma = paddle.incubate.optimizer.ModelAverage(0.15, parameters=ps)
    snaps = []
    # drive the weights on a deliberately moving trajectory
    for i in range(3):
        lin.weight._data = lin.weight._data + np.float32(0.1 * (i + 1))
        ma.step()
        snaps.append(lin.weight.numpy().copy())
    live = snaps[-1]
    with ma.apply():
        inside = lin.weight.numpy().copy()
    np.testing.assert_allclose(inside, np.mean(snaps, axis=0), rtol=1e-6)
    assert not np.allclose(inside, live)
    np.testing.assert_allclose(lin.weight.numpy(), live)  # restored
    with ma.apply(need_restore=False):
        pass
    np.testing.assert_allclose(lin.weight.numpy(),
                               np.mean(snaps, axis=0), rtol=1e-6)
