"""Parity tests for the Pallas kernel tier (interpret mode on CPU).

The lax compositions in ops/nn_ops.py are the reference; each Pallas kernel
must match them in fwd and grad (SURVEY §4: OpTest check_output/check_grad
analog, applied to the custom-kernel layer)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.registry import get_op

rng = np.random.RandomState(0)


def _lax_sdpa(q, k, v, causal):
    return get_op("scaled_dot_product_attention").fn(
        q, k, v, None, None, is_causal=causal)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, causal):
        b, s, h, d = 2, 128, 2, 32
        q = rng.randn(b, s, h, d).astype(np.float32)
        k = rng.randn(b, s, h, d).astype(np.float32)
        v = rng.randn(b, s, h, d).astype(np.float32)
        ref = _lax_sdpa(q, k, v, causal)
        out = pk.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), is_causal=causal,
                                 block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_forward_parity_cross_length(self):
        # non-causal with kv longer than q
        b, h, d = 1, 2, 32
        q = rng.randn(b, 64, h, d).astype(np.float32)
        k = rng.randn(b, 128, h, d).astype(np.float32)
        v = rng.randn(b, 128, h, d).astype(np.float32)
        ref = _lax_sdpa(q, k, v, False)
        out = pk.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_parity(self, causal):
        b, s, h, d = 1, 64, 2, 16
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        w = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)  # cotangent mix

        def loss_ref(q, k, v):
            return jnp.sum(_lax_sdpa(q, k, v, causal) * w)

        def loss_fa(q, k, v):
            return jnp.sum(pk.flash_attention(
                q, k, v, is_causal=causal, block_q=32, block_k=32) * w)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5, rtol=5e-5)

    def test_bf16_forward(self):
        b, s, h, d = 1, 64, 2, 32
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        ref = _lax_sdpa(q, k, v, True)
        out = pk.flash_attention(q, k, v, is_causal=True,
                                 block_q=32, block_k=32)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

    def test_dispatch_override_selected(self):
        # through the public F.scaled_dot_product_attention path
        b, s, h, d = 1, 128, 2, 32
        q = rng.randn(b, s, h, d).astype(np.float32)
        base = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q),
            is_causal=True).numpy()
        try:
            set_flags({"FLAGS_pallas_force": True})
            out = F.scaled_dot_product_attention(
                paddle.to_tensor(q), paddle.to_tensor(q),
                paddle.to_tensor(q), is_causal=True).numpy()
        finally:
            set_flags({"FLAGS_pallas_force": False})
        np.testing.assert_allclose(out, base, atol=2e-5, rtol=2e-5)


class TestFusedLayerNorm:
    def test_forward_parity(self):
        x = rng.randn(6, 128, 64).astype(np.float32)
        w = rng.randn(64).astype(np.float32)
        b = rng.randn(64).astype(np.float32)
        ref = get_op("layer_norm").fn(x, w, b, epsilon=1e-5)
        out = pk.fused_layer_norm(jnp.asarray(x), jnp.asarray(w),
                                  jnp.asarray(b))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_grad_parity(self):
        x = jnp.asarray(rng.randn(4, 64, 32), jnp.float32)
        w = jnp.asarray(rng.randn(32), jnp.float32)
        b = jnp.asarray(rng.randn(32), jnp.float32)
        ct = jnp.asarray(rng.randn(4, 64, 32), jnp.float32)

        def loss_ref(x, w, b):
            return jnp.sum(get_op("layer_norm").fn(x, w, b) * ct)

        def loss_pl(x, w, b):
            return jnp.sum(pk.fused_layer_norm(x, w, b) * ct)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
        gp = jax.grad(loss_pl, argnums=(0, 1, 2))(x, w, b)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=2e-4)

    def test_dispatch_override_selected(self):
        import paddle_tpu.nn as nn
        ln = nn.LayerNorm(64)
        x = paddle.to_tensor(rng.randn(2, 128, 64).astype(np.float32))
        base = ln(x).numpy()
        try:
            set_flags({"FLAGS_pallas_force": True})
            out = ln(x).numpy()
        finally:
            set_flags({"FLAGS_pallas_force": False})
        np.testing.assert_allclose(out, base, atol=1e-5, rtol=1e-5)

    def test_layer_norm_train_step_with_override(self):
        # grads flow through the Pallas LN inside a real layer
        import paddle_tpu.nn as nn
        try:
            set_flags({"FLAGS_pallas_force": True})
            ln = nn.LayerNorm(32)
            x = paddle.to_tensor(rng.randn(4, 32).astype(np.float32),
                                 stop_gradient=False)
            loss = ln(x).sum()
            loss.backward()
            assert x.grad is not None
            assert ln.weight.grad is not None
            assert ln.bias.grad is not None
        finally:
            set_flags({"FLAGS_pallas_force": False})


class TestFusedAdamW:
    def test_parity_with_rule(self):
        import paddle_tpu.optimizer as opt
        shape = (3, 50)  # deliberately not lane-aligned (pad path)
        p = jnp.asarray(rng.randn(*shape), jnp.float32)
        g = jnp.asarray(rng.randn(*shape), jnp.float32)
        m = jnp.asarray(rng.randn(*shape), jnp.float32) * 0.1
        v = jnp.abs(jnp.asarray(rng.randn(*shape), jnp.float32)) * 0.1
        o = opt.AdamW(learning_rate=1e-2, weight_decay=0.05)
        ref_p, ref_slots = o._rule(p, g, {"moment1": m, "moment2": v},
                                   1e-2, 3)
        new_p, new_m, new_v = pk.fused_adamw(
            p, g, m, v, lr=1e-2, beta1=o._beta1, beta2=o._beta2,
            eps=o._eps, weight_decay=0.05, step=3)
        np.testing.assert_allclose(np.asarray(new_p), np.asarray(ref_p),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(new_m),
                                   np.asarray(ref_slots["moment1"]),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(new_v),
                                   np.asarray(ref_slots["moment2"]),
                                   atol=1e-6, rtol=1e-6)

    def test_eager_step_fused_matches_unfused(self):
        import paddle_tpu.optimizer as opt
        from paddle_tpu.framework.tensor import Parameter, Tensor

        def run(forced):
            p = Parameter(jnp.asarray(np.full((5, 7), 1.5, np.float32)))
            o = opt.AdamW(learning_rate=1e-2, weight_decay=0.1,
                          parameters=[p])
            try:
                set_flags({"FLAGS_pallas_force": forced})
                for i in range(3):
                    p.grad = Tensor(jnp.full((5, 7), 0.5 + i, jnp.float32))
                    o.step()
            finally:
                set_flags({"FLAGS_pallas_force": False})
            return np.asarray(p._data)

        np.testing.assert_allclose(run(True), run(False),
                                   atol=1e-6, rtol=1e-6)


class TestStreamingFlashVariant:
    """The 3D-grid streaming kernels (no sequence cap) must agree with
    the VMEM-resident kernels and the lax reference."""

    def test_streaming_matches_resident_fwd_bwd(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops import pallas_kernels as pk

        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
        k = jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
        v = jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
        for causal in (False, True):
            o_s, lse_s = pk._fa_call_fwd(q, k, v, 0.125, causal, 128, 128)
            o_r, lse_r = pk._fa_call_fwd_resident(q, k, v, 0.125, causal,
                                                  128, 128)
            np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_r),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(lse_s),
                                       np.asarray(lse_r), atol=1e-5)
            do = jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
            gs = pk._fa_call_bwd(q, k, v, o_s, lse_s, do, 0.125, causal,
                                 128, 128)
            gr = pk._fa_call_bwd_resident(q, k, v, o_r, lse_r, do, 0.125,
                                          causal, 128, 128)
            for a, b in zip(gs, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-4)

    def test_dispatch_picks_streaming_beyond_vmem_budget(self):
        from paddle_tpu.ops import pallas_kernels as pk
        assert pk._use_resident(1024, 1024, 64)
        assert not pk._use_resident(16384, 16384, 128)
        # predicate no longer caps the sequence
        assert pk._fa_supported(
            np.zeros((1, 32768, 4, 128)), np.zeros((1, 32768, 4, 128)),
            None, None, None, 0.0, True)


class TestSmokeGate:
    """``pallas_smoke.ensure()``: a kernel that fails its smoke on the
    chip is an error naming the kernel — never a silent
    ``FLAGS_use_pallas=False`` and a run on the lax compositions."""

    def test_failed_smoke_raises_and_leaves_the_flag_alone(self,
                                                           monkeypatch):
        from paddle_tpu.framework.flags import flag_value
        from paddle_tpu.ops import pallas_smoke

        def refused():
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(pk, "_on_tpu", lambda: True)
        monkeypatch.setitem(pallas_smoke._state, "passed", False)
        monkeypatch.setattr(pallas_smoke, "_KERNEL_SMOKES",
                            {"ragged_paged_attention": refused})
        assert flag_value("FLAGS_use_pallas") is True
        with pytest.raises(pallas_smoke.PallasSmokeError,
                           match="ragged_paged_attention.*Mosaic failed"):
            pallas_smoke.ensure()
        assert flag_value("FLAGS_use_pallas") is True
        assert pallas_smoke._state["passed"] is False

    def test_off_tpu_and_switched_off_are_not_gated(self, monkeypatch):
        from paddle_tpu.ops import pallas_smoke
        monkeypatch.setattr(pallas_smoke, "_KERNEL_SMOKES",
                            {"boom": lambda: 1 / 0})
        assert pallas_smoke.ensure() is True          # CPU: nothing to gate
        monkeypatch.setattr(pk, "_on_tpu", lambda: True)
        monkeypatch.setitem(pallas_smoke._state, "passed", False)
        set_flags({"FLAGS_use_pallas": False})        # the user's choice
        try:
            assert pallas_smoke.ensure() is False
        finally:
            set_flags({"FLAGS_use_pallas": True})
