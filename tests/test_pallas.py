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


# (batch, sq, sk, heads, head_dim, causal, dtype, block_q, block_k); blocks
# None = the plan's.  The first cases are the small shapes these tests
# always had; the others are the shape classes the plan must serve.
_FLASH_CASES = {
    "small": (2, 128, 128, 2, 32, False, "float32", 64, 64),
    "small-causal": (2, 128, 128, 2, 32, True, "float32", 64, 64),
    "tiny": (1, 64, 64, 2, 16, False, "float32", 32, 32),
    "tiny-causal": (1, 64, 64, 2, 16, True, "float32", 32, 32),
    # GPT-2's head geometry at the train cell's length: the wide plan,
    # two heads a grid step, the mask on the diagonal blocks only
    "cell-f32": (1, 1024, 1024, 12, 64, True, "float32", None, None),
    "cell-bf16": (1, 1024, 1024, 12, 64, True, "bfloat16", None, None),
    "odd-heads": (1, 512, 512, 3, 64, True, "float32", None, None),
    "d128": (1, 512, 512, 2, 128, True, "float32", None, None),
    "cross": (2, 256, 384, 4, 64, False, "float32", None, None),
    "not-wide": (1, 640, 640, 2, 64, True, "float32", None, None),
    # (3072 + 3072) * 256 is past the resident budget: the streamed family
    "streamed": (1, 3072, 3072, 1, 256, True, "float32", None, None),
}


def _flash_case(name):
    b, sq, sk, h, d, causal, dtype, bq, bk = _FLASH_CASES[name]
    r = np.random.RandomState(len(name))
    q, k, v, w = (jnp.asarray(r.randn(b, s_, h, d), dtype)
                  for s_ in (sq, sk, sk, sq))
    fa = functools.partial(pk.flash_attention, is_causal=causal,
                           block_q=bq, block_k=bk)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    return (q, k, v), f32, w.astype(jnp.float32), fa, causal


class TestFlashAttention:
    @pytest.mark.parametrize("case", list(_FLASH_CASES))
    def test_forward_parity(self, case):
        qkv, f32, _, fa, causal = _flash_case(case)
        ref = _lax_sdpa(*f32, causal)
        out = fa(*qkv)
        assert out.dtype == qkv[0].dtype
        tol = 2e-5 if out.dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=tol, rtol=tol)

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

    @pytest.mark.parametrize("case", list(_FLASH_CASES))
    def test_grad_parity(self, case):
        qkv, f32, w, fa, causal = _flash_case(case)

        def loss_ref(q, k, v):
            return jnp.sum(_lax_sdpa(q, k, v, causal) * w)  # cotangent mix

        def loss_fa(q, k, v):
            return jnp.sum(fa(q, k, v).astype(jnp.float32) * w)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(*f32)
        gf = jax.grad(loss_fa, argnums=(0, 1, 2))(*qkv)
        tol = 5e-5 if qkv[0].dtype == jnp.float32 else 3e-2
        for a, b_ in zip(gf, gr):
            assert a.dtype == qkv[0].dtype
            # bf16: 3e-2 of the gradient's own scale, as the forward's of 1
            scale = 1.0 if tol == 5e-5 else float(jnp.abs(b_).max())
            np.testing.assert_allclose(
                np.asarray(a, np.float32) / scale, np.asarray(b_) / scale,
                atol=tol, rtol=tol)

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
    """The 3D-grid streaming kernels (no sequence cap, one head a step on
    [B*H, S, D]) must agree with the VMEM-resident kernels and the lax
    reference."""

    def test_streaming_matches_resident_fwd_bwd(self):
        r = np.random.RandomState(0)
        q = jnp.asarray(r.randn(2, 256, 64), jnp.float32)
        k = jnp.asarray(r.randn(2, 256, 64), jnp.float32)
        v = jnp.asarray(r.randn(2, 256, 64), jnp.float32)
        for causal in (False, True):
            o_s, lse_s = pk._fa_call_fwd(q, k, v, 0.125, causal, 128, 128)
            # the resident family on the same arrays: one head of 64 lanes
            o_r, lse_r = pk._fa_call_fwd_resident(q, k, v, 0.125, causal,
                                                  128, 128, 64)
            np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_r),
                                       atol=1e-5)
            # lse: [BH, S, 8] lane-replicated | rows [B, groups, heads, S]
            np.testing.assert_allclose(np.asarray(lse_s[:, :, 0]),
                                       np.asarray(lse_r[:, 0, 0, :]),
                                       atol=1e-5)
            do = jnp.asarray(r.randn(2, 256, 64), jnp.float32)
            gs = pk._fa_call_bwd(q, k, v, o_s, lse_s, do, 0.125, causal,
                                 128, 128)
            gr = pk._fa_call_bwd_resident(q, k, v, o_r, lse_r, do, 0.125,
                                          causal, 128, 128, 64)
            for a, b in zip(gs, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-4)

    def test_dispatch_picks_streaming_beyond_vmem_budget(self):
        assert pk._use_resident(1024, 1024, 64)
        assert not pk._use_resident(16384, 16384, 128)
        # predicate no longer caps the sequence
        assert pk._fa_supported(
            np.zeros((1, 32768, 4, 128)), np.zeros((1, 32768, 4, 128)),
            None, None, None, 0.0, True)


_STREAMED_PLAN = dict(
    block_q=512, block_k=512, bwd_block_q=512, bwd_block_k=512,
    heads_per_step=1, resident=False, packed=False)


def _plan(block_q, block_k, bwd_block_q, bwd_block_k, heads_per_step,
          resident, packed):
    return dict(block_q=block_q, block_k=block_k, bwd_block_q=bwd_block_q,
                bwd_block_k=bwd_block_k, heads_per_step=heads_per_step,
                resident=resident, packed=packed)


class TestFlashAttentionPlan:
    """``flash_attention_plan``: what one grid step holds, pinned."""

    @pytest.fixture(autouse=True)
    def no_autotune_cache(self, tmp_path, monkeypatch):
        from paddle_tpu.ops import autotune_cache as at
        monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE_DIR", str(tmp_path))
        at.set_device_kind("testdev")
        at.clear()
        yield
        at.clear()
        at.set_device_kind(None)

    @pytest.mark.parametrize("shape, want", [
        # (sq, sk, d, heads, causal)
        # gpt2-124m.train: wide tiles, both heads of a pair a step, on
        # [B, S, H*D] as it stands
        ((1024, 1024, 64, 12, True), _plan(512, 512, 256, 256, 2, True, True)),
        # gpt2-large's 20 heads, 2,048 long
        ((2048, 2048, 64, 20, True), _plan(512, 512, 256, 256, 2, True, True)),
        # BERT: one 128 block (and under FLASH_MIN_SEQ: lax by default)
        ((128, 128, 64, 12, False), _plan(128, 128, 128, 128, 2, True, True)),
        # cross attention, lengths that are multiples of 128 only
        ((384, 640, 64, 8, False), _plan(128, 128, 128, 128, 2, True, True)),
        # a multiple of 256 but not of 512
        ((768, 768, 64, 12, True), _plan(256, 256, 256, 256, 2, True, True)),
        # an odd head count cannot pair: one head a step, transposed
        ((1024, 1024, 64, 3, True), _plan(512, 512, 256, 256, 1, True, False)),
        # four heads of 32 fill the 128 lanes
        ((512, 512, 32, 4, True), _plan(256, 256, 256, 256, 4, True, True)),
        # D of 128 and 256: a head is a lane block (or two) of its own
        ((1024, 1024, 128, 8, True), _plan(512, 512, 256, 256, 1, True, True)),
        ((1024, 1024, 256, 4, True), _plan(512, 512, 256, 256, 1, True, True)),
        # 32 k: past the resident budget, the streamed family
        ((32768, 32768, 128, 4, True), _STREAMED_PLAN),
        # a length the 8-row tiling allows and 128 does not divide
        ((64, 64, 16, 2, True), _plan(64, 64, 64, 64, 1, True, False)),
    ])
    def test_plan_table(self, shape, want):
        sq, sk, d, heads, causal = shape
        plan = pk.flash_attention_plan(sq, sk, d, heads, causal, "bfloat16")
        assert plan == want
        # legal: the tiles divide the lengths, a step's lanes are whole
        # 128-lane blocks of [B, S, H*D] or the one head of [B*H, S, D]
        assert sq % plan["block_q"] == 0 and sq % plan["bwd_block_q"] == 0
        assert sk % plan["block_k"] == 0 and sk % plan["bwd_block_k"] == 0
        lanes = plan["heads_per_step"] * d
        assert heads % plan["heads_per_step"] == 0
        assert lanes % 128 == 0 or heads == 1 or not plan["packed"]

    def test_cell_plan_needs_no_autotune_cache(self):
        from paddle_tpu.ops import autotune_cache as at
        q = jnp.zeros((16, 1024, 12, 64), jnp.bfloat16)
        assert at.stats()["entries"] == 0
        assert pk._fa_supported(q, q, q, None, None, 0.0, True)
        assert pk._tuned_blocks(q, q, True) == (512, 512)

    def test_cache_entry_still_overrides_the_plan(self):
        from paddle_tpu.ops import autotune_cache as at
        q = jnp.zeros((16, 1024, 12, 64), jnp.bfloat16)
        at.record("scaled_dot_product_attention",
                  pk._sdpa_key(16, 12, 1024, 1024, 64, q.dtype, True),
                  "pallas:256x128", persist=False)
        assert pk._tuned_blocks(q, q, True) == (256, 128)
        # and what the dispatch then builds runs: both passes on 256 x 128
        r = np.random.RandomState(3)
        x = [jnp.asarray(r.randn(1, 256, 2, 64), jnp.float32)
             for _ in range(3)]
        ref = _lax_sdpa(*x, True)
        out = pk.flash_attention(*x, is_causal=True, block_q=256,
                                 block_k=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_plan_is_logged_once_a_shape(self, caplog):
        import logging
        pk._log_plan.cache_clear()
        x = jnp.zeros((1, 256, 2, 64), jnp.float32)
        with caplog.at_level(logging.INFO, logger=pk.logger.name):
            pk.flash_attention(x, x, x, is_causal=True)
            pk.flash_attention(x, x, x, is_causal=True)
        lines = [r.getMessage() for r in caplog.records
                 if "flash_attention" in r.getMessage()]
        assert len(lines) == 1 and "heads_per_step" in lines[0]


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
