"""Fused transformer layers (reference incubate/nn/layer/
fused_transformer.py:176/437/641)."""
from __future__ import annotations

import math

import numpy as np
from typing import Optional

from .... import nn
from ....framework.dispatch import call_op
from ....nn import functional as F
from ....nn.layer.layers import Layer

__all__ = ["FusedMultiHeadAttention", "FusedFeedForward",
           "FusedTransformerEncoderLayer"]


class FusedMultiHeadAttention(Layer):
    """Attention + residual + (pre/post) LayerNorm in one module
    (reference fused_transformer.py:176 — fused_attention_op.cu).

    On TPU the attention core runs through
    ``F.scaled_dot_product_attention`` (Pallas flash attention when the
    shapes qualify) and the LN through the fused Pallas LN; XLA fuses
    the qkv bias add, dropout and residual epilogues.
    """

    def __init__(self, embed_dim, num_heads, dropout_rate=0.5,
                 attn_dropout_rate=0.5, normalize_before=False,
                 need_weights=False, qkv_weight_attr=None,
                 qkv_bias_attr=None, linear_weight_attr=None,
                 linear_bias_attr=None, pre_ln_scale_attr=None,
                 pre_ln_bias_attr=None, ln_scale_attr=None,
                 ln_bias_attr=None, epsilon=1e-5, name=None):
        super().__init__()
        if embed_dim % num_heads:
            raise ValueError("num_heads must divide embed_dim")
        if need_weights:
            raise NotImplementedError(
                "need_weights=True is unsupported (the reference fused op "
                "asserts the same); use nn.MultiHeadAttention to inspect "
                "attention weights")
        attrs = [qkv_weight_attr, qkv_bias_attr, linear_weight_attr,
                 linear_bias_attr, pre_ln_scale_attr, pre_ln_bias_attr,
                 ln_scale_attr, ln_bias_attr]
        if any(a is not None for a in attrs):
            raise NotImplementedError(
                "ParamAttr-based initializers are not wired for the fused "
                "layers; initialize via state_dict/set_state_dict instead "
                "of silently ignoring the attrs")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.dropout_rate = dropout_rate
        self.attn_dropout_rate = attn_dropout_rate
        self.qkv_proj = nn.Linear(embed_dim, 3 * embed_dim)
        self.out_proj = nn.Linear(embed_dim, embed_dim)
        self.norm = nn.LayerNorm(embed_dim, epsilon=epsilon)

    def forward(self, x, attn_mask=None, cache=None, time_step=None):
        b, s, d = x.shape
        residual = x
        if self.normalize_before:
            x = self.norm(x)
        qkv = self.qkv_proj(x)                       # [B, S, 3D]
        qkv = call_op("reshape", qkv,
                      shape=(b, s, 3, self.num_heads, self.head_dim))
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]                             # [B, S, H, Dh]
        if cache is None:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                dropout_p=self.attn_dropout_rate if self.training else 0.0)
            new_cache = None
        else:
            out, new_cache = self._cached_attention(q, k, v, cache,
                                                    time_step, attn_mask)
        out = call_op("reshape", out, shape=(b, s, d))
        out = self.out_proj(out)
        if self.dropout_rate and self.training:
            out = F.dropout(out, p=self.dropout_rate, training=True)
        out = residual + out
        if not self.normalize_before:
            out = self.norm(out)
        return out if cache is None else (out, new_cache)

    def _cached_attention(self, q, k, v, cache, time_step, attn_mask):
        """Fixed-capacity CacheKV attention, the reference kernel's
        layout: cache [2, B, H, max_len, Dh]
        (fused_multi_transformer_op.cu:1). time_step=None is the context
        (prefill) stage — the prompt's K/V land at slots [0, S); an
        int/Tensor SCALAR time_step writes the chunk at [t, t+S) (S=1 is
        the usual decode step); a VECTOR time_step [B] is the
        slot-indexed update for pooled decode: example b's chunk lands
        at [t_b, t_b+S) with a per-row causal horizon, so sequences at
        DIFFERENT positions decode in one batch. This is the
        CacheKV-layout counterpart of the continuous-batching serving
        engine's step (models/generation.py ``build_fused_step_fn``,
        over the paged ``serving.PagedKVPool``) — the engine does
        NOT call through here; both are pinned to ``generate()``'s
        semantics by their own parity tests. Queries attend
        causally to slots <= their own, intersected with any caller
        attn_mask. Functional update: the new cache is RETURNED, not
        aliased."""
        import jax.numpy as jnp
        from jax import lax

        from ....framework.tensor import Tensor
        ckv = cache._data if isinstance(cache, Tensor) else \
            jnp.asarray(cache)
        max_len = ckv.shape[3]
        # [B, S, H, Dh] -> the cache's [B, H, S, Dh]
        kv = jnp.stack([jnp.swapaxes(k._data, 1, 2),
                        jnp.swapaxes(v._data, 1, 2)]).astype(ckv.dtype)
        z = jnp.int32(0)
        s = q.shape[1]
        b = q.shape[0]
        if time_step is None:                         # prefill
            start = 0
        else:
            ts = time_step._data if isinstance(time_step, Tensor) else \
                time_step
            start = ts
        if getattr(start, "ndim", 0) == 1:            # slot-indexed [B]
            return self._slot_indexed_attention(q, kv, ckv, start,
                                                attn_mask, max_len, s, b)
        if isinstance(start, (int, np.integer)):
            if int(start) + s > max_len:
                raise ValueError(
                    f"time_step {int(start)} + chunk {s} exceeds the "
                    f"cache capacity {max_len} — dynamic_update_slice "
                    f"would silently clamp and corrupt slot "
                    f"{max_len - 1}")
        pos = jnp.asarray(start, jnp.int32).reshape(())
        # query at slot pos+i attends to cache slots <= pos+i
        valid = (jnp.arange(max_len)[None, :] <=
                 (pos + jnp.arange(s))[:, None])[None, None]  # [1,1,S,L]
        if attn_mask is not None:
            m = attn_mask._data if isinstance(attn_mask, Tensor) else \
                jnp.asarray(attn_mask)
            if m.shape[-1] not in (1, max_len):
                raise ValueError(
                    f"attn_mask last dim {m.shape[-1]} must equal the "
                    f"cache capacity max_len={max_len} (or be 1 for a "
                    f"per-query broadcast): cached attention scores span "
                    f"every cache slot, so a prompt-length mask cannot "
                    f"broadcast against them — pad the mask to max_len "
                    f"(False / -inf for empty slots)")
            if m.dtype == jnp.bool_:
                mask = valid & m
            else:  # additive float mask: keep it, kill invalid slots
                mask = jnp.where(valid, m.astype(jnp.float32), -1e30)
        else:
            mask = valid
        ckv = lax.dynamic_update_slice(ckv, kv, (z, z, z, pos, z))
        k_full = Tensor(jnp.swapaxes(ckv[0], 1, 2))   # [B, L, H, Dh]
        v_full = Tensor(jnp.swapaxes(ckv[1], 1, 2))
        out = F.scaled_dot_product_attention(
            q, k_full, v_full, attn_mask=Tensor(mask))
        return out, Tensor(ckv, stop_gradient=True)

    def _slot_indexed_attention(self, q, kv, ckv, starts, attn_mask,
                                max_len, s, b):
        """Per-example time_step [B]: example b's S-chunk scatters to
        time indices [starts[b], starts[b]+S) and its queries see slots
        <= starts[b]+i. One trace serves every position mix (starts is
        traced), which is what lets a continuous batcher decode
        sequences of different lengths in one program. (The serving
        engine's own step is ``build_fused_step_fn`` over a paged pool;
        this is the incubate API's dense-cache form.)"""
        import jax.numpy as jnp

        from ....framework.tensor import Tensor
        starts = jnp.asarray(starts, jnp.int32).reshape(-1)
        if starts.shape[0] != b:
            raise ValueError(
                f"vector time_step has {starts.shape[0]} entries for "
                f"batch {b}")
        tidx = starts[:, None] + jnp.arange(s)[None, :]        # [B, S]
        # concrete starts get the same loud capacity check as the scalar
        # path (an out-of-range scatter index silently DROPS the write);
        # traced starts can't be inspected — their bound is the serving
        # engine's admission contract
        try:
            hi = int(np.max(np.asarray(starts)))
        except Exception:                               # noqa: BLE001
            hi = None                                   # traced under jit
        if hi is not None and hi + s > max_len:
            raise ValueError(
                f"time_step max {hi} + chunk {s} exceeds the cache "
                f"capacity {max_len}")
        # kv [2, B, H, S, Dh] -> scatter rows at [b, tidx[b, i]]
        val = jnp.transpose(kv, (1, 3, 0, 2, 4))       # [B, S, 2, H, Dh]
        ckv = ckv.at[:, jnp.arange(b)[:, None], :, tidx].set(val)
        # query i of example b attends to slots <= starts[b] + i
        valid = (jnp.arange(max_len)[None, None, :] <=
                 tidx[:, :, None])[:, None]            # [B, 1, S, L]
        if attn_mask is not None:
            m = attn_mask._data if isinstance(attn_mask, Tensor) else \
                jnp.asarray(attn_mask)
            if m.shape[-1] not in (1, max_len):
                raise ValueError(
                    f"attn_mask last dim {m.shape[-1]} must equal the "
                    f"cache capacity max_len={max_len} (or be 1 for a "
                    f"per-query broadcast)")
            if m.dtype == jnp.bool_:
                mask = valid & m
            else:
                mask = jnp.where(valid, m.astype(jnp.float32), -1e30)
        else:
            mask = valid
        k_full = Tensor(jnp.swapaxes(ckv[0], 1, 2))    # [B, L, H, Dh]
        v_full = Tensor(jnp.swapaxes(ckv[1], 1, 2))
        out = F.scaled_dot_product_attention(
            q, k_full, v_full, attn_mask=Tensor(mask))
        return out, Tensor(ckv, stop_gradient=True)


class FusedFeedForward(Layer):
    """FFN + residual + (pre/post) LN (reference fused_transformer.py:437
    — fused_feedforward_op)."""

    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1,
                 epsilon=1e-5, activation="relu", act_dropout_rate=None,
                 normalize_before=False, linear1_weight_attr=None,
                 linear1_bias_attr=None, linear2_weight_attr=None,
                 linear2_bias_attr=None, ln1_scale_attr=None,
                 ln1_bias_attr=None, ln2_scale_attr=None,
                 ln2_bias_attr=None, name=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.dropout_rate = dropout_rate
        self.act_dropout_rate = dropout_rate if act_dropout_rate is None \
            else act_dropout_rate
        # dispatch by NAME through the functional registry — silently
        # substituting gelu for an unknown activation trains a different
        # model with no diagnostic
        if not hasattr(F, activation):
            raise ValueError(
                f"unknown activation {activation!r} (no "
                f"paddle_tpu.nn.functional.{activation})")
        self.activation = activation
        self.linear1 = nn.Linear(d_model, dim_feedforward)
        self.linear2 = nn.Linear(dim_feedforward, d_model)
        self.norm = nn.LayerNorm(d_model, epsilon=epsilon)

    def forward(self, src, cache=None):
        residual = src
        x = self.norm(src) if self.normalize_before else src
        x = self.linear1(x)
        x = getattr(F, self.activation)(x)
        if self.act_dropout_rate and self.training:
            x = F.dropout(x, p=self.act_dropout_rate, training=True)
        x = self.linear2(x)
        if self.dropout_rate and self.training:
            x = F.dropout(x, p=self.dropout_rate, training=True)
        out = residual + x
        if not self.normalize_before:
            out = self.norm(out)
        return out


class FusedTransformerEncoderLayer(Layer):
    """Reference fused_transformer.py:641: FusedMultiHeadAttention +
    FusedFeedForward."""

    def __init__(self, d_model, nhead, dim_feedforward,
                 dropout_rate=0.1, activation="relu", attn_dropout_rate=None,
                 act_dropout_rate=None, normalize_before=False):
        super().__init__()
        self.fused_attn = FusedMultiHeadAttention(
            d_model, nhead,
            dropout_rate=dropout_rate,
            attn_dropout_rate=(dropout_rate if attn_dropout_rate is None
                               else attn_dropout_rate),
            normalize_before=normalize_before)
        self.ffn = FusedFeedForward(
            d_model, dim_feedforward, dropout_rate=dropout_rate,
            activation=activation,
            act_dropout_rate=act_dropout_rate,
            normalize_before=normalize_before)

    def forward(self, src, src_mask=None, cache=None, time_step=None):
        if cache is None:
            out = self.fused_attn(src, attn_mask=src_mask)
            return self.ffn(out)
        out, new_cache = self.fused_attn(src, attn_mask=src_mask,
                                         cache=cache, time_step=time_step)
        return self.ffn(out), new_cache


class FusedLinear(Layer):
    """Reference incubate/nn/layer/fc.py FusedLinear — cublasLt-epilogue
    fused matmul+bias there; XLA fuses the same epilogue on TPU, so this
    is the plain expression with the reference's transpose_weight knob."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, transpose_weight=False, name=None):
        super().__init__()
        self._transpose_weight = transpose_weight
        shape = [out_features, in_features] if transpose_weight else \
            [in_features, out_features]
        self.weight = self.create_parameter(shape, attr=weight_attr)
        self.bias = None if bias_attr is False else \
            self.create_parameter([out_features], attr=bias_attr,
                                  is_bias=True)

    def forward(self, x):
        from ....nn import functional as F
        w = self.weight
        if self._transpose_weight:
            from ....framework.dispatch import call_op
            w = call_op("transpose", w, perm=[1, 0])
        return F.linear(x, w, self.bias)


class FusedBiasDropoutResidualLayerNorm(Layer):
    """Reference fused_transformer.py FusedBiasDropoutResidualLayerNorm:
    y = layer_norm(residual + dropout(x + bias)) in one kernel there;
    one fused XLA region here (LN itself takes the Pallas fused path)."""

    def __init__(self, embed_dim, dropout_rate=0.5, weight_attr=None,
                 bias_attr=None, epsilon=1e-5, name=None):
        super().__init__()
        from ....nn.initializer import Constant
        self._dropout_rate = dropout_rate
        self._epsilon = epsilon
        self.linear_bias = self.create_parameter(
            [embed_dim], attr=bias_attr, is_bias=True)
        self.ln_scale = self.create_parameter(
            [embed_dim], attr=weight_attr,
            default_initializer=Constant(1.0))
        self.ln_bias = self.create_parameter([embed_dim], is_bias=True)

    def forward(self, x, residual):
        from ....nn import functional as F
        y = x + self.linear_bias
        if self._dropout_rate:
            y = F.dropout(y, p=self._dropout_rate,
                          training=self.training)
        return F.layer_norm(residual + y, y.shape[-1:],
                            weight=self.ln_scale, bias=self.ln_bias,
                            epsilon=self._epsilon)


class FusedMultiTransformer(Layer):
    """Reference fused_transformer.py FusedMultiTransformer — the fused
    GPT decoder stack (fused_multi_transformer_op.cu): pre-LN attention
    (causal) + FFN per layer. Here each layer rides the flash-attention
    dispatch and XLA's epilogue fusion; weights live in per-layer
    sublayers rather than the reference's flat weight lists."""

    def __init__(self, embed_dim, num_heads, dim_feedforward,
                 dropout_rate=0.0, activation="gelu",
                 normalize_before=True, ln_scale_attrs=None,
                 num_layers=-1, nranks=1, ring_id=-1, name=None):
        super().__init__()
        if num_layers < 1:
            raise ValueError("num_layers must be >= 1")
        if not normalize_before:
            raise NotImplementedError(
                "FusedMultiTransformer is pre-LN by definition in the "
                "reference kernel; normalize_before=False is not a "
                "supported configuration there either")
        from ....nn import LayerList
        self.layers = LayerList([
            FusedTransformerEncoderLayer(
                embed_dim, num_heads, dim_feedforward,
                dropout_rate=dropout_rate, activation=activation,
                normalize_before=True)
            for _ in range(num_layers)])

    def gen_cache(self, batch, max_len, dtype="float32"):
        """Preallocate the per-layer CacheKV tensors the reference makes
        callers build by hand: list of [2, B, num_heads, max_len,
        head_dim] zeros (fused_multi_transformer_op.cu CacheKV layout)."""
        import jax.numpy as jnp

        from ....framework.tensor import Tensor
        a = self.layers[0].fused_attn
        shape = (2, batch, a.num_heads, max_len, a.head_dim)
        return [Tensor(jnp.zeros(shape, jnp.dtype(dtype)),
                       stop_gradient=True) for _ in self.layers]

    def forward(self, src, attn_mask=None, caches=None, time_step=None):
        if time_step is not None and caches is None:
            raise ValueError(
                "time_step requires caches (decode steps read/write the "
                "CacheKV tensors); pass caches=gen_cache(...)")
        if caches is not None:
            # inference stages (reference contract: returns (out, caches)):
            # time_step None = context/prefill, else chunk decode at t
            if len(caches) != len(self.layers):
                raise ValueError(
                    f"got {len(caches)} cache tensors for "
                    f"{len(self.layers)} layers")
            out = src
            new_caches = []
            for layer, c in zip(self.layers, caches):
                out, nc = layer(out, src_mask=attn_mask, cache=c,
                                time_step=time_step)
                new_caches.append(nc)
            return out, new_caches
        if attn_mask is None:
            # the reference kernel is a CAUSAL decoder by construction —
            # ported callers pass no mask and still expect causality
            import jax.numpy as jnp
            from ....framework.tensor import Tensor
            s = src.shape[1]
            causal = jnp.where(
                jnp.tril(jnp.ones((s, s), jnp.bool_)), 0.0, -1e9)
            attn_mask = Tensor(causal.reshape(1, 1, s, s),
                               stop_gradient=True)
        out = src
        for layer in self.layers:
            out = layer(out, src_mask=attn_mask)
        return out
