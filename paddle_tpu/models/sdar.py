"""SDAR-MoE (``model_type: sdar_moe``, JetLM/SDAR-30B-A3B-Chat
``config.json``): a decoder of grouped-query attention and routed experts
whose text is GENERATED BY DIFFUSION OVER BLOCKS — the third caller of
the decoder spec (``models/decoder_spec.py``) beside GPT-2 and A.X-K1.

Per layer (the Qwen3-MoE block), pre-norm with RMSNorm and residual adds,
no biases:

* **Attention.** ``q = h W_q`` -> ``H`` heads of ``Dh``; ``k = h W_k``,
  ``v = h W_v`` -> ``Hkv`` heads of ``Dh``; ``q`` and ``k`` each through
  an RMSNorm over their ``Dh`` lanes with a learned gain; rotary
  positions (``rope_theta``, the half-split convention: lane ``i`` turns
  with lane ``i + Dh / 2``, no scaling); query head ``j`` reads KV head
  ``j // (H / Hkv)``; scores ``/ sqrt(Dh)``; a row at position ``i`` sees
  column ``j`` iff ``j // B <= i // B`` (causal over blocks of ``B =
  block_length``, full inside a block); ``out = concat(p v) W_o``. What a
  token leaves in the cache is one ``[K | V]`` row a KV head.
* **Expert layer** (every layer: ``decoder_sparse_step`` 1, no
  ``mlp_only_layers``): ``s = softmax(h W_r^T)`` in float32 over all
  ``num_experts``; the ``num_experts_per_tok`` largest, their weights
  divided by their sum (``norm_topk_prob``); ``y = sum_e w_e
  down_e(silu(gate_e(h)) * up_e(h))``. No shared expert.
  ``experts_held=(lo, hi)`` serves a share, as ``models/axk1.py`` does.

The logits at position ``i`` are FOR position ``i`` (no shift): a masked
position's row predicts its own token. How the blocks are denoised and
committed is the decoder spec's generation rule, run by the fused step
(``models/generation.py``) and the scheduler; ``forward`` here is the
plain pass of whole sequences under the block mask, no cache.

What this shares with ``models/axk1.py`` — the norm, the products, the
router and the dropless grouped experts, the scope ``moe_experts`` — is
imported from there.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

import numpy as np

from .. import nn
from ..framework.tensor import Parameter, Tensor
from . import decoder_spec as DS
from .axk1 import (_mm, _param_maker, _params, _rms_norm,
                   route_top_k, routed_experts)

__all__ = ["SDARConfig", "SDARForCausalLM", "rope_half_split"]


@dataclass
class SDARConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: list = field(default_factory=list)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    # generation by diffusion over blocks (the family's generate.py)
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669
    remasking: str = "low_confidence_static"
    # the share of an expert-parallel deployment this model holds
    # (None = every expert)
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, int(self.num_experts))
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range inside "
                f"[0, {self.num_experts})")
        self.experts_held = (lo, hi)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} is no "
                f"multiple of num_key_value_heads {self.num_key_value_heads}")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary halves)")
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError(
                "every layer is an expert layer here (decoder_sparse_step "
                "1, mlp_only_layers []): dense layers are not built")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} outside the vocabulary")

    @property
    def generation(self) -> DS.GenerationRule:
        return DS.GenerationRule(
            block_length=self.block_length,
            denoising_steps=self.denoising_steps,
            mask_token_id=self.mask_token_id, remasking=self.remasking)

    @classmethod
    def tiny(cls, **over):  # tests
        kw = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                  num_hidden_layers=2, num_attention_heads=8,
                  num_key_value_heads=2, head_dim=16, num_experts=16,
                  num_experts_per_tok=4, max_position_embeddings=256,
                  mask_token_id=255)
        kw.update(over)
        return cls(**kw)


def rope_half_split(x, positions, theta: float):
    """Rotary positions in the half-split convention: lane ``i`` of the
    last axis turns with lane ``i + dim / 2`` by ``positions *
    theta^(-2i / dim)``. ``x [rows, heads, dim]``, ``positions [rows]``;
    float32 inside."""
    import jax.numpy as jnp
    dim = x.shape[-1]
    inv = 1.0 / float(theta) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :dim // 2], xf[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class SDARAttention(nn.Layer):
    def __init__(self, cfg: SDARConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        self.wq = p("wq", (E, H * Dh))
        self.wk = p("wk", (E, Hkv * Dh))
        self.wv = p("wv", (E, Hkv * Dh))
        self.q_norm = p("q_norm", (Dh,))
        self.k_norm = p("k_norm", (Dh,))
        self.wo = p("wo", (H * Dh, E))

    def project(self, h, positions):
        """``h [Q, E]`` (normed) -> ``q [Q, H, Dh]``, ``k``/``v [Q, Hkv,
        Dh]``: ``q`` and ``k`` normed over their lanes and rotated."""
        cfg = self.cfg
        Q = h.shape[0]
        q = _mm(h, self.wq._data).reshape(Q, cfg.num_attention_heads, -1)
        k = _mm(h, self.wk._data).reshape(Q, cfg.num_key_value_heads, -1)
        v = _mm(h, self.wv._data).reshape(Q, cfg.num_key_value_heads, -1)
        q = rope_half_split(_rms_norm(q, self.q_norm._data, cfg.rms_norm_eps),
                            positions, cfg.rope_theta)
        k = rope_half_split(_rms_norm(k, self.k_norm._data, cfg.rms_norm_eps),
                            positions, cfg.rope_theta)
        return q, k, v

    def out(self, o):
        """``o [Q, H, Dh]`` -> ``[Q, E]``."""
        return _mm(o.reshape(o.shape[0], -1), self.wo._data)

    def naive(self, h, positions):
        """Attention of one whole sequence ``h [S, E]`` under the block
        mask (no cache)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        S, Hkv = h.shape[0], cfg.num_key_value_heads
        q, k, v = self.project(h, positions)
        f32 = lambda a: a.astype(jnp.float32)
        q = f32(q).reshape(S, Hkv, -1, cfg.head_dim)       # [S, Hkv, g, Dh]
        s = jnp.einsum("qngd,knd->ngqk", q, f32(k)) * cfg.head_dim ** -0.5
        blk = positions // cfg.block_length
        s = jnp.where((blk[None, :] <= blk[:, None])[None, None], s,
                      -jnp.inf)
        o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), f32(v))
        return self.out(o.reshape(S, -1, cfg.head_dim).astype(h.dtype))


class SDARRoutedFFN(nn.Layer):
    kind = DS.ROUTED

    def __init__(self, cfg: SDARConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, I = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.experts_held[1] - cfg.experts_held[0]
        self.router = p("router", (cfg.num_experts, E))
        self.experts_gate = p("experts_gate", (n, E, I))
        self.experts_up = p("experts_up", (n, E, I))
        self.experts_down = p("experts_down", (n, I, E))

    def apply(self, x, valid):
        """``x [Q, E]`` -> ``(the held experts' part of the routed sum,
        counters)``. The layout of the pairs and the trips of the grouped
        products are ``axk1.routed_experts``' own, from the shapes (a
        slot's q block of 8 holds its block of 4, or 8 rows where a
        finished block's commit rides with the next block's first pass:
        5/8 of a steady launch's rows are real, and the trips follow the
        real pairs)."""
        cfg = self.cfg
        k = cfg.num_experts_per_tok
        with DS.section(DS.MOE_SCOPE):
            with DS.section(DS.ROUTER):
                idx, w, _ = route_top_k(
                    x, self.router._data, k, 1.0, cfg.norm_topk_prob,
                    scoring="softmax")
            y, counters = routed_experts(
                x, valid, idx, w,
                (self.experts_gate._data, self.experts_up._data,
                 self.experts_down._data), cfg.experts_held,
                cfg.num_experts)
        with DS.section(DS.MLP):          # with the add that closes the layer
            return y.astype(x.dtype), counters


class SDARLayer(nn.Layer):
    def __init__(self, cfg: SDARConfig, index: int, make):
        super().__init__()
        prefix = f"layers.{index}."
        p = _params(make, prefix)
        self.cfg = cfg
        self.attn_norm = p("attn_norm", (cfg.hidden_size,))
        self.attn = SDARAttention(cfg, make, prefix + "attn.")
        self.ffn_norm = p("ffn_norm", (cfg.hidden_size,))
        self.ffn = SDARRoutedFFN(cfg, make, prefix + "ffn.")

    def _ffn(self, x, valid):
        with DS.section(DS.NORM):
            h = _rms_norm(x, self.ffn_norm._data, self.cfg.rms_norm_eps)
        y, counters = self.ffn.apply(h, valid)
        with DS.section(DS.MLP):
            return x + y, counters

    # -- the decoder spec's layer surface (x is a Tensor [1, Q, E]) --------
    def attn_in(self, x, positions):
        import jax.numpy as jnp
        with DS.section(DS.NORM):
            h = _rms_norm(x._data[0], self.attn_norm._data,
                          self.cfg.rms_norm_eps)
        with DS.section(DS.QKV):
            q, k, v = self.attn.project(h, positions)
            return jnp.swapaxes(q, 0, 1), (k, v)          # [H, Q, Dh]

    def attn_out(self, x, a, row_valid):
        import jax.numpy as jnp
        with DS.section(DS.O_PROJ):
            o = self.attn.out(jnp.swapaxes(a, 0, 1))
            x = x._data[0] + o
        y, counters = self._ffn(x, row_valid)
        with DS.section(DS.MLP):
            return Tensor(y[None], stop_gradient=True), counters

    # -- no cache: one whole sequence [S, E] -------------------------------
    def full(self, x, positions):
        import jax.numpy as jnp
        h = _rms_norm(x, self.attn_norm._data, self.cfg.rms_norm_eps)
        x = x + self.attn.naive(h, positions)
        return self._ffn(x, jnp.ones(x.shape[0], bool))[0]


class SDARForCausalLM(nn.Layer):
    """SDAR-MoE with its untied head. ``forward(input_ids [B, S])`` ->
    float32 logits ``[B, S, V]`` under the block mask (no cache; row ``i``
    is FOR position ``i``); ``serving_decoder()`` is what
    ``GenerationEngine`` consumes. Parameters are made by
    ``param_init(name, shape, dtype)``, one call a parameter, every array
    ONCE in its serving dtype (as ``AXK1ForCausalLM``)."""

    def __init__(self, cfg: SDARConfig, dtype="float32",
                 param_init: Optional[Callable] = None):
        super().__init__()
        self.cfg = cfg
        make = _param_maker(dtype, param_init, cfg.initializer_range)
        self.embed = Parameter(make("embed", (cfg.vocab_size, cfg.hidden_size)))
        self.layers = nn.LayerList(
            [SDARLayer(cfg, i, make) for i in range(cfg.num_hidden_layers)])
        self.norm = Parameter(make("norm", (cfg.hidden_size,)))
        self.lm_head = Parameter(make("lm_head",
                                      (cfg.hidden_size, cfg.vocab_size)))
        # one [K | V] row a KV head: the query heads are more (grouped)
        cache = DS.CacheSpec(rows=cfg.num_key_value_heads,
                             lanes=2 * cfg.head_dim)
        self.spec = DS.DecoderSpec(
            layers=tuple(DS.LayerSpec(DS.FULL, cache, DS.ROUTED,
                                      query_heads=cfg.num_attention_heads)
                         for _ in self.layers),
            vocab_size=cfg.vocab_size,
            max_positions=cfg.max_position_embeddings,
            generation=cfg.generation)

    def serving_decoder(self):
        return self

    # -- the decoder spec's model surface ----------------------------------
    def embed_tokens(self, token_ids, positions):
        return Tensor(self.embed._data[token_ids][None], stop_gradient=True)

    def final_norm(self, x):
        return Tensor(_rms_norm(x._data, self.norm._data,
                                self.cfg.rms_norm_eps), stop_gradient=True)

    def logits(self, hidden):
        import jax.numpy as jnp
        return Tensor(jnp.dot(hidden._data, self.lm_head._data,
                              preferred_element_type=jnp.float32),
                      stop_gradient=True)

    def forward(self, input_ids):
        import jax.numpy as jnp
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        out = []
        for row in ids:
            x = self.embed._data[row]
            for layer in self.layers:
                x = layer.full(x, pos)
            out.append(self.logits(self.final_norm(
                Tensor(x, stop_gradient=True)))._data)
        return Tensor(jnp.stack(out), stop_gradient=True)
