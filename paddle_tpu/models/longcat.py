"""LongCat-Flash (meituan-longcat/LongCat-Flash-Chat ``config.json``): a
decoder whose published layer is TWO latent-attention blocks and TWO
dense FFNs with the routed experts on a SHORTCUT around the second pair
(shortcut-connected MoE), and whose router is wider than its experts:
``zero_expert_num`` of its outputs are IDENTITY ("zero-computation")
experts that cost nothing — the seventh caller of the decoder spec
(``models/decoder_spec.py``).

Pre-norm, RMSNorm, no biases. Published layer ``l``, sub-block ``s``::

    for s in (0, 1):
        x = x + MLA[l,s]( RMSNorm_in[l,s](x) )
        u = RMSNorm_post[l,s](x)
        if s == 0:  m = MoE[l](u)          # the shortcut opens: m is carried
        x = x + Dense[l,s](u)              # SwiGLU, width ffn_hidden_size
        if s == 1:  x = x + m              # the shortcut closes

* **MLA** is A.X-K1's (``models/axk1.py:AXK1Attention``, its module doc)
  with plain rotary positions (``rope_theta``, no YaRN) and the two
  multipliers the config switches on: ``q = (c_q W_qb) * sqrt(E /
  q_lora_rank)`` (``mla_scale_q_lora``) and ``c_kv = RMSNorm(c) * sqrt(E
  / kv_lora_rank)`` (``mla_scale_kv_lora``); ``k_pe`` takes neither. The
  second lives in the STORED ROW: what a token leaves in a sub-block's
  cache is ``[sqrt(E / kv_rank) RMSNorm(c) | k_pe]``, multiplied in
  float32 inside the norm and rounded once, so the absorbed form's
  ``W_UK`` / ``W_UV`` are the plain halves of ``W_kvb`` and the latent
  kernel sees nothing new.
* **MoE** (``u [R, E]``): ``g = softmax(u W_r^T)`` in float32 over
  ``n_routed_experts + zero_expert_num`` outputs; ``T`` = the ``moe_topk``
  largest of ``g + b`` (``b`` the score-correction bias); ``w_e =
  routed_scaling_factor * g_e``, NOT normalised over ``T``; ::

      m = sum_{e in T, e < n_routed} w_e SwiGLU_e(u) + (sum_{e in T, e >= n_routed} w_e) u

  An identity expert touches no weights: its term is the row itself.
  Real pairs a token vary from 0 to ``moe_topk``.

**Serving a share** (``experts_held=(lo, hi)``, inside the REAL experts):
the first sum runs over ``lo <= e < hi`` only (``axk1.routed_experts``,
told the router's whole width so that its plan expects ``rows k /
width`` pairs a held expert); the identity term is WHOLE for every row
of this chip — a token's identity experts need no dispatch, they are
computed where the token lives. What the absent experts would add is
left out, and that partial ``m`` goes on.

**Two spec layers a published layer**: ``LayerSpec(LATENT, cache, DENSE,
shortcut=1)`` then ``LayerSpec(LATENT, cache, DENSE)`` — ``2 num_layers``
cache-bearing layers of one cache group. The opening sub-block's
``attn_out`` RETURNS ``m`` beside ``(x, counters)``; the tower carries it
to the end of the next sub-block and adds it there (section
``shortcut``). No layer object keeps a traced value between calls;
``forward`` (no cache, naive attention) carries ``m`` in its own loop.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

from .. import nn
from ..framework.tensor import Parameter, Tensor
from . import decoder_spec as DS
from .axk1 import (AXK1Attention, AXK1DenseFFN, _param_maker, _params,
                   _rms_norm, latent_lanes, route_top_k, routed_experts)

__all__ = ["LongCatConfig", "LongCatForCausalLM"]


@dataclass
class LongCatConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28                 # published layers: two sub-blocks each
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512          # experts WITH weights
    zero_expert_num: int = 256           # identity experts after them
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    # the share of an expert-parallel deployment this model holds: real
    # experts lo .. hi - 1 of every published layer (None = all of them)
    experts_held: Optional[Tuple[int, int]] = None

    rope_scaling = None                  # plain rotary positions

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, int(self.n_routed_experts))
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range inside "
                f"the {self.n_routed_experts} experts with weights")
        self.experts_held = (lo, hi)
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if self.moe_topk > self.router_width:
            raise ValueError(f"moe_topk {self.moe_topk} exceeds the "
                             f"router's {self.router_width} outputs")

    @property
    def router_width(self) -> int:
        return int(self.n_routed_experts) + int(self.zero_expert_num)

    @property
    def intermediate_size(self) -> int:      # what AXK1DenseFFN reads
        return self.ffn_hidden_size

    @property
    def q_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.q_lora_rank) \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.kv_lora_rank) \
            if self.mla_scale_kv_lora else 1.0

    @property
    def latent_lanes(self) -> int:
        return latent_lanes(self.kv_lora_rank, self.qk_rope_head_dim)

    @classmethod
    def tiny(cls, **over):  # tests
        kw = dict(
            vocab_size=256, hidden_size=64, ffn_hidden_size=128,
            expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, n_routed_experts=16,
            zero_expert_num=8, moe_topk=4, max_position_embeddings=128)
        kw.update(over)
        return cls(**kw)


class LongCatExperts(nn.Layer):
    """The routed experts of one published layer: the router over real
    and identity experts, the held experts' weights."""

    def __init__(self, cfg: LongCatConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, I = cfg.hidden_size, cfg.expert_ffn_hidden_size
        n = cfg.experts_held[1] - cfg.experts_held[0]
        self.router = p("router", (cfg.router_width, E))
        self.router_bias = p("router_bias", (cfg.router_width,))
        self.experts_gate = p("experts_gate", (n, E, I))
        self.experts_up = p("experts_up", (n, E, I))
        self.experts_down = p("experts_down", (n, I, E))

    def apply(self, u, valid):
        """``u [Q, E]`` (normed) -> ``(m [Q, E], counters)``: the held
        experts' part of the routed sum plus the identity experts' whole
        term, 0 on pad rows; the fifth counter is the (real row,
        identity expert) pairs."""
        import jax.numpy as jnp
        cfg = self.cfg
        with DS.section(DS.MOE_SCOPE):
            with DS.section(DS.ROUTER):
                idx, w, _ = route_top_k(
                    u, self.router._data, cfg.moe_topk,
                    cfg.routed_scaling_factor, norm=False,
                    scoring="softmax", select_bias=self.router_bias._data)
            y, counters = routed_experts(
                u, valid, idx, w,
                (self.experts_gate._data, self.experts_up._data,
                 self.experts_down._data), cfg.experts_held,
                cfg.router_width)
            with DS.section(DS.ZERO_EXPERTS):
                zero = (idx >= cfg.n_routed_experts) & valid[:, None]
                w_zero = jnp.sum(jnp.where(zero, w, 0.0), axis=-1)
                y = y + w_zero[:, None] * u.astype(jnp.float32)
                zero_pairs = jnp.sum(zero, dtype=jnp.int32)
            return y.astype(u.dtype), counters[:-1] + (zero_pairs,)


class LongCatSubBlock(nn.Layer):
    """One ``MLA -> dense FFN`` sub-block; an even one also holds its
    published layer's experts and opens their shortcut."""

    def __init__(self, cfg: LongCatConfig, index: int, make):
        super().__init__()
        prefix = f"layers.{index}."
        p = _params(make, prefix)
        self.cfg = cfg
        self.attn_norm = p("attn_norm", (cfg.hidden_size,))
        self.attn = AXK1Attention(cfg, make, prefix + "attn.",
                                  q_scale=cfg.q_scale, kv_scale=cfg.kv_scale)
        self.ffn_norm = p("ffn_norm", (cfg.hidden_size,))
        self.ffn = AXK1DenseFFN(cfg, make, prefix + "ffn.")
        self.moe = LongCatExperts(cfg, make, prefix + "moe.") \
            if index % 2 == 0 else None

    def _ffn(self, x, valid):
        """``x [Q, E]`` after attention -> ``(x + Dense(u), counters, m)``,
        ``counters`` and ``m`` ``None`` where no shortcut opens."""
        with DS.section(DS.NORM):
            u = _rms_norm(x, self.ffn_norm._data, self.cfg.rms_norm_eps)
        m, counters = (None, None) if self.moe is None \
            else self.moe.apply(u, valid)
        y, _ = self.ffn.apply(u, valid)
        with DS.section(DS.MLP):          # the add that closes the sub-block
            return x + y, counters, m

    # -- the decoder spec's layer surface (x is a Tensor [1, Q, E]) --------
    def attn_in(self, x, positions):
        with DS.section(DS.NORM):
            h = _rms_norm(x._data[0], self.attn_norm._data,
                          self.cfg.rms_norm_eps)
        with DS.section(DS.QKV):
            return self.attn.absorbed_in(h, positions)

    def attn_out(self, x, o_lat, row_valid):
        with DS.section(DS.O_PROJ):
            x = x._data[0] + self.attn.absorbed_out(o_lat)
        y, counters, m = self._ffn(x, row_valid)
        y = Tensor(y[None], stop_gradient=True)
        return (y, counters) if self.moe is None else (y, counters, m)

    # -- no cache: one whole sequence [S, E] -------------------------------
    def full(self, x, positions):
        """-> ``(x, m)``: ``m`` the opened shortcut's value, or None."""
        import jax.numpy as jnp
        h = _rms_norm(x, self.attn_norm._data, self.cfg.rms_norm_eps)
        x = x + self.attn.naive(h, positions)
        y, _, m = self._ffn(x, jnp.ones(x.shape[0], bool))
        return y, m


class LongCatForCausalLM(nn.Layer):
    """LongCat-Flash with its untied head; no multi-token-prediction head
    is built. ``forward(input_ids [B, S])`` -> float32 logits ``[B, S,
    V]`` (no cache, naive attention); ``serving_decoder()`` is what
    ``GenerationEngine`` consumes. ``layers`` holds the SUB-BLOCKS, two a
    published layer."""

    def __init__(self, cfg: LongCatConfig, dtype="float32",
                 param_init: Optional[Callable] = None):
        super().__init__()
        self.cfg = cfg
        make = _param_maker(dtype, param_init, cfg.initializer_range)
        self.embed = Parameter(make("embed", (cfg.vocab_size, cfg.hidden_size)))
        self.layers = nn.LayerList([LongCatSubBlock(cfg, i, make)
                                    for i in range(2 * cfg.num_layers)])
        self.norm = Parameter(make("norm", (cfg.hidden_size,)))
        self.lm_head = Parameter(make("lm_head",
                                      (cfg.hidden_size, cfg.vocab_size)))
        cache = DS.CacheSpec(rows=1, lanes=cfg.latent_lanes,
                             v_aliases_k=True, v_lanes=cfg.kv_lora_rank)
        self.spec = DS.DecoderSpec(
            layers=tuple(DS.LayerSpec(DS.LATENT, cache, DS.DENSE,
                                      shortcut=int(layer.moe is not None))
                         for layer in self.layers),
            vocab_size=cfg.vocab_size,
            max_positions=cfg.max_position_embeddings)

    def serving_decoder(self):
        return self

    @property
    def attention_scale(self) -> float:
        return self.layers[0].attn.scale

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
            for opening, closing in zip(self.layers[0::2], self.layers[1::2]):
                x, m = opening.full(x, pos)
                x = closing.full(x, pos)[0] + m
            out.append(self.logits(self.final_norm(
                Tensor(x, stop_gradient=True)))._data)
        return Tensor(jnp.stack(out), stop_gradient=True)
