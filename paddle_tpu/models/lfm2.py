"""LFM2-MoE (``model_type: lfm2_moe``, LiquidAI/LFM2-24B-A2B
``config.json``): a decoder most of whose layers hold NO attention cache —
the sixth caller of the decoder spec (``models/decoder_spec.py``), and the
first with layers whose mixer is a recurrent state ALONE.

``layer_types`` says a layer's OPERATOR: ``conv`` (a gated short
convolution; three layers in four as published) or ``full_attention``.
Every layer, pre-norm with RMSNorm (``norm_eps``) and residual adds, no
biases: ``h = x + Operator(RMSNorm(x; operator_norm))``, then ``x = h +
FFN(RMSNorm(h; ffn_norm))``.

* **``conv`` operator** (``conv_L_cache`` K = 3 taps, ``conv_bias``
  false). ``[B | C | z] = W_in u`` (``E -> 3 E``, split in that order);
  ``g = B * z``; ``c_t = sum_j w_j * g_{t-K+1+j}`` (depthwise, causal,
  ``g`` before the sequence's start is 0, no activation); ``y = C * c``;
  the output is ``W_out y``. What a SEQUENCE leaves behind in the layer is
  ``g`` at its last ``K - 1`` positions — ``2 x E`` values, whatever the
  context's length: the decoder spec's ``StateSpec`` of one part, held a
  slot by the paged pool. Such a layer has no query heads, writes nothing
  to the block pool and reads no page table.
* **``full_attention`` operator.** ``q = W_q u`` -> ``H`` heads of ``Dh =
  E / H``; ``k``, ``v`` -> ``Hkv`` heads; RMSNorm with a learned gain
  over the lanes of every q head and every k head (``q_layernorm``,
  ``k_layernorm``) BEFORE rotary; rotary positions on all ``Dh`` lanes
  (``rope_theta``, the half-split convention of ``models/sdar.py``, no
  scaling); query head ``j`` reads KV head ``j // (H / Hkv)``; causal
  ``softmax(q k^T / sqrt(Dh)) v``; ``W_out``. What a token leaves in the
  cache is one ``[K | V]`` row a KV head.
* **FFN.** Layers before ``num_dense_layers``: ``W_2(silu(W_1 v) * W_3
  v)`` of width ``intermediate_size``. The others ROUTED: ``s =
  sigmoid(W_g v)`` over ``num_experts`` in float32; the choice is the
  ``num_experts_per_tok`` largest of ``s + expert_bias``
  (``use_expert_bias``); the weights are the chosen experts' ``s`` over
  their sum + 1e-6 (``norm_topk_prob``), times ``routed_scaling_factor``;
  every expert a SwiGLU of width ``moe_intermediate_size``; no shared
  expert. This is ``axk1.route_top_k`` + ``axk1.routed_experts``.

Embedding; after the last layer ``RMSNorm(.; embedding_norm)``; the head
is the embedding's array (``tie_word_embeddings``, the family's default:
ONE ``[vocab, hidden]`` parameter read by both). Products are in the
weights' dtype with float32 accumulation; the norms, the gates around the
convolution, the convolution and the router in float32.

``forward`` is the plain pass of whole sequences, no cache, through the
same functions from a zero state.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

from .. import nn
from ..framework.tensor import Parameter, Tensor
from ..ops import ssm as SSM
from . import decoder_spec as DS
from .axk1 import (_mm, _param_maker, _params, _rms_norm, _swiglu,
                   route_top_k, routed_experts)
from .sdar import rope_half_split

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM"]

STATE_DTYPE = "float32"
CONV, ATTENTION = "conv", "full_attention"
ROUTER_EPS = 1e-6       # joins the sum the chosen scores are divided by


def _published_layer_types():
    return [CONV, CONV] + [ATTENTION, CONV, CONV, CONV] * 9 \
        + [ATTENTION, CONV]


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: list = field(default_factory=_published_layer_types)
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    # the range [lo, hi) of experts THIS chip holds: the router scores all
    # ``num_experts``, the layer adds only these experts' part (all 64 as
    # the one configuration of the benchmark holds them)
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(v) for v in self.experts_held)
        self.layer_types = list(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (CONV, ATTENTION) for t in self.layer_types):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} operators "
                f"{sorted(set(self.layer_types))} for "
                f"{self.num_hidden_layers} layers: one of {CONV!r} and "
                f"{ATTENTION!r} a layer")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size}, num_attention_heads "
                f"{self.num_attention_heads} and num_key_value_heads "
                f"{self.num_key_value_heads} do not divide")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary halves)")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache must be >= 2: a convolution of "
                             "one tap leaves no tail behind")
        if self.conv_bias:
            raise ValueError("conv_bias true is not built (the published "
                             "configuration says false)")
        if not self.use_expert_bias:
            raise ValueError("the router is built with its expert_bias, as "
                             "published (a zero bias is the plain top-k)")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.num_experts} experts")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def state_spec(self) -> DS.StateSpec:
        return DS.StateSpec((
            ("conv", (self.conv_L_cache - 1, self.hidden_size),
             STATE_DTYPE),))

    @classmethod
    def tiny(cls, **over):  # tests
        kw = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_hidden_layers=6,
                  layer_types=[CONV, CONV, ATTENTION, CONV, CONV, CONV],
                  num_attention_heads=8, num_key_value_heads=2,
                  num_dense_layers=2, num_experts=8, num_experts_per_tok=2,
                  max_position_embeddings=512, rope_theta=1e4)
        kw.update(over)
        return cls(**kw)


class Lfm2Attention(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig, make, prefix):
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

    def project(self, u, positions):
        """``u [Q, E]`` (normed) -> ``q [Q, H, Dh]``, ``k``/``v [Q, Hkv,
        Dh]``: ``q`` and ``k`` normed over their lanes, then rotated."""
        cfg = self.cfg
        Q = u.shape[0]
        q = _mm(u, self.wq._data).reshape(Q, cfg.num_attention_heads, -1)
        k = _mm(u, self.wk._data).reshape(Q, cfg.num_key_value_heads, -1)
        v = _mm(u, self.wv._data).reshape(Q, cfg.num_key_value_heads, -1)
        q = rope_half_split(_rms_norm(q, self.q_norm._data, cfg.norm_eps),
                            positions, cfg.rope_theta)
        k = rope_half_split(_rms_norm(k, self.k_norm._data, cfg.norm_eps),
                            positions, cfg.rope_theta)
        return q, k, v

    def out(self, o):
        """``o [Q, H, Dh]`` -> ``[Q, E]``."""
        return _mm(o.reshape(o.shape[0], -1), self.wo._data)

    def naive(self, u, positions):
        """Causal attention of one whole sequence (no cache)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        S, Hkv = u.shape[0], cfg.num_key_value_heads
        q, k, v = self.project(u, positions)
        f32 = lambda a: a.astype(jnp.float32)
        q = f32(q).reshape(S, Hkv, -1, cfg.head_dim)
        s = jnp.einsum("qngd,knd->ngqk", q, f32(k)) * cfg.head_dim ** -0.5
        s = jnp.where((positions[None, :] <= positions[:, None])[None, None],
                      s, -jnp.inf)
        o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), f32(v))
        return self.out(o.reshape(S, -1, cfg.head_dim).astype(u.dtype))


class Lfm2ShortConv(nn.Layer):
    """The gated short convolution (module doc). ``apply`` runs a ragged
    launch's rows against the slots' tails."""

    def __init__(self, cfg: Lfm2MoeConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E = cfg.hidden_size
        self.conv_in = p("conv_in", (E, 3 * E))
        self.conv_w = p("conv_w", (cfg.conv_L_cache, E))
        self.conv_out = p("conv_out", (E, E))

    def apply(self, u, lay, state, index):
        """``u [Q, E]`` (normed), ``lay`` the rows' sequence layout,
        ``state = (tails,)`` -> ``(s [Q, E], state)``."""
        import jax
        import jax.numpy as jnp
        E = self.cfg.hidden_size
        (tail,) = state
        with DS.section(DS.SSM_PROJ):
            # ONE product, held: its readers otherwise each get a
            # rematerialized copy of it fused into them (as the mixer of
            # models/falcon_h1.py found on the chip)
            bcz = jax.lax.optimization_barrier(_mm(u, self.conv_in._data))
            part = lambda i: bcz[:, i * E:(i + 1) * E].astype(jnp.float32)
            g = part(0) * part(2)
        with DS.section(DS.SSM_CONV):
            c, tail = SSM.conv_rows(g, self.conv_w._data, None, tail, index,
                                    lay)
        with DS.section(DS.SSM_PROJ):
            y = (part(1) * c).astype(u.dtype)
            return _mm(y, self.conv_out._data), (tail,)


class Lfm2DenseFFN(nn.Layer):
    kind = DS.DENSE

    def __init__(self, cfg: Lfm2MoeConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        E, I = cfg.hidden_size, cfg.intermediate_size
        self.gate = p("gate", (E, I))
        self.up = p("up", (E, I))
        self.down = p("down", (I, E))

    def apply(self, x, valid):
        with DS.section(DS.MLP):
            return _swiglu(x, self.gate._data, self.up._data,
                           self.down._data).astype(x.dtype), None


class Lfm2RoutedFFN(nn.Layer):
    kind = DS.ROUTED

    def __init__(self, cfg: Lfm2MoeConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, I = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.experts_held[1] - cfg.experts_held[0]
        self.router = p("router", (cfg.num_experts, E))
        self.expert_bias = p("expert_bias", (cfg.num_experts,))
        self.experts_gate = p("experts_gate", (n, E, I))
        self.experts_up = p("experts_up", (n, E, I))
        self.experts_down = p("experts_down", (n, I, E))

    def apply(self, x, valid):
        """``x [Q, E]`` -> ``(the held experts' part of the routed sum,
        counters)``. All 64 experts are held: every (row, expert) pair
        goes through the grouped products, laid out and cut into trips by
        ``axk1.routed_experts`` from the shapes."""
        cfg = self.cfg
        k = cfg.num_experts_per_tok
        with DS.section(DS.MOE_SCOPE):
            with DS.section(DS.ROUTER):
                idx, w, _ = route_top_k(
                    x, self.router._data, k, cfg.routed_scaling_factor,
                    cfg.norm_topk_prob, scoring="sigmoid",
                    select_bias=self.expert_bias._data, eps=ROUTER_EPS)
            y, counters = routed_experts(
                x, valid, idx, w,
                (self.experts_gate._data, self.experts_up._data,
                 self.experts_down._data), cfg.experts_held,
                cfg.num_experts)
        with DS.section(DS.MLP):          # with the add that closes the layer
            return y.astype(x.dtype), counters


class Lfm2Layer(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig, index: int, make):
        super().__init__()
        prefix = f"layers.{index}."
        p = _params(make, prefix)
        self.cfg = cfg
        self.is_conv = cfg.layer_types[index] == CONV
        self.operator_norm = p("operator_norm", (cfg.hidden_size,))
        if self.is_conv:
            self.conv = Lfm2ShortConv(cfg, make, prefix + "conv.")
        else:
            self.attn = Lfm2Attention(cfg, make, prefix + "attn.")
        self.ffn_norm = p("ffn_norm", (cfg.hidden_size,))
        ffn = Lfm2DenseFFN if index < cfg.num_dense_layers else Lfm2RoutedFFN
        self.ffn = ffn(cfg, make, prefix + "ffn.")

    @property
    def layer_spec(self) -> DS.LayerSpec:
        cfg = self.cfg
        if self.is_conv:
            return DS.LayerSpec(None, None, self.ffn.kind,
                                state=cfg.state_spec)
        return DS.LayerSpec(
            DS.FULL, DS.CacheSpec(rows=cfg.num_key_value_heads,
                                  lanes=2 * cfg.head_dim), self.ffn.kind,
            query_heads=cfg.num_attention_heads)

    def _normed(self, x):
        with DS.section(DS.NORM):
            rows = x._data[0] if isinstance(x, Tensor) else x
            return _rms_norm(rows, self.operator_norm._data,
                             self.cfg.norm_eps)

    def _ffn(self, x, valid):
        with DS.section(DS.NORM):
            v = _rms_norm(x, self.ffn_norm._data, self.cfg.norm_eps)
        y, counters = self.ffn.apply(v, valid)
        with DS.section(DS.MLP):
            return x + y, counters

    # -- the decoder spec's layer surface (x is a Tensor [1, Q, E]) --------
    def attn_in(self, x, positions):
        """An attention layer's only (the tower never asks a ``conv``
        layer)."""
        import jax.numpy as jnp
        u = self._normed(x)
        with DS.section(DS.QKV):
            q, k, v = self.attn.project(u, positions)
            return jnp.swapaxes(q, 0, 1), (k, v)          # [H, Q, Dh]

    def mixer(self, x, layout, state, index):
        """A ``conv`` layer's only: its whole operator."""
        return self.conv.apply(self._normed(x), layout, state, index)

    def attn_out(self, x, a, row_valid, s=None):
        """``a`` the attention's output (an attention layer) or ``None``
        with ``s`` the convolution's (a ``conv`` layer)."""
        import jax.numpy as jnp
        with DS.section(DS.O_PROJ):
            x = x._data[0] + (s if a is None
                              else self.attn.out(jnp.swapaxes(a, 0, 1)))
        y, counters = self._ffn(x, row_valid)
        with DS.section(DS.MLP):
            return Tensor(y[None], stop_gradient=True), counters

    # -- no cache: one whole sequence [S, E] from a zero state -------------
    def full(self, x, positions):
        import jax.numpy as jnp
        S = x.shape[0]
        u = self._normed(x)
        if self.is_conv:
            lay = SSM.SeqLayout(
                jnp.zeros(S, jnp.int32), positions.astype(jnp.int32),
                jnp.zeros(1, jnp.int32), jnp.full(1, S, jnp.int32),
                jnp.ones(1, bool))
            zeros = tuple(jnp.zeros((1, 2) + shape, dtype) for _, shape,
                          dtype in self.cfg.state_spec.parts)
            op, _ = self.conv.apply(u, lay, zeros, 0)
        else:
            op = self.attn.naive(u, positions)
        return self._ffn(x + op, jnp.ones(S, bool))[0]


class Lfm2MoeForCausalLM(nn.Layer):
    """LFM2-MoE with its tied head. ``forward(input_ids [B, S])`` ->
    float32 logits ``[B, S, V]`` (no cache); ``serving_decoder()`` is what
    ``GenerationEngine`` consumes: the spec's ``conv`` layers are state
    alone, so the pool holds blocks for the attention layers and a tail a
    slot for the others. Parameters are made by ``param_init(name, shape,
    dtype)``, one call a parameter, every array ONCE in its serving dtype
    (as ``AXK1ForCausalLM``)."""

    def __init__(self, cfg: Lfm2MoeConfig, dtype="float32",
                 param_init: Optional[Callable] = None):
        super().__init__()
        self.cfg = cfg
        make = _param_maker(dtype, param_init, cfg.initializer_range)
        self.embed = Parameter(make("embed", (cfg.vocab_size, cfg.hidden_size)))
        self.layers = nn.LayerList(
            [Lfm2Layer(cfg, i, make) for i in range(cfg.num_hidden_layers)])
        self.embedding_norm = Parameter(make("embedding_norm",
                                             (cfg.hidden_size,)))
        self.spec = DS.DecoderSpec(
            layers=tuple(layer.layer_spec for layer in self.layers),
            vocab_size=cfg.vocab_size,
            max_positions=cfg.max_position_embeddings)

    def serving_decoder(self):
        return self

    # -- the decoder spec's model surface ----------------------------------
    def embed_tokens(self, token_ids, positions):
        return Tensor(self.embed._data[token_ids][None], stop_gradient=True)

    def final_norm(self, x):
        return Tensor(_rms_norm(x._data, self.embedding_norm._data,
                                self.cfg.norm_eps), stop_gradient=True)

    def logits(self, hidden):
        """The head is the embedding's array: ``hidden [..., E]`` against
        ``embed [V, E]`` over ``E``."""
        import jax
        import jax.numpy as jnp
        h = hidden._data
        return Tensor(jax.lax.dot_general(
            h, self.embed._data, (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), stop_gradient=True)

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
