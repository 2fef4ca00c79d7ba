"""MiMo-V2-Flash (``model_type: mimo_v2_flash``, XiaomiMiMo/MiMo-V2-Flash
``config.json``): a decoder whose layers are of TWO attention kinds —
GLOBAL (full causal attention) and WINDOW (a sliding window of
``sliding_window`` keys with a learned sink logit a head), as
``hybrid_layer_pattern`` marks them — with routed experts chosen by a
bias-corrected top-k. The fourth caller of the decoder spec
(``models/decoder_spec.py``), and the first whose layers form more than
one CACHE GROUP.

Per layer, pre-norm with RMSNorm (``layernorm_epsilon``) and residual
adds, no biases, no q/k norm:

* **Attention.** ``q = h W_q`` -> ``H`` heads of ``head_dim``; ``k = h
  W_k`` -> ``Hkv`` heads of ``head_dim``; ``v = attention_value_scale *
  (h W_v)`` -> ``Hkv`` heads of ``v_head_dim``. ``Hkv`` is
  ``num_key_value_heads`` in a global layer and ``swa_num_key_value_heads``
  in a window layer. Rotary positions on the FIRST ``int(head_dim *
  partial_rotary_factor)`` lanes of every q and k head, half-split (lane
  ``i`` turns with lane ``i + rot / 2``), theta ``rope_theta`` (global) or
  ``swa_rope_theta`` (window); the other lanes are not rotated. Query
  head ``j`` reads KV head ``j // (H / Hkv)``; scores ``/ sqrt(head_dim)``.
  GLOBAL: row ``i`` sees columns ``j <= i``, plain softmax. WINDOW: row
  ``i`` sees ``i - W + 1 <= j <= i`` (``W`` keys, its own included) and
  the head's learned sink ``s_h`` joins the softmax's denominator: ``exp(s
  - m) / (sum exp(s' - m) + exp(s_h - m))`` — the sink takes mass and adds
  no value. ``out = concat(p v) W_o``. What a token leaves in the cache is
  one ``[K | V]`` row a KV head: ``head_dim`` K lanes and ``v_head_dim`` V
  lanes, stored with V from the next whole 128-lane tile on (192 | 128 in
  384; ``ops/ragged_paged_attention.py``).
* **FFN.** ``moe_layer_freq[l] == 0``: dense SwiGLU of width
  ``intermediate_size``. Else ``s = sigmoid(h W_r^T)`` in float32 over all
  ``n_routed_experts``; the choice is the ``num_experts_per_tok`` largest
  of ``s + b`` (``b`` the score-correction bias of ``topk_method``
  ``noaux_tc``; one group, so no group limit); weights ``s_e / sum of the
  chosen s`` (``norm_topk_prob``), times ``routed_scaling_factor`` (null:
  1); ``y = sum_e w_e down_e(silu(gate_e(h)) * up_e(h))``. No shared
  expert. ``experts_held=(lo, hi)`` serves a share, as ``models/axk1.py``.

``forward`` is the plain pass of whole sequences, no cache. The norm, the
products, the router and the dropless grouped experts, the scope
``moe_experts`` — are imported from where ``models/sdar.py`` imports them.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

from .. import nn
from ..framework.tensor import Parameter, Tensor
from . import decoder_spec as DS
from .axk1 import (_mm, _param_maker, _params, _rms_norm, _swiglu,
                   route_top_k, routed_experts)
from .sdar import rope_half_split

__all__ = ["MiMoV2Config", "MiMoV2ForCausalLM", "stored_lanes"]

GLOBAL, WINDOW = 0, 1           # hybrid_layer_pattern's two marks


def _published_pattern():
    return [GLOBAL] + ([WINDOW] * 4 + [GLOBAL]) + \
        ([WINDOW] * 5 + [GLOBAL]) * 7


@dataclass
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    hybrid_layer_pattern: list = field(default_factory=_published_pattern)
    moe_layer_freq: list = field(default_factory=lambda: [0] + [1] * 47)
    sliding_window: int = 128
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256
    n_shared_experts: Optional[int] = None
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: Optional[float] = None
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # the share of an expert-parallel deployment this model holds
    # (None = every expert)
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        L = int(self.num_hidden_layers)
        self.hybrid_layer_pattern = [int(v) for v in
                                     self.hybrid_layer_pattern][:L]
        self.moe_layer_freq = [int(v) for v in self.moe_layer_freq][:L]
        if len(self.hybrid_layer_pattern) != L \
                or len(self.moe_layer_freq) != L:
            raise ValueError(
                f"hybrid_layer_pattern and moe_layer_freq must mark each "
                f"of the {L} layers")
        if self.experts_held is None:
            self.experts_held = (0, int(self.n_routed_experts))
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range inside "
                f"[0, {self.n_routed_experts})")
        self.experts_held = (lo, hi)
        for h, hkv in ((self.num_attention_heads, self.num_key_value_heads),
                       (self.swa_num_attention_heads,
                        self.swa_num_key_value_heads)):
            if h % hkv:
                raise ValueError(f"{h} query heads are no multiple of "
                                 f"{hkv} KV heads")
        if self.add_full_attention_sink_bias:
            raise ValueError(
                "add_full_attention_sink_bias: sink logits in a global "
                "layer are not built (the source has them off)")
        if (self.scoring_func, self.topk_method, self.n_group,
                self.topk_group) != ("sigmoid", "noaux_tc", 1, 1):
            raise ValueError(
                "the router built here scores with a sigmoid and chooses "
                "by noaux_tc in one group (scoring_func, topk_method, "
                "n_group, topk_group as the source has them)")
        if self.n_shared_experts:
            raise ValueError("a shared expert is not built (the source "
                             "has none)")
        if self.rotary_lanes(False) % 2 or self.rotary_lanes(True) % 2:
            raise ValueError("the rotary lanes must be even (halves)")

    def rotary_lanes(self, window: bool) -> int:
        dh = self.swa_head_dim if window else self.head_dim
        return int(dh * self.partial_rotary_factor)

    @classmethod
    def tiny(cls, **over):  # tests
        kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, num_hidden_layers=4,
                  num_attention_heads=8, num_key_value_heads=2,
                  head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
                  swa_num_key_value_heads=4, swa_head_dim=24,
                  swa_v_head_dim=16, hybrid_layer_pattern=[0, 1, 1, 0],
                  moe_layer_freq=[0, 1, 1, 1], sliding_window=8,
                  n_routed_experts=16, num_experts_per_tok=4,
                  max_position_embeddings=256)
        kw.update(over)
        return cls(**kw)


def stored_lanes(k_lanes: int, v_lanes: int) -> int:
    """Lanes of a stored ``[K | V]`` row: where V is whole 128-lane tiles,
    K is padded up to the next tile so that the kernel takes each side
    apart for free (192 | 128 -> 384); else the two side by side."""
    if v_lanes % 128 == 0:
        return -(-k_lanes // 128) * 128 + v_lanes
    return k_lanes + v_lanes


class MiMoAttention(nn.Layer):
    def __init__(self, cfg: MiMoV2Config, window: bool, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg, self.is_window = cfg, bool(window)
        E = cfg.hidden_size
        if window:
            self.heads, self.kv_heads, self.dk, self.dv = (
                cfg.swa_num_attention_heads, cfg.swa_num_key_value_heads,
                cfg.swa_head_dim, cfg.swa_v_head_dim)
        else:
            self.heads, self.kv_heads, self.dk, self.dv = (
                cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim, cfg.v_head_dim)
        self.window = int(cfg.sliding_window) if window else 0
        self.theta = cfg.swa_rope_theta if window else cfg.rope_theta
        self.rot = cfg.rotary_lanes(window)
        self.wq = p("wq", (E, self.heads * self.dk))
        self.wk = p("wk", (E, self.kv_heads * self.dk))
        self.wv = p("wv", (E, self.kv_heads * self.dv))
        self.wo = p("wo", (self.heads * self.dv, E))
        self.has_sinks = bool(window and cfg.add_swa_attention_sink_bias)
        if self.has_sinks:
            self.sink = p("sink", (self.heads,))

    @property
    def sinks(self):
        """The heads' sink logits, float32 ``[H]`` (None: none)."""
        import jax.numpy as jnp
        return self.sink._data.astype(jnp.float32) if self.has_sinks \
            else None

    def _rope(self, x, positions):
        import jax.numpy as jnp
        r = self.rot
        return jnp.concatenate(
            [rope_half_split(x[..., :r], positions, self.theta),
             x[..., r:]], axis=-1)

    def project(self, h, positions):
        """``h [Q, E]`` (normed) -> ``q [Q, H, Dk]``, ``k [Q, Hkv, Dk]``
        (their first ``rot`` lanes rotated), ``v [Q, Hkv, Dv]`` scaled."""
        import jax.numpy as jnp
        Q = h.shape[0]
        q = _mm(h, self.wq._data).reshape(Q, self.heads, self.dk)
        k = _mm(h, self.wk._data).reshape(Q, self.kv_heads, self.dk)
        v = _mm(h, self.wv._data).reshape(Q, self.kv_heads, self.dv)
        v = (v.astype(jnp.float32)
             * jnp.float32(self.cfg.attention_value_scale)).astype(v.dtype)
        return self._rope(q, positions), self._rope(k, positions), v

    def out(self, o):
        """``o [Q, H, Dv]`` -> ``[Q, E]``."""
        return _mm(o.reshape(o.shape[0], -1), self.wo._data)

    def naive(self, h, positions):
        """Attention of one whole sequence ``h [S, E]`` under this layer's
        mask (no cache), the sink in the denominator of a window layer."""
        import jax.numpy as jnp
        S, Hkv = h.shape[0], self.kv_heads
        q, k, v = self.project(h, positions)
        f32 = lambda a: a.astype(jnp.float32)
        q = f32(q).reshape(S, Hkv, -1, self.dk)            # [S, Hkv, g, Dk]
        s = jnp.einsum("qngd,knd->ngqk", q, f32(k)) * self.dk ** -0.5
        i, j = positions[:, None], positions[None, :]
        seen = j <= i
        if self.window:
            seen = seen & (j > i - self.window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        denom_more = 0.0
        if self.has_sinks:
            sink = self.sinks.reshape(Hkv, -1)[:, :, None, None]
            m = jnp.maximum(m, sink)
            denom_more = jnp.exp(sink - m)
        e = jnp.exp(s - m)
        w = e / (jnp.sum(e, axis=-1, keepdims=True) + denom_more)
        o = jnp.einsum("ngqk,knd->qngd", w, f32(v))
        return self.out(o.reshape(S, -1, self.dv).astype(h.dtype))


class MiMoDenseFFN(nn.Layer):
    kind = DS.DENSE

    def __init__(self, cfg, make, prefix):
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


class MiMoRoutedFFN(nn.Layer):
    kind = DS.ROUTED

    def __init__(self, cfg: MiMoV2Config, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, I = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.experts_held[1] - cfg.experts_held[0]
        self.router = p("router", (cfg.n_routed_experts, E))
        self.router_bias = p("router_bias", (cfg.n_routed_experts,))
        self.experts_gate = p("experts_gate", (n, E, I))
        self.experts_up = p("experts_up", (n, E, I))
        self.experts_down = p("experts_down", (n, I, E))

    def apply(self, x, valid):
        """``x [Q, E]`` -> ``(the held experts' part of the routed sum,
        counters)``; the pairs' layout and the grouped products' trips are
        ``axk1.routed_experts``' own, from the shapes (a sixteenth of the
        experts are held: ``Q k / 16`` pairs are expected)."""
        cfg = self.cfg
        with DS.section(DS.MOE_SCOPE):
            with DS.section(DS.ROUTER):
                idx, w, _ = route_top_k(
                    x, self.router._data, cfg.num_experts_per_tok,
                    cfg.routed_scaling_factor or 1.0, cfg.norm_topk_prob,
                    scoring="sigmoid", select_bias=self.router_bias._data)
            y, counters = routed_experts(
                x, valid, idx, w,
                (self.experts_gate._data, self.experts_up._data,
                 self.experts_down._data), cfg.experts_held,
                cfg.n_routed_experts)
        with DS.section(DS.MLP):          # with the add that closes the layer
            return y.astype(x.dtype), counters


class MiMoLayer(nn.Layer):
    def __init__(self, cfg: MiMoV2Config, index: int, make):
        super().__init__()
        prefix = f"layers.{index}."
        p = _params(make, prefix)
        self.cfg = cfg
        window = cfg.hybrid_layer_pattern[index] == WINDOW
        self.attn_norm = p("attn_norm", (cfg.hidden_size,))
        self.attn = MiMoAttention(cfg, window, make, prefix + "attn.")
        self.ffn_norm = p("ffn_norm", (cfg.hidden_size,))
        ffn = MiMoRoutedFFN if cfg.moe_layer_freq[index] else MiMoDenseFFN
        self.ffn = ffn(cfg, make, prefix + "ffn.")

    @property
    def sinks(self):
        return self.attn.sinks

    @property
    def layer_spec(self) -> DS.LayerSpec:
        a = self.attn
        cache = DS.CacheSpec(rows=a.kv_heads,
                             lanes=stored_lanes(a.dk, a.dv),
                             k_lanes=a.dk, v_lanes=a.dv)
        return DS.LayerSpec(DS.FULL, cache, self.ffn.kind,
                            window=a.window, sinks=a.has_sinks,
                            query_heads=a.heads)

    def _ffn(self, x, valid):
        with DS.section(DS.NORM):
            h = _rms_norm(x, self.ffn_norm._data,
                          self.cfg.layernorm_epsilon)
        y, counters = self.ffn.apply(h, valid)
        with DS.section(DS.MLP):
            return x + y, counters

    # -- the decoder spec's layer surface (x is a Tensor [1, Q, E]) --------
    def attn_in(self, x, positions):
        import jax.numpy as jnp
        with DS.section(DS.NORM):
            h = _rms_norm(x._data[0], self.attn_norm._data,
                          self.cfg.layernorm_epsilon)
        with DS.section(DS.QKV):
            q, k, v = self.attn.project(h, positions)
            return jnp.swapaxes(q, 0, 1), (k, v)          # [H, Q, Dk]

    def attn_out(self, x, a, row_valid):
        import jax.numpy as jnp
        with DS.section(DS.O_PROJ):
            o = self.attn.out(jnp.swapaxes(a, 0, 1))      # a [H, Q, Dv]
            x = x._data[0] + o
        y, counters = self._ffn(x, row_valid)
        with DS.section(DS.MLP):
            return Tensor(y[None], stop_gradient=True), counters

    # -- no cache: one whole sequence [S, E] -------------------------------
    def full(self, x, positions):
        import jax.numpy as jnp
        h = _rms_norm(x, self.attn_norm._data, self.cfg.layernorm_epsilon)
        x = x + self.attn.naive(h, positions)
        return self._ffn(x, jnp.ones(x.shape[0], bool))[0]


class MiMoV2ForCausalLM(nn.Layer):
    """MiMo-V2-Flash with its untied head. ``forward(input_ids [B, S])`` ->
    float32 logits ``[B, S, V]`` (no cache); ``serving_decoder()`` is what
    ``GenerationEngine`` consumes: its spec has one cache group for the
    global layers and one for the window layers. Parameters are made by
    ``param_init(name, shape, dtype)``, one call a parameter, every array
    ONCE in its serving dtype (as ``AXK1ForCausalLM``)."""

    def __init__(self, cfg: MiMoV2Config, dtype="float32",
                 param_init: Optional[Callable] = None):
        super().__init__()
        self.cfg = cfg
        make = _param_maker(dtype, param_init, cfg.initializer_range)
        self.embed = Parameter(make("embed", (cfg.vocab_size, cfg.hidden_size)))
        self.layers = nn.LayerList(
            [MiMoLayer(cfg, i, make) for i in range(cfg.num_hidden_layers)])
        self.norm = Parameter(make("norm", (cfg.hidden_size,)))
        self.lm_head = Parameter(make("lm_head",
                                      (cfg.hidden_size, cfg.vocab_size)))
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
        return Tensor(_rms_norm(x._data, self.norm._data,
                                self.cfg.layernorm_epsilon),
                      stop_gradient=True)

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
