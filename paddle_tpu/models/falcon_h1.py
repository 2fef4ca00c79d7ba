"""Falcon-H1 (``model_type: falcon_h1``, tiiuae/Falcon-H1-34B-Instruct
``config.json``): a decoder whose EVERY layer runs a Mamba-2 state-space
mixer and grouped-query attention IN PARALLEL on the same normed input,
adds the two, and then runs a SwiGLU FFN — the fifth caller of the
decoder spec (``models/decoder_spec.py``), and the first whose layers
hold a recurrent STATE beside their attention cache.

Per layer, pre-norm with RMSNorm and residual adds, no biases but the
convolution's; ``u = RMSNorm(x; attn_norm)``:

* **Attention** on ``u' = u * attention_in_multiplier`` (1 as
  published). ``q = W_q u'`` -> ``H`` heads of ``Dh``; ``k = (W_k u') *
  key_multiplier``, ``v = W_v u'`` -> ``Hkv`` heads; rotary positions
  on all ``Dh`` lanes (``rope_theta``, the half-split convention of
  ``models/sdar.py``, no scaling); query head
  ``j`` reads KV head ``j // (H / Hkv)``; causal ``softmax(q k^T /
  sqrt(Dh)) v``; ``a = W_o(.) * attention_out_multiplier``. What a token
  leaves in the cache is one ``[K | V]`` row a KV head.
* **Mixer** (Mamba-2: ``mamba_d_ssm = heads x P``, state ``N``, ``G``
  groups, ``d_conv`` taps). ``[z | xBC | dt] = (W_in (u *
  ssm_in_multiplier)) * m`` of widths ``d_ssm | d_ssm + 2 G N | heads``,
  ``m`` the muP vector that holds ``ssm_multipliers[0..4]`` on the z, x,
  B, C, dt segments; ``xBC_t = silu(sum_j w_j xBC_{t-K+1+j} + b)``
  (depthwise, causal); split x ``[heads, P]``, B, C ``[G, N]`` (head
  ``h`` reads group ``h // (heads / G)``); ``dt_t = softplus(dt_t +
  dt_bias_h)``, ``A_h = -exp(A_log_h)``; **``H_t = exp(dt_t A_h)
  H_{t-1} + dt_t x_t (x) B_t``**, ``y_t = H_t C_t + D_h x_t``; gated norm
  with ``mamba_norm_before_gate`` false: ``g = RMSNorm_grouped(y *
  silu(z); G groups, learned gain)``; ``s = W_out g *
  ssm_out_multiplier``. What a SEQUENCE leaves behind is the last ``K -
  1`` inputs of the convolution and ``H``, in float32 (the recurrence
  sums its rounding), whatever its context's length: the decoder spec's
  ``StateSpec``, held a slot by the paged pool.
* ``x = x + a + s``; then ``x = x + W_down(silu(W_gate v *
  mlp_multipliers[0]) * W_up v) * mlp_multipliers[1]`` with ``v =
  RMSNorm(x; ffn_norm)``.

Embedding ``* embedding_multiplier``; final RMSNorm; untied head, logits
``* lm_head_multiplier``. The fourteen multipliers are DATA of the
configuration (five of them the muP vector's). Products are in the
weights' dtype with float32 accumulation; the convolution, the
recurrence and every norm in float32.

The recurrence's two forms and the convolution over a ragged launch are
``ops/ssm.py``; ``forward`` here is the plain pass of whole sequences, no
cache, through the same functions from a zero state.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from .. import nn
from ..framework.tensor import Parameter, Tensor
from ..ops import ssm as SSM
from . import decoder_spec as DS
from .axk1 import _mm, _param_maker, _params, _rms_norm, _swiglu
from .sdar import rope_half_split

__all__ = ["FalconH1Config", "FalconH1ForCausalLM"]

STATE_DTYPE = "float32"


@dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # the fourteen multipliers (muP: the model was trained with them)
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: list = field(default_factory=lambda: [
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738])
    mlp_multipliers: list = field(default_factory=lambda: [
        0.1767766952966369, 0.011160714285714284])

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} is no "
                f"multiple of num_key_value_heads {self.num_key_value_heads}")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary halves)")
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_d_ssm {self.mamba_d_ssm} != mamba_n_heads x "
                f"mamba_d_head {self.mamba_n_heads * self.mamba_d_head}")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.mamba_d_ssm % self.mamba_n_groups:
            raise ValueError(
                f"mamba_n_groups {self.mamba_n_groups} divides neither the "
                f"{self.mamba_n_heads} heads nor the gated norm's lanes")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers holds 5 values (z, x, B, C, "
                             "dt) and mlp_multipliers 2 (gate, down)")
        for name in ("mamba_proj_bias", "attention_bias", "mlp_bias",
                     "mamba_norm_before_gate"):
            if getattr(self, name):
                raise ValueError(f"{name} true is not built (the published "
                                 f"configuration says false)")
        if not (self.mamba_conv_bias and self.mamba_rms_norm):
            raise ValueError("the mixer is built with its convolution's "
                             "bias and its gated norm, as published")

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, then B and C of every group."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_spec(self) -> DS.StateSpec:
        return DS.StateSpec((
            ("conv", (self.mamba_d_conv - 1, self.conv_dim), STATE_DTYPE),
            ("ssm", (self.mamba_n_heads, self.mamba_d_head,
                     self.mamba_d_state), STATE_DTYPE)))

    @classmethod
    def tiny(cls, **over):  # tests
        kw = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                  num_hidden_layers=2, num_attention_heads=10,
                  num_key_value_heads=2, head_dim=16, mamba_d_ssm=64,
                  mamba_n_heads=4, mamba_d_head=16, mamba_d_state=32,
                  mamba_n_groups=2, mamba_chunk_size=16,
                  max_position_embeddings=512, rope_theta=1e4)
        kw.update(over)
        return cls(**kw)


def mup_vector(cfg: FalconH1Config) -> np.ndarray:
    """The muP vector over ``[z | x | B | C | dt]``: ``ssm_multipliers[i]``
    on segment ``i``."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return np.concatenate([np.full(w, m, np.float32) for w, m in
                           zip(widths, cfg.ssm_multipliers)])


class FalconH1Attention(nn.Layer):
    def __init__(self, cfg: FalconH1Config, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        self.wq = p("wq", (E, H * Dh))
        self.wk = p("wk", (E, Hkv * Dh))
        self.wv = p("wv", (E, Hkv * Dh))
        self.wo = p("wo", (H * Dh, E))

    def project(self, u, positions):
        """``u [Q, E]`` (normed) -> ``q [Q, H, Dh]``, ``k``/``v [Q, Hkv,
        Dh]``, ``q`` and ``k`` rotated."""
        cfg = self.cfg
        Q = u.shape[0]
        qin = u if cfg.attention_in_multiplier == 1.0 else \
            (u * cfg.attention_in_multiplier).astype(u.dtype)
        q = _mm(qin, self.wq._data).reshape(Q, cfg.num_attention_heads, -1)
        k = (_mm(qin, self.wk._data) * cfg.key_multiplier).astype(
            u.dtype).reshape(Q, cfg.num_key_value_heads, -1)
        v = _mm(qin, self.wv._data).reshape(Q, cfg.num_key_value_heads, -1)
        return (rope_half_split(q, positions, cfg.rope_theta),
                rope_half_split(k, positions, cfg.rope_theta), v)

    def out(self, o):
        """``o [Q, H, Dh]`` -> ``[Q, E]``, multiplier applied."""
        y = _mm(o.reshape(o.shape[0], -1), self.wo._data)
        return (y * self.cfg.attention_out_multiplier).astype(y.dtype)

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


class FalconH1Mixer(nn.Layer):
    """The Mamba-2 branch (module doc). ``apply`` runs a ragged launch's
    rows against the slots' state arrays."""

    def __init__(self, cfg: FalconH1Config, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, D, Hs = cfg.hidden_size, cfg.mamba_d_ssm, cfg.mamba_n_heads
        self.ssm_in = p("ssm_in", (E, D + cfg.conv_dim + Hs))
        self.conv_w = p("conv_w", (cfg.mamba_d_conv, cfg.conv_dim))
        self.conv_b = p("conv_b", (cfg.conv_dim,))
        self.dt_bias = p("dt_bias", (Hs,))
        self.A_log = p("A_log", (Hs,))
        self.D = p("D", (Hs,))
        self.ssm_norm = p("ssm_norm", (D,))
        self.ssm_out = p("ssm_out", (D, E))
        self.mup = mup_vector(cfg)

    def apply(self, u, lay, state, index):
        """``u [Q, E]`` (normed), ``lay`` the rows' sequence layout,
        ``state = (conv tails, recurrent states)`` -> ``(s [Q, E],
        state)``."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        D, Hs, G, N = (cfg.mamba_d_ssm, cfg.mamba_n_heads,
                       cfg.mamba_n_groups, cfg.mamba_d_state)
        f32 = lambda a: a._data.astype(jnp.float32)
        tail, hidden = state
        with DS.section(DS.SSM_PROJ):
            # ONE product, held: its three readers (z, xBC, dt) otherwise
            # each get a rematerialized copy of the whole product fused
            # into them — six products a layer on the chip (PERF.md, PR 40)
            zxbcdt = jax.lax.optimization_barrier(
                _mm((u * cfg.ssm_in_multiplier).astype(u.dtype),
                    self.ssm_in._data))
            C = D + cfg.conv_dim
            part = lambda lo, hi: zxbcdt[:, lo:hi].astype(jnp.float32) \
                * self.mup[lo:hi]
            z, xbc, dt = part(0, D), part(D, C), part(C, C + Hs)
        with DS.section(DS.SSM_CONV):
            xbc, tail = SSM.conv_rows(xbc, self.conv_w._data,
                                      self.conv_b._data, tail, index, lay)
            xbc = jax.nn.silu(xbc)
        with DS.section(DS.SSM_SCAN):
            Q = u.shape[0]
            x = xbc[:, :D].reshape(Q, Hs, -1)
            b = xbc[:, D:D + G * N].reshape(Q, G, N)
            c = xbc[:, D + G * N:].reshape(Q, G, N)
            dt = jax.nn.softplus(dt + f32(self.dt_bias))
            y, hidden = SSM.ssm_scan(
                x, dt, -jnp.exp(f32(self.A_log)), b, c, f32(self.D),
                hidden, index, lay, chunk=cfg.mamba_chunk_size)
        with DS.section(DS.SSM_PROJ):
            g = (y.reshape(Q, D) * jax.nn.silu(z)).reshape(Q, G, D // G)
            g = g * jax.lax.rsqrt(
                jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps)
            g = (g.reshape(Q, D) * f32(self.ssm_norm)).astype(u.dtype)
            s = _mm(g, self.ssm_out._data)
            return (s * cfg.ssm_out_multiplier).astype(s.dtype), \
                (tail, hidden)


class FalconH1FFN(nn.Layer):
    kind = DS.DENSE

    def __init__(self, cfg: FalconH1Config, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, I = cfg.hidden_size, cfg.intermediate_size
        self.gate = p("gate", (E, I))
        self.up = p("up", (E, I))
        self.down = p("down", (I, E))

    def apply(self, v):
        gm, dm = self.cfg.mlp_multipliers
        with DS.section(DS.MLP):
            return (_swiglu(v, self.gate._data, self.up._data,
                            self.down._data, gate_scale=gm)
                    * dm).astype(v.dtype)


class FalconH1Layer(nn.Layer):
    def __init__(self, cfg: FalconH1Config, index: int, make):
        super().__init__()
        prefix = f"layers.{index}."
        p = _params(make, prefix)
        self.cfg = cfg
        self.attn_norm = p("attn_norm", (cfg.hidden_size,))
        self.attn = FalconH1Attention(cfg, make, prefix + "attn.")
        self.ssm = FalconH1Mixer(cfg, make, prefix + "ssm.")
        self.ffn_norm = p("ffn_norm", (cfg.hidden_size,))
        self.ffn = FalconH1FFN(cfg, make, prefix + "ffn.")

    def _normed(self, x):
        """The input of both branches: ``x`` a Tensor ``[1, Q, E]`` or its
        rows."""
        with DS.section(DS.NORM):
            rows = x._data[0] if isinstance(x, Tensor) else x
            return _rms_norm(rows, self.attn_norm._data,
                             self.cfg.rms_norm_eps)

    def _ffn(self, x):
        with DS.section(DS.NORM):
            v = _rms_norm(x, self.ffn_norm._data, self.cfg.rms_norm_eps)
        y = self.ffn.apply(v)
        with DS.section(DS.MLP):
            return x + y

    # -- the decoder spec's layer surface (x is a Tensor [1, Q, E]) --------
    def attn_in(self, x, positions):
        import jax.numpy as jnp
        u = self._normed(x)
        with DS.section(DS.QKV):
            q, k, v = self.attn.project(u, positions)
            return jnp.swapaxes(q, 0, 1), (k, v)          # [H, Q, Dh]

    def mixer(self, x, layout, state, index):
        return self.ssm.apply(self._normed(x), layout, state, index)

    def attn_out(self, x, a, row_valid, s):
        import jax.numpy as jnp
        with DS.section(DS.O_PROJ):
            x = x._data[0] + self.attn.out(jnp.swapaxes(a, 0, 1)) + s
        y = self._ffn(x)
        with DS.section(DS.MLP):
            return Tensor(y[None], stop_gradient=True), None

    # -- no cache: one whole sequence [S, E] from a zero state -------------
    def full(self, x, positions):
        import jax.numpy as jnp
        S = x.shape[0]
        u = self._normed(x)
        lay = SSM.SeqLayout(
            jnp.zeros(S, jnp.int32), positions.astype(jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.full(1, S, jnp.int32),
            jnp.ones(1, bool))
        zeros = tuple(jnp.zeros((1, 2) + shape, dtype) for _, shape, dtype
                      in self.cfg.state_spec.parts)
        s, _ = self.ssm.apply(u, lay, zeros, 0)
        return self._ffn(x + self.attn.naive(u, positions) + s)


class FalconH1ForCausalLM(nn.Layer):
    """Falcon-H1 with its untied head. ``forward(input_ids [B, S])`` ->
    float32 logits ``[B, S, V]`` (no cache); ``serving_decoder()`` is what
    ``GenerationEngine`` consumes. Parameters are made by
    ``param_init(name, shape, dtype)``, one call a parameter, every array
    ONCE in its serving dtype (as ``AXK1ForCausalLM``)."""

    def __init__(self, cfg: FalconH1Config, dtype="float32",
                 param_init: Optional[Callable] = None):
        super().__init__()
        self.cfg = cfg
        make = _param_maker(dtype, param_init or _default_init(cfg),
                            cfg.initializer_range)
        self.embed = Parameter(make("embed", (cfg.vocab_size, cfg.hidden_size)))
        self.layers = nn.LayerList(
            [FalconH1Layer(cfg, i, make)
             for i in range(cfg.num_hidden_layers)])
        self.norm = Parameter(make("norm", (cfg.hidden_size,)))
        self.lm_head = Parameter(make("lm_head",
                                      (cfg.hidden_size, cfg.vocab_size)))
        cache = DS.CacheSpec(rows=cfg.num_key_value_heads,
                             lanes=2 * cfg.head_dim)
        self.spec = DS.DecoderSpec(
            layers=tuple(DS.LayerSpec(DS.FULL, cache, DS.DENSE,
                                      query_heads=cfg.num_attention_heads,
                                      state=cfg.state_spec)
                         for _ in self.layers),
            vocab_size=cfg.vocab_size,
            max_positions=cfg.max_position_embeddings)

    def serving_decoder(self):
        return self

    # -- the decoder spec's model surface ----------------------------------
    def embed_tokens(self, token_ids, positions):
        x = self.embed._data[token_ids]
        return Tensor((x * self.cfg.embedding_multiplier).astype(x.dtype)[None],
                      stop_gradient=True)

    def final_norm(self, x):
        return Tensor(_rms_norm(x._data, self.norm._data,
                                self.cfg.rms_norm_eps), stop_gradient=True)

    def logits(self, hidden):
        import jax.numpy as jnp
        return Tensor(jnp.dot(hidden._data, self.lm_head._data,
                              preferred_element_type=jnp.float32)
                      * self.cfg.lm_head_multiplier, stop_gradient=True)

    def forward(self, input_ids):
        import jax.numpy as jnp
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        out = []
        for row in ids:
            x = self.embed_tokens(row, pos)._data[0]
            for layer in self.layers:
                x = layer.full(x, pos)
            out.append(self.logits(self.final_norm(
                Tensor(x, stop_gradient=True)))._data)
        return Tensor(jnp.stack(out), stop_gradient=True)


def _default_init(cfg: FalconH1Config):
    """The family's initialisation of the mixer's per-head vectors (``A_log
    = log(1 .. heads)``, ``D = 1``, ``dt_bias`` the inverse softplus of a
    step in ``[1e-3, 1e-1]``), norm gains of 1 and ``N(0,
    initializer_range^2)`` elsewhere."""
    import jax
    import jax.numpy as jnp
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 4096))
    Hs = cfg.mamba_n_heads

    def init(name, shape, dtype):
        leaf = name.rsplit(".", 1)[-1]
        if leaf.endswith("norm") or leaf == "D":
            return jnp.ones(shape, dtype)
        if leaf == "A_log":
            return jnp.log(jnp.arange(1, Hs + 1, dtype=jnp.float32)
                           ).astype(dtype)
        if leaf == "dt_bias":
            dt = jnp.exp(jnp.linspace(np.log(1e-3), np.log(1e-1), Hs))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        if leaf == "conv_b":
            return jnp.zeros(shape, dtype)
        return (cfg.initializer_range * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    return init
