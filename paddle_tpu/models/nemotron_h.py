"""Nemotron-H (``model_type: nemotron_h``,
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 ``config.json``): a decoder
whose every block is HALF of what the other families call a layer — the
eighth caller of the decoder spec (``models/decoder_spec.py``), and the
first with layers that are a mixer WITHOUT an FFN and an FFN WITHOUT a
mixer.

``hybrid_override_pattern`` names the kind of each published block, a
character a block; every block is ``x <- x + f(RMSNorm(x; g))`` (eps
``layer_norm_epsilon``), no biases but the convolution's, ``u`` the normed
input:

* **``M``** — a Mamba-2 mixer as the block's WHOLE content (``heads =
  mamba_num_heads`` of ``P = mamba_head_dim``, ``d_ssm = heads P``, state
  ``N = ssm_state_size``, ``G = n_groups``, ``K = conv_kernel`` taps).
  ``[z | xBC | dt] = W_in u`` of widths ``d_ssm | d_ssm + 2 G N | heads``;
  ``xBC_t = silu(sum_j w_j xBC_{t-K+1+j} + b)`` (depthwise, causal, zeros
  before position 0); split x ``[heads, P]``, B, C ``[G, N]`` (head ``h``
  reads group ``h // (heads / G)``); ``dt_t = softplus(dt_t + dt_bias_h)``
  (not clamped), ``A_h = -exp(A_log_h)``; **``H_t = exp(dt_t A_h) H_{t-1}
  + dt_t x_t (x) B_t``**, ``y_t = H_t C_t + D_h x_t``; ``g = RMSNorm over
  each of G groups of d_ssm / G lanes of (y * silu(z))`` with a gain a
  lane; ``f = W_out g``. What a SEQUENCE leaves behind is the
  convolution's last ``K - 1`` inputs and ``H``, float32, whatever its
  context's length: the spec's ``StateSpec``, a row a slot of the pool.
  The recurrence's two forms and the convolution over a ragged launch
  are ``ops/ssm.py``, as Falcon-H1's.
* **``*``** — grouped-query attention as the block's whole content: ``q =
  W_q u`` -> ``H`` heads of ``Dh``, ``k``, ``v`` -> ``Hkv`` heads, query
  head ``j`` reads KV head ``j // (H / Hkv)``, causal ``softmax(q k^T /
  sqrt(Dh)) v``, ``f = W_o(.)``. NO position embedding: the family's
  attention has no rotary (the Mamba blocks carry position), so
  ``attn_in`` ignores ``positions``. What a token leaves in the cache is
  one ``[K | V]`` row a KV head.
* **``E``** — LatentMoE as the block's whole content. ``s = sigmoid(W_g
  u)`` in float32 over ALL ``n_routed_experts``; the choice is the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  (``n_group`` 1: no group limit); the weights are the chosen ``s`` over
  their sum (``norm_topk_prob``) times ``routed_scaling_factor``. The
  experts run in a LATENT of ``moe_latent_size`` lanes: ``v = W_down u``;
  ``expert_e(v) = W2_e relu(W1_e v)^2`` (UNGATED, ``mlp_hidden_act:
  relu2``; ``latent -> moe_intermediate_size -> latent``); ``f = W_up
  (sum_e w_e expert_e(v)) + shared(u)``, ``shared(u) = W2_s relu(W1_s
  u)^2`` at the stream's width (``moe_shared_expert_intermediate_size``).
  The grouped products are ``axk1.routed_experts`` (handed two matrices
  an expert, it runs the ungated form at the width of what it is handed);
  the two latent projections are THIS layer's, outside them.

**Serving a share** (``experts_held=(lo, hi)``): as ``models/axk1.py``:
the router stays ``n_routed_experts`` wide, the block adds the held
experts' part of the routed sum (up-projected) to ``shared(u)``, and what
the absent experts would add is left out.

Embedding unscaled; after the last block ``RMSNorm(.; norm_f)``; untied
head. The multi-token-prediction head (``num_nextn_predict_layers``) is a
drafter and is not built. Products are in the weights' dtype with float32
accumulation; the convolution, the recurrence, every norm and the router
in float32.

``forward`` is the plain pass of whole sequences, no cache, through the
same functions from a zero state.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np

from .. import nn
from ..framework.tensor import Parameter, Tensor
from ..ops import ssm as SSM
from . import decoder_spec as DS
from .axk1 import (_mm, _param_maker, _params, _rms_norm, route_top_k,
                   routed_experts)

__all__ = ["NemotronHConfig", "NemotronHForCausalLM"]

STATE_DTYPE = "float32"
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # the range [lo, hi) of experts THIS chip holds: the router scores all
    # ``n_routed_experts``, an ``E`` block adds only these experts' part
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, int(self.n_routed_experts))
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.n_routed_experts} experts")
        self.experts_held = (lo, hi)
        # a stage holds the first blocks of the published pattern
        self.hybrid_override_pattern = str(
            self.hybrid_override_pattern)[:self.num_hidden_layers]
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or any(
                c not in (MAMBA, ATTENTION, EXPERTS) for c in pattern):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} names "
                f"{len(pattern)} blocks for {self.num_hidden_layers}: one "
                f"of {MAMBA!r}, {ATTENTION!r}, {EXPERTS!r} a block (a "
                f"dense-FFN block, '-', is not built: the published "
                f"pattern has none)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} is no "
                f"multiple of num_key_value_heads {self.num_key_value_heads}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"n_groups {self.n_groups} does not divide the "
                f"{self.mamba_num_heads} mixer heads")
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be >= 2: a convolution of "
                             "one tap leaves no tail behind")
        if not self.use_conv_bias:
            raise ValueError("the mixer is built with its convolution's "
                             "bias, as published")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what an E block builds "
                             f"(n_shared_experts={self.n_shared_experts})")

    @property
    def d_ssm(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, then B and C of every group."""
        return self.d_ssm + 2 * self.n_groups * self.ssm_state_size

    @property
    def state_spec(self) -> DS.StateSpec:
        return DS.StateSpec((
            ("conv", (self.conv_kernel - 1, self.conv_dim), STATE_DTYPE),
            ("ssm", (self.mamba_num_heads, self.mamba_head_dim,
                     self.ssm_state_size), STATE_DTYPE)))

    @classmethod
    def tiny(cls, **over):  # tests
        kw = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                  hybrid_override_pattern="ME*EM", num_attention_heads=8,
                  num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
                  mamba_head_dim=16, ssm_state_size=32, n_groups=2,
                  chunk_size=16, n_routed_experts=16, num_experts_per_tok=4,
                  moe_intermediate_size=48, moe_latent_size=32,
                  moe_shared_expert_intermediate_size=96,
                  max_position_embeddings=512)
        kw.update(over)
        return cls(**kw)


def _relu2(x, up, down):
    """``down(relu(up(x))^2)``: operands in the weights' dtype, float32
    accumulation, the square taken in float32 and rounded once. Returns
    float32."""
    import jax
    import jax.numpy as jnp
    f32 = lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
    return f32(jnp.square(jax.nn.relu(f32(x, up))).astype(x.dtype), down)


class NemotronHMixer(nn.Layer):
    """An ``M`` block's Mamba-2 mixer (module doc). ``apply`` runs a
    ragged launch's rows against the slots' state arrays."""

    def __init__(self, cfg: NemotronHConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, D, Hs = cfg.hidden_size, cfg.d_ssm, cfg.mamba_num_heads
        self.ssm_in = p("ssm_in", (E, D + cfg.conv_dim + Hs))
        self.conv_w = p("conv_w", (cfg.conv_kernel, cfg.conv_dim))
        self.conv_b = p("conv_b", (cfg.conv_dim,))
        self.dt_bias = p("dt_bias", (Hs,))
        self.A_log = p("A_log", (Hs,))
        self.D = p("D", (Hs,))
        self.ssm_norm = p("ssm_norm", (D,))
        self.ssm_out = p("ssm_out", (D, E))

    def apply(self, u, lay, state, index):
        """``u [Q, E]`` (normed), ``lay`` the rows' sequence layout,
        ``state = (conv tails, recurrent states)`` -> ``(f [Q, E],
        state)``."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        D, Hs, G, N = (cfg.d_ssm, cfg.mamba_num_heads, cfg.n_groups,
                       cfg.ssm_state_size)
        f32 = lambda a: a._data.astype(jnp.float32)
        tail, hidden = state
        with DS.section(DS.SSM_PROJ):
            # ONE product, held: its three readers (z, xBC, dt) otherwise
            # each get a rematerialized copy of the whole product fused
            # into them (models/falcon_h1.py; PERF.md, PR 40)
            zxbcdt = jax.lax.optimization_barrier(
                _mm(u, self.ssm_in._data))
            C = D + cfg.conv_dim
            part = lambda lo, hi: zxbcdt[:, lo:hi].astype(jnp.float32)
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
                hidden, index, lay, chunk=cfg.chunk_size)
        with DS.section(DS.SSM_PROJ):
            g = (y.reshape(Q, D) * jax.nn.silu(z)).reshape(Q, G, D // G)
            g = g * jax.lax.rsqrt(
                jnp.mean(g * g, axis=-1, keepdims=True)
                + cfg.layer_norm_epsilon)
            g = (g.reshape(Q, D) * f32(self.ssm_norm)).astype(u.dtype)
            return _mm(g, self.ssm_out._data), (tail, hidden)


class NemotronHAttention(nn.Layer):
    """A ``*`` block's grouped-query attention, no position embedding."""

    def __init__(self, cfg: NemotronHConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        self.wq = p("wq", (E, H * Dh))
        self.wk = p("wk", (E, Hkv * Dh))
        self.wv = p("wv", (E, Hkv * Dh))
        self.wo = p("wo", (H * Dh, E))

    def project(self, u):
        """``u [Q, E]`` (normed) -> ``q [Q, H, Dh]``, ``k``/``v [Q, Hkv,
        Dh]``."""
        cfg = self.cfg
        Q = u.shape[0]
        return (_mm(u, self.wq._data).reshape(Q, cfg.num_attention_heads, -1),
                _mm(u, self.wk._data).reshape(Q, cfg.num_key_value_heads, -1),
                _mm(u, self.wv._data).reshape(Q, cfg.num_key_value_heads, -1))

    def out(self, o):
        """``o [Q, H, Dh]`` -> ``[Q, E]``."""
        return _mm(o.reshape(o.shape[0], -1), self.wo._data)

    def naive(self, u, positions):
        """Causal attention of one whole sequence (no cache)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        S, Hkv = u.shape[0], cfg.num_key_value_heads
        q, k, v = self.project(u)
        f32 = lambda a: a.astype(jnp.float32)
        q = f32(q).reshape(S, Hkv, -1, cfg.head_dim)
        s = jnp.einsum("qngd,knd->ngqk", q, f32(k)) * cfg.head_dim ** -0.5
        s = jnp.where((positions[None, :] <= positions[:, None])[None, None],
                      s, -jnp.inf)
        o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), f32(v))
        return self.out(o.reshape(S, -1, cfg.head_dim).astype(u.dtype))


class NemotronHLatentMoE(nn.Layer):
    """An ``E`` block's experts (module doc): ungated ``relu^2`` experts
    in a latent of ``moe_latent_size`` lanes, a shared one at the stream's
    width."""
    kind = DS.ROUTED

    def __init__(self, cfg: NemotronHConfig, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, L, I = (cfg.hidden_size, cfg.moe_latent_size,
                   cfg.moe_intermediate_size)
        Is = cfg.moe_shared_expert_intermediate_size
        n = cfg.experts_held[1] - cfg.experts_held[0]
        self.router = p("router", (cfg.n_routed_experts, E))
        self.router_bias = p("router_bias", (cfg.n_routed_experts,))
        self.latent_down = p("latent_down", (E, L))
        self.latent_up = p("latent_up", (L, E))
        self.shared_up = p("shared_up", (E, Is))
        self.shared_down = p("shared_down", (Is, E))
        self.experts_up = p("experts_up", (n, L, I))
        self.experts_down = p("experts_down", (n, I, L))

    def apply(self, u, valid):
        """``u [Q, E]`` (normed) -> ``(shared(u) + W_up of the held
        experts' part, counters)``."""
        cfg = self.cfg
        with DS.section(DS.MOE_SCOPE):
            with DS.section(DS.ROUTER):
                idx, w, _ = route_top_k(
                    u, self.router._data, cfg.num_experts_per_tok,
                    cfg.routed_scaling_factor, cfg.norm_topk_prob,
                    scoring="sigmoid", select_bias=self.router_bias._data)
            with DS.section(DS.LATENT_PROJ):
                v = _mm(u, self.latent_down._data)
            y, counters = routed_experts(
                v, valid, idx, w,
                (self.experts_up._data, self.experts_down._data),
                cfg.experts_held, cfg.n_routed_experts)
            with DS.section(DS.LATENT_PROJ):
                routed = _mm(y.astype(u.dtype), self.latent_up._data)
            with DS.section(DS.SHARED_EXPERT):
                shared = _relu2(u, self.shared_up._data,
                                self.shared_down._data)
            return (shared + routed).astype(u.dtype), counters


class NemotronHBlock(nn.Layer):
    """One published block: a norm and ONE of the three mixers."""

    def __init__(self, cfg: NemotronHConfig, index: int, make):
        super().__init__()
        prefix = f"layers.{index}."
        self.cfg = cfg
        self.kind = cfg.hybrid_override_pattern[index]
        self.norm = _params(make, prefix)("norm", (cfg.hidden_size,))
        if self.kind == MAMBA:
            self.ssm = NemotronHMixer(cfg, make, prefix + "ssm.")
        elif self.kind == ATTENTION:
            self.attn = NemotronHAttention(cfg, make, prefix + "attn.")
        else:
            self.ffn = NemotronHLatentMoE(cfg, make, prefix + "ffn.")

    @property
    def layer_spec(self) -> DS.LayerSpec:
        cfg = self.cfg
        if self.kind == MAMBA:
            return DS.LayerSpec(None, None, DS.NO_FFN, state=cfg.state_spec)
        if self.kind == ATTENTION:
            return DS.LayerSpec(
                DS.FULL, DS.CacheSpec(rows=cfg.num_key_value_heads,
                                      lanes=2 * cfg.head_dim), DS.NO_FFN,
                query_heads=cfg.num_attention_heads)
        return DS.LayerSpec(None, None, DS.ROUTED)

    def _normed(self, x):
        with DS.section(DS.NORM):
            rows = x._data[0] if isinstance(x, Tensor) else x
            return _rms_norm(rows, self.norm._data,
                             self.cfg.layer_norm_epsilon)

    # -- the decoder spec's layer surface (x is a Tensor [1, Q, E]) --------
    def attn_in(self, x, positions):
        """A ``*`` block's only. No rotary: ``positions`` is not read."""
        import jax.numpy as jnp
        u = self._normed(x)
        with DS.section(DS.QKV):
            q, k, v = self.attn.project(u)
            return jnp.swapaxes(q, 0, 1), (k, v)          # [H, Q, Dh]

    def mixer(self, x, layout, state, index):
        """An ``M`` block's only: its whole content but the residual."""
        return self.ssm.apply(self._normed(x), layout, state, index)

    def attn_out(self, x, a, row_valid, s=None):
        """``a`` the attention's output (a ``*`` block) or ``None`` with
        ``s`` the mixer's (an ``M`` block): the residual closes the block,
        there is no FFN."""
        import jax.numpy as jnp
        with DS.section(DS.O_PROJ):
            y = x._data[0] + (s if a is None
                              else self.attn.out(jnp.swapaxes(a, 0, 1)))
            return Tensor(y[None], stop_gradient=True), None

    def ffn_out(self, x, row_valid):
        """An ``E`` block's only: the whole block."""
        f, counters = self.ffn.apply(self._normed(x), row_valid)
        with DS.section(DS.MLP):          # the add that closes the block
            return Tensor((x._data[0] + f)[None], stop_gradient=True), \
                counters

    # -- no cache: one whole sequence [S, E] from a zero state -------------
    def full(self, x, positions):
        import jax.numpy as jnp
        S = x.shape[0]
        u = self._normed(x)
        if self.kind == MAMBA:
            lay = SSM.SeqLayout(
                jnp.zeros(S, jnp.int32), positions.astype(jnp.int32),
                jnp.zeros(1, jnp.int32), jnp.full(1, S, jnp.int32),
                jnp.ones(1, bool))
            zeros = tuple(jnp.zeros((1, 2) + shape, dtype) for _, shape,
                          dtype in self.cfg.state_spec.parts)
            return x + self.ssm.apply(u, lay, zeros, 0)[0]
        if self.kind == ATTENTION:
            return x + self.attn.naive(u, positions)
        return x + self.ffn.apply(u, jnp.ones(S, bool))[0]


class NemotronHForCausalLM(nn.Layer):
    """Nemotron-H with its untied head. ``forward(input_ids [B, S])`` ->
    float32 logits ``[B, S, V]`` (no cache); ``serving_decoder()`` is what
    ``GenerationEngine`` consumes: ONE ``LayerSpec`` a published block, so
    the pool holds blocks for the ``*`` blocks, a state row a slot for the
    ``M`` blocks and nothing for the ``E`` blocks. Parameters are made by
    ``param_init(name, shape, dtype)``, one call a parameter, every array
    ONCE in its serving dtype (as ``AXK1ForCausalLM``)."""

    def __init__(self, cfg: NemotronHConfig, dtype="float32",
                 param_init: Optional[Callable] = None):
        super().__init__()
        self.cfg = cfg
        make = _param_maker(dtype, param_init or _default_init(cfg),
                            cfg.initializer_range)
        self.embed = Parameter(make("embed", (cfg.vocab_size, cfg.hidden_size)))
        self.layers = nn.LayerList(
            [NemotronHBlock(cfg, i, make)
             for i in range(cfg.num_hidden_layers)])
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
                                self.cfg.layer_norm_epsilon),
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


def _default_init(cfg: NemotronHConfig):
    """The family's initialisation of the mixer's per-head vectors (``A_log
    = log(1 .. heads)``, ``D = 1``, ``dt_bias`` the inverse softplus of a
    step in ``[time_step_min, time_step_max]`` = ``[1e-3, 1e-1]``), norm
    gains of 1, a zero score-correction bias and ``N(0,
    initializer_range^2)`` elsewhere."""
    import jax
    import jax.numpy as jnp
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 4096))
    Hs = cfg.mamba_num_heads

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
        if leaf in ("conv_b", "router_bias"):
            return jnp.zeros(shape, dtype)
        return (cfg.initializer_range * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(dtype)

    return init
