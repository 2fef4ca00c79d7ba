"""GPT-2 decoder-only language model — the flagship model (north-star
config 5: GPT-2 124M hybrid-parallel).

Design notes (TPU-first):
- pre-LN blocks, causal flash-friendly attention through the single
  ``scaled_dot_product_attention`` op (is_causal=True → no mask tensor is
  ever materialised; the Pallas override exploits this).
- weights stay [in, out] for the MXU; LM head ties the embedding matrix.
- no data-dependent python control flow: one forward is one XLA program.

Reference parity target: the GPT examples built on the reference's
MultiHeadAttention/TransformerDecoder (python/paddle/nn/layer/transformer.py)
and fleet meta_parallel GPT models.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .. import nn
from ..framework.dispatch import call_op
from ..framework.tensor import Tensor
from ..nn import functional as F

__all__ = ["GPTConfig", "GPTBlock", "GPTModel", "GPTForPretraining"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded to a multiple of 128
    hidden_size: int = 768           # (MXU-friendly vocab tiling)
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02

    @classmethod
    def gpt2_small(cls):  # 124M
        return cls()

    @classmethod
    def tiny(cls):  # for tests/dryrun
        return cls(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=128,
                   max_position_embeddings=64, hidden_dropout_prob=0.0,
                   attention_dropout_prob=0.0)


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        self.ln_1 = nn.LayerNorm(h)
        self.attn = nn.MultiHeadAttention(
            h, cfg.num_attention_heads, dropout=cfg.attention_dropout_prob)
        self.ln_2 = nn.LayerNorm(h)
        self.mlp_fc = nn.Linear(h, cfg.intermediate_size)
        self.mlp_proj = nn.Linear(cfg.intermediate_size, h)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def _qkv(self, x):
        """ln_1 + split-head q/k/v projections (shared by train/serve)."""
        return self._heads(self.ln_1(x))

    def _heads(self, h):
        """Split-head q/k/v projections of the normed rows."""
        q = self.attn._split_heads(self.attn.q_proj(h))
        k = self.attn._split_heads(self.attn.k_proj(h))
        v = self.attn._split_heads(self.attn.v_proj(h))
        return q, k, v

    def _tail(self, x, a):
        """out-proj + residual + MLP half of the block (shared)."""
        x = self._attn_residual(x, a)
        return self._mlp_residual(x, self.ln_2(x))

    def _attn_residual(self, x, a):
        return x + self.dropout(
            self.attn.out_proj(self.attn._merge_heads(a)))

    def _mlp_residual(self, x, h):
        """``x`` + the MLP of its normed rows ``h``."""
        m = self.mlp_proj(F.gelu(self.mlp_fc(h), approximate=True))
        return x + self.dropout(m)

    def forward(self, x, cache=None):
        # attention with implicit causal masking
        q, k, v = self._qkv(x)
        if cache is not None:
            k = call_op("concat", [cache.k, k], axis=1)
            v = call_op("concat", [cache.v, v], axis=1)
            cache = nn.MultiHeadAttention.Cache(k, v)
        a = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.attn.dropout if self.training else 0.0,
            training=self.training)
        x = self._tail(x, a)
        return x if cache is None else (x, cache)

    # -- static-cache decode path (serving) ---------------------------------
    # The concat cache above grows the seq axis every step, so each decode
    # step is a NEW XLA program — fine eagerly, fatal under jit.  These two
    # methods keep the cache at a FIXED [B, max_len, H, D] shape and write
    # into it with dynamic_update_slice, so the whole generate loop compiles
    # once (reference analog: the fixed-capacity CacheKV of
    # paddle/fluid/operators/fused/fused_multi_transformer_op.cu:1).
    def prefill(self, x, cache_k, cache_v, key_valid=None):
        """Process the whole prompt; write its K/V into the cache at [0:S).

        x: [B, S, E]; cache_k/v: jnp [B, max_len, H, D] (zeros);
        key_valid: optional jnp bool [B, S] — False marks left-pad
        positions no query may attend to. Returns (hidden, cache_k,
        cache_v) with caches as raw jnp arrays.
        """
        from jax import lax
        q, k, v = self._qkv(x)
        cache_k = lax.dynamic_update_slice(
            cache_k, k._data.astype(cache_k.dtype), (0, 0, 0, 0))
        cache_v = lax.dynamic_update_slice(
            cache_v, v._data.astype(cache_v.dtype), (0, 0, 0, 0))
        mask = None if key_valid is None else \
            Tensor(key_valid[:, None, None, :])  # [B, 1(h), 1(q), S]
        a = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                           attn_mask=mask)
        return self._tail(x, a), cache_k, cache_v

    def decode_step(self, x, cache_k, cache_v, pos, key_valid=None):
        """One token: x [B, 1, E], pos scalar (traced) — attend over the
        first pos+1 cache rows (or the rows marked True in key_valid
        [B, max_len] when prompts are ragged/left-padded). Cache shapes
        never change."""
        import jax.numpy as jnp
        from jax import lax
        q, k, v = self._qkv(x)
        z = jnp.int32(0)
        pos = jnp.asarray(pos, jnp.int32)
        cache_k = lax.dynamic_update_slice(
            cache_k, k._data.astype(cache_k.dtype), (z, pos, z, z))
        cache_v = lax.dynamic_update_slice(
            cache_v, v._data.astype(cache_v.dtype), (z, pos, z, z))
        # valid-position mask, broadcast over [B, H, q=1, max_len]
        max_len = cache_k.shape[1]
        if key_valid is None:
            mask = (jnp.arange(max_len) <= pos)[None, None, None, :]
        else:
            mask = key_valid[:, None, None, :]
        a = F.scaled_dot_product_attention(
            q, Tensor(cache_k, stop_gradient=True),
            Tensor(cache_v, stop_gradient=True), attn_mask=Tensor(mask))
        return self._tail(x, a), cache_k, cache_v


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wpe = nn.Embedding(cfg.max_position_embeddings,
                                cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(cfg.hidden_dropout_prob)
        self.blocks = nn.LayerList(
            [GPTBlock(cfg) for _ in range(cfg.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            import jax.numpy as jnp
            seq = input_ids.shape[1]
            position_ids = Tensor(jnp.arange(seq, dtype=jnp.int64)[None, :])
        x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        for block in self.blocks:
            x = block(x)
        return self.ln_f(x)

    def logits(self, hidden):
        """LM head tied to wte (matmul against the embedding table)."""
        return call_op("matmul", hidden, self.wte.weight, transpose_y=True)

    def serving_decoder(self):
        """What the fused paged serving path consumes
        (``models/decoder_spec.py``)."""
        return GPTServingDecoder(self)

    # -- static-cache decode path (serving) ---------------------------------
    def init_cache(self, batch, max_len, dtype):
        """Preallocate per-layer K/V buffers: tuple of (k, v) jnp arrays,
        each [B, max_len, num_heads, head_dim]."""
        import jax.numpy as jnp
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_attention_heads
        shape = (batch, max_len, cfg.num_attention_heads, hd)
        return tuple(
            (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(cfg.num_hidden_layers))

    def prefill(self, input_ids, caches, key_valid=None):
        """Run the prompt through all blocks, filling `caches` in place
        (functionally). key_valid: optional jnp bool [B, S] marking real
        (non-left-pad) prompt positions; position embeddings then count
        only real tokens per example. Returns (last-position hidden
        [B, 1, E], caches)."""
        import jax.numpy as jnp
        seq = input_ids.shape[1]
        if key_valid is None:
            position_ids = Tensor(jnp.arange(seq, dtype=jnp.int32)[None, :])
        else:
            # left-padded: pads get position 0, reals count 0,1,2,...
            position_ids = Tensor(jnp.maximum(
                jnp.cumsum(key_valid.astype(jnp.int32), axis=1) - 1, 0))
        x = self.wte(input_ids) + self.wpe(position_ids)
        new_caches = []
        for block, (ck, cv) in zip(self.blocks, caches):
            x, ck, cv = block.prefill(x, ck, cv, key_valid=key_valid)
            new_caches.append((ck, cv))
        x = self.ln_f(x)
        last = call_op("slice", x, axes=[1], starts=[seq - 1], ends=[seq])
        return last, tuple(new_caches)

    def decode_step(self, token_ids, caches, pos, key_valid=None,
                    positions=None):
        """One decode step: token_ids [B, 1], pos scalar (may be traced).
        positions: optional per-example LOGICAL positions [B, 1] (ragged
        prompts — the cache slot `pos` is shared but position embeddings
        differ per example). Returns (hidden [B, 1, E], caches)."""
        import jax.numpy as jnp
        if positions is None:
            pos_ids = Tensor(jnp.full((1, 1), pos, dtype=jnp.int32))
        else:
            pos_ids = Tensor(positions.astype(jnp.int32))
        x = self.wte(token_ids) + self.wpe(pos_ids)
        new_caches = []
        for block, (ck, cv) in zip(self.blocks, caches):
            x, ck, cv = block.decode_step(x, ck, cv, pos,
                                          key_valid=key_valid)
            new_caches.append((ck, cv))
        return self.ln_f(x), tuple(new_caches)


class _GPTServingLayer:
    """One ``GPTBlock`` behind the decoder spec's layer surface, its work
    under the spec's sections (``_qkv`` and ``_tail`` piece by piece: the
    training step, which calls those, is not sectioned)."""

    def __init__(self, block):
        self.block = block

    def attn_in(self, x, positions):
        import jax.numpy as jnp
        from . import decoder_spec as DS
        with DS.section(DS.NORM):
            h = self.block.ln_1(x)
        with DS.section(DS.QKV):
            q, k, v = self.block._heads(h)
            return jnp.transpose(q._data, (0, 2, 1, 3))[0], \
                (k._data[0], v._data[0])

    def attn_out(self, x, a, row_valid):
        import jax.numpy as jnp
        from . import decoder_spec as DS
        with DS.section(DS.O_PROJ):
            a = jnp.transpose(a[None], (0, 2, 1, 3))
            x = self.block._attn_residual(x, Tensor(a, stop_gradient=True))
        with DS.section(DS.NORM):
            h = self.block.ln_2(x)
        with DS.section(DS.MLP):
            return self.block._mlp_residual(x, h), None


class GPTServingDecoder:
    """GPT-2 as the fused serving stack sees it (``models/decoder_spec.py``):
    full attention over one K|V row a head, dense FFNs, learned positions
    added at the embedding, a head tied to it."""

    def __init__(self, gpt: "GPTModel"):
        from . import decoder_spec as DS
        cfg = gpt.cfg
        self.gpt = gpt
        dh = cfg.hidden_size // cfg.num_attention_heads
        cache = DS.CacheSpec(rows=cfg.num_attention_heads, lanes=2 * dh)
        self.spec = DS.DecoderSpec(
            layers=tuple(DS.LayerSpec(DS.FULL, cache, DS.DENSE)
                         for _ in gpt.blocks),
            vocab_size=cfg.vocab_size,
            max_positions=cfg.max_position_embeddings)
        self.layers = [_GPTServingLayer(b) for b in gpt.blocks]

    def embed_tokens(self, token_ids, positions):
        return self.gpt.wte(Tensor(token_ids[None, :], stop_gradient=True)) \
            + self.gpt.wpe(Tensor(positions[None, :]))

    def final_norm(self, x):
        return self.gpt.ln_f(x)

    def logits(self, hidden):
        return self.gpt.logits(hidden)


def _chunked_lm_loss(hidden, labels, table, n_chunks):
    """Tied-head softmax cross-entropy WITHOUT materializing the full
    [B, S, V] logits tensor: lax.scan over sequence chunks, each chunk
    rematerialized in backward (jax.checkpoint), so peak memory is one
    [B, S/n, V] block instead of the whole thing. At GPT-2 scale
    (b8 x s1024 x v50304) the full tensor is 1.6 GB fp32 — the classic
    HBM squeeze on small-model-large-vocab training. Reference analog:
    the fused softmax-with-cross-entropy kernels
    (paddle/phi/kernels/softmax_with_cross_entropy* and
    fused c_softmax_with_cross_entropy), which exist for the same
    memory/bandwidth reason."""
    import jax
    import jax.numpy as jnp

    B, S, H = hidden.shape
    C = S // n_chunks
    hs = jnp.moveaxis(hidden.reshape(B, n_chunks, C, H), 1, 0)
    ys = jnp.moveaxis(labels.reshape(B, n_chunks, C), 1, 0)

    @jax.checkpoint
    def chunk_nll(h_c, y_c):
        logits = jnp.einsum("bch,vh->bcv", h_c, table,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = y_c != -100                     # ignore_index convention
        safe = jnp.where(valid, y_c, 0).astype(jnp.int32)
        gold = jnp.take_along_axis(logits, safe[..., None],
                                   axis=-1)[..., 0]
        nll = jnp.where(valid, lse - gold, 0.0)
        return nll.sum(), valid.sum().astype(jnp.int32)

    def body(acc, xs):
        h_c, y_c = xs
        nll, n = chunk_nll(h_c, y_c)
        return (acc[0] + nll, acc[1] + n), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (hs, ys))
    return total / jnp.maximum(count, 1).astype(jnp.float32)


class GPTForPretraining(nn.Layer):
    """GPT with the tied-embedding LM head and causal-LM loss.

    Return contract of ``forward``:

    * ``labels is None`` — the logits Tensor ``[B, S, V]``;
    * ``labels`` given, ``lm_loss_chunks == 1`` — ``(loss, logits)``;
    * ``labels`` given, ``lm_loss_chunks > 1`` — ``(loss, None)``: the
      chunked cross-entropy (``_chunked_lm_loss``) exists precisely to
      never materialize the ``[B, S, V]`` logits tensor (1.6 GB fp32 at
      GPT-2 124M scale), so there are no logits to return. Callers that
      need logits must either use ``lm_loss_chunks=1`` or call
      ``self.gpt.logits(hidden)`` themselves and pay the memory.

    ``S`` must be divisible by ``lm_loss_chunks``; a silent dense
    fallback would defeat the memory bound, so an indivisible length
    raises instead.
    """

    def __init__(self, cfg: GPTConfig, lm_loss_chunks: int = 1):
        super().__init__()
        self.gpt = GPTModel(cfg)
        if lm_loss_chunks < 1:
            raise ValueError(f"lm_loss_chunks must be >= 1, "
                             f"got {lm_loss_chunks}")
        self.lm_loss_chunks = int(lm_loss_chunks)

    def forward(self, input_ids, labels=None, position_ids=None):
        hidden = self.gpt(input_ids, position_ids)
        if labels is None:
            return self.gpt.logits(hidden)
        if self.lm_loss_chunks > 1:
            if hidden.shape[1] % self.lm_loss_chunks:
                # a silent dense fallback would re-materialize the very
                # [B, S, V] tensor this flag exists to avoid (and flip
                # the logits output between None and real) — refuse
                raise ValueError(
                    f"sequence length {hidden.shape[1]} is not divisible "
                    f"by lm_loss_chunks={self.lm_loss_chunks}")
            from ..autograd import differentiable_apply
            loss = differentiable_apply(
                lambda h, y, w: _chunked_lm_loss(h, y, w,
                                                 self.lm_loss_chunks),
                hidden, labels, self.gpt.wte.weight)
            return loss, None
        logits = self.gpt.logits(hidden)
        loss = F.cross_entropy(
            call_op("reshape", logits, shape=(-1, logits.shape[-1])),
            call_op("reshape", labels, shape=(-1,)),
            reduction="mean")
        return loss, logits

    def serving_decoder(self):
        return self.gpt.serving_decoder()

    def generate(self, input_ids, **kwargs):
        """Compiled static-cache autoregressive decode; see
        models.generation.generate."""
        from .generation import generate
        return generate(self, input_ids, **kwargs)
