"""Autoregressive generation, compiled once — the TPU serving decode path.

Design (TPU-first):
- The whole generate loop (prefill + ``lax.while_loop`` over decode steps)
  is ONE jitted XLA program. The KV cache is preallocated at
  ``[B, prompt+max_new, H, D]`` per layer and written with
  ``dynamic_update_slice`` — shapes never change, so there is exactly one
  compile per (batch, prompt_len, max_new, sampling-mode) class.
  Temperature is a traced scalar: changing it never recompiles.
- Early exit: the while_loop condition stops as soon as every sequence
  has emitted EOS — unlike a fixed-length scan, short answers don't pay
  for the full budget.
- Sampling (greedy / temperature / top-k / top-p) runs on-device with
  ``jax.random.categorical``; no host round-trip per token.

Reference analog: the reference serves decoder LMs through
fused_multi_transformer's fixed-capacity CacheKV
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu:1) driven by
a Python sampling loop; here the loop itself is compiled.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

from ..framework.tensor import Tensor, no_grad_guard

__all__ = ["GenerationConfig", "generate", "save_for_serving",
           "shard_params_megatron", "megatron_param_specs",
           "build_fused_step_fn", "build_sharded_fused_step_fn",
           "build_draft_prefill_fn", "build_draft_propose_scan_fn",
           "build_spec_verify_fn", "make_draft_model"]


def shard_params_megatron(model, mesh, mp_axis="mp"):
    """Place the model's parameters in the Megatron tensor-parallel
    layout over ``mesh``: attention q/k/v and MLP-in column-sharded on
    the output dim, out-proj/MLP-out row-sharded on the input dim
    (weights are [in, out]), everything else replicated. One shared
    policy for the sharded-decode tests and the multichip dryrun."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    col = NamedSharding(mesh, P(None, mp_axis))
    row = NamedSharding(mesh, P(mp_axis, None))
    rep = NamedSharding(mesh, P())
    for name, p in model.named_parameters():
        if p._data.ndim == 2 and any(k in name for k in (
                "q_proj.weight", "k_proj.weight", "v_proj.weight",
                "mlp_fc.weight")):
            sh = col
        elif p._data.ndim == 2 and any(k in name for k in (
                "out_proj.weight", "mlp_proj.weight")):
            sh = row
        else:
            sh = rep
        p._data = jax.device_put(p._data, sh)


def megatron_param_specs(model, mp_axis="mp"):
    """The flat ``{param_name: PartitionSpec}`` dict matching
    :func:`shard_params_megatron`'s placement, keyed like
    ``get_params_tree`` — the params entry of a ``shard_map``'s
    ``in_specs`` over the tensor-parallel serving steps. Column-parallel
    weights split their OUTPUT dim, row-parallel weights their INPUT dim
    (weights are [in, out]); everything else (biases, LayerNorms,
    embeddings, the tied LM head) is replicated."""
    from jax.sharding import PartitionSpec as P

    specs = {}
    for name, p in model.named_parameters():
        if p._data.ndim == 2 and any(k in name for k in (
                "q_proj.weight", "k_proj.weight", "v_proj.weight",
                "mlp_fc.weight")):
            specs[name] = P(None, mp_axis)
        elif p._data.ndim == 2 and any(k in name for k in (
                "out_proj.weight", "mlp_proj.weight")):
            specs[name] = P(mp_axis, None)
        else:
            specs[name] = P()
    return specs


def save_for_serving(model, path, batch, prompt_len, runtime_key=False,
                     **generate_kwargs):
    """Export the COMPILED generate loop as an inference artifact: one
    StableHLO program (prefill + while_loop decode + sampling, weights
    baked in) serving ``ids [batch, prompt_len] -> tokens``. Loadable by
    jit.load / inference.create_predictor — including from C via the
    PDT_* API — with no Python model code at serve time. Sampling
    strategy and budgets are FROZEN into the artifact (pass them here);
    shapes are fixed to the serving shape class, the same contract as
    the BatchingEngine's pow2 buckets. Reference analog: exporting
    fused_multi_transformer inference programs for analysis_predictor
    (paddle/fluid/inference/api/analysis_predictor.cc:1).

    Sampling: with ``runtime_key=True`` the PRNG key is a RUNTIME INPUT
    of the artifact — it serves ``(ids [batch, prompt_len] int32,
    key [2] uint32) -> tokens``, so the caller draws per request and
    two calls on the same prompt can differ (the reference's serving
    loop draws per request; this was the standing per-request-sampling
    gap). Requires ``do_sample=True`` and no ``seed`` (the seed IS the
    runtime key now).

    Without ``runtime_key`` the key is a trace CONSTANT in the
    artifact, so a sampled export returns the same tokens for a given
    prompt on every call — sampling picks a fixed draw per artifact,
    it does not re-randomize per request. That is only sane when the
    caller chose the draw, so an unseeded ``do_sample=True`` export is
    rejected (pass ``runtime_key=True`` for per-request draws)."""
    import jax.numpy as jnp

    from .. import jit
    from ..nn.layer.layers import get_buffers_tree
    from ..static import InputSpec

    if runtime_key:
        unknown = sorted(set(generate_kwargs) - set(_GEN_DEFAULTS))
        if unknown:
            raise ValueError(
                f"runtime_key export got unsupported kwargs: {unknown}")
        resolved = dict(_GEN_DEFAULTS)
        resolved.update(generate_kwargs)
        if not resolved["do_sample"]:
            raise ValueError(
                "runtime_key=True requires do_sample=True: a greedy "
                "export never consumes the key, so a key input would "
                "be dead weight in the artifact's signature")
        if resolved["seed"] is not None:
            raise ValueError(
                "runtime_key=True replaces seed=: the key arrives per "
                "call at serve time (jax.random.PRNGKey(seed) makes "
                "one)")
        if resolved["num_beams"] != 1:
            raise ValueError("runtime_key=True requires num_beams=1 "
                             "(beam search is deterministic)")
        static_key = (
            int(resolved["max_new_tokens"]), True,
            int(resolved["top_k"]), float(resolved["top_p"]),
            None if resolved["eos_token_id"] is None
            else int(resolved["eos_token_id"]),
            int(resolved["pad_token_id"]), False)
        fn = _build_generate_fn(model, int(batch), int(prompt_len),
                                static_key)
        was_training = model.training
        model.eval()
        try:
            params = {k: p._data for k, p in model.named_parameters()}
            buffers = get_buffers_tree(model)
            temp = float(resolved["temperature"])

            def _serve_keyed(ids, key):
                # jit.save hands Tensors: jax.random takes a key's data
                # only as an array (a Tensor's shape is a list)
                return fn(params, buffers, ids._data, key._data,
                          jnp.float32(temp), jnp.int32(0))

            return jit.save(
                _serve_keyed, path,
                input_spec=[InputSpec([int(batch), int(prompt_len)],
                                      "int32"),
                            InputSpec([2], "uint32")])
        finally:
            if was_training:
                model.train()

    if generate_kwargs.get("do_sample") and \
            generate_kwargs.get("seed") is None:
        raise ValueError(
            "save_for_serving(do_sample=True) requires an explicit seed "
            "(or runtime_key=True for per-request draws): the key is "
            "baked into the artifact as a constant, so the export "
            "freezes ONE draw per prompt — make that choice explicit "
            "(and avoid silently advancing the global RNG at export "
            "time)")

    def _serve(ids):
        return generate(model, ids, **generate_kwargs)

    return jit.save(_serve, path,
                    input_spec=[InputSpec([int(batch), int(prompt_len)],
                                          "int32")])


@dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0            # 0 = disabled
    top_p: float = 1.0        # 1.0 = disabled
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    seed: Optional[int] = None
    num_beams: int = 1        # >1 = deterministic beam search
    length_penalty: float = 0.0   # GNMT ((5+len)/6)^alpha; 0 = off


def _filter_logits(logits, top_k, top_p, temperature):
    """The sampling truncation shared by :func:`_pick_token` and
    :func:`_sample_probs`: temperature scaling, then static top-k /
    top-p masking to ``-inf``. Works over any leading batch shape
    (``[..., V]``)."""
    import jax
    import jax.numpy as jnp
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sort_idx = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum_excl = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = cum_excl < top_p          # always keeps the top-1
        inv = jnp.argsort(sort_idx, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def _pick_token(logits, key, do_sample, top_k, top_p, temperature):
    """logits: jnp [B, V] f32 -> jnp [B] int32. top_k/top_p are static
    (part of the compile key); temperature is traced."""
    import jax
    import jax.numpy as jnp
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, _filter_logits(logits, top_k, top_p, temperature),
        axis=-1).astype(jnp.int32)


def _sample_probs(logits, sample_mask, top_k, top_p, temperature):
    """The per-row SAMPLING DISTRIBUTION as explicit probabilities
    ``[N, V]`` f32 — what speculative decoding's rejection sampling
    needs on both sides of the accept ratio. Sampled rows get the
    softmax of the ``_filter_logits`` truncation (the distribution
    ``categorical(filtered_logits)`` draws from — categorical is
    shift-invariant, so the two agree exactly); greedy rows get the
    DEGENERATE one-hot at the argmax, which makes greedy speculative
    acceptance collapse to token equality with exact parity.

    ``sample_mask [N]`` bool and ``temperature [N]`` are traced."""
    import jax
    import jax.numpy as jnp
    v = logits.shape[-1]
    onehot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), v,
                            dtype=jnp.float32)
    soft = jax.nn.softmax(
        _filter_logits(logits, top_k, top_p, temperature[..., None]),
        axis=-1)
    return jnp.where(sample_mask[..., None], soft, onehot)


def _categorical_probs(key, probs):
    """Draw per-row tokens from explicit probabilities ``[..., V]``
    (zero-probability entries are exactly ``-inf`` in log space, so a
    one-hot distribution picks its token DETERMINISTICALLY — the greedy
    degenerate case of the speculative sampler)."""
    import jax
    import jax.numpy as jnp
    logp = jnp.where(probs > 0, jnp.log(jnp.maximum(probs, 1e-38)),
                     -jnp.inf)
    return jax.random.categorical(key, logp, axis=-1).astype(jnp.int32)


def _spec_accept(p_probs, q_probs, drafts, n_spec, base_probs, key):
    """Device-side speculative rejection sampling (one decode cycle).

    Per slot ``s``, the draft proposed ``drafts[s, :n_spec[s]]`` and
    the verify launch produced the target's sampling distribution
    ``p_probs[s, j]`` at each candidate row ``j`` (the row that FED
    candidate ``j``'s predecessor); ``q_probs[s, j]`` is the draft's
    proposal distribution for that candidate. Standard rejection
    sampling: candidate ``d`` is accepted while ``u * q(d) < p(d)``
    (strict, with ``u ~ U[0, 1)``); the first rejected position emits a
    token from the residual ``max(p - q, 0)`` renormalized. Greedy rows
    carry one-hot distributions, collapsing all of this to exact
    argmax-equality acceptance and argmax correction — the degenerate
    case with EXACT parity to the non-speculative engine.

    ``base_probs [S, V]`` is each slot's last-row distribution, drawn
    for slots that verified nothing this launch (``n_spec == 0``: a
    prefill chunk finishing its feed emits its first token from it).

    Returns ``(accepted [S] int32, token [S] int32)`` — ``token`` is
    the corrected/residual draw when ``accepted < n_spec``, the base
    draw when ``n_spec == 0``, and unused garbage when every candidate
    was accepted (the scheduler emits the accepted drafts instead).
    """
    import jax
    import jax.numpy as jnp
    s_, k_, _v = p_probs.shape
    ku, kr, kb = jax.random.split(key, 3)
    u = jax.random.uniform(ku, (s_, k_))
    pd = jnp.take_along_axis(p_probs, drafts[..., None], axis=-1)[..., 0]
    qd = jnp.take_along_axis(q_probs, drafts[..., None], axis=-1)[..., 0]
    valid = jnp.arange(k_)[None, :] < n_spec[:, None]
    acc = valid & (u * qd < pd)
    accepted = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                       axis=1)                  # leading-accept count
    ridx = jnp.minimum(accepted, k_ - 1)
    pr = jnp.take_along_axis(p_probs, ridx[:, None, None], axis=1)[:, 0]
    qr = jnp.take_along_axis(q_probs, ridx[:, None, None], axis=1)[:, 0]
    res = jnp.maximum(pr - qr, 0.0)
    rsum = jnp.sum(res, axis=-1, keepdims=True)
    # a rejection implies p != q somewhere, so the residual has mass;
    # the p fallback only guards numerically-identical distributions
    res = jnp.where(rsum > 0, res / jnp.maximum(rsum, 1e-38), pr)
    rejected = accepted < n_spec
    token = jnp.where(rejected & (n_spec > 0),
                      _categorical_probs(kr, res),
                      _categorical_probs(kb, base_probs))
    return accepted, token


def _mask_preamble(attn_mask, batch, max_new):
    """(key_valid [B, total_len] bool over the prompt, real_len [B, 1])
    for a left-padded prompt mask — shared by the greedy/sampling and
    beam builders so the left-pad invariant lives in one place."""
    import jax.numpy as jnp
    key_valid = jnp.concatenate(
        [attn_mask.astype(bool), jnp.zeros((batch, max_new), bool)], axis=1)
    real_len = attn_mask.astype(jnp.int32).sum(axis=1, keepdims=True)
    return key_valid, real_len


def _step_mask(key_valid, real_len, prompt_len, total_len, pos, tile=1):
    """Per-decode-step key validity (prompt mask | generated slots up to
    pos) and per-example logical positions; tile>1 repeats rows for
    flattened beams."""
    import jax.numpy as jnp
    r = jnp.arange(total_len)
    kv = key_valid | ((r >= prompt_len) & (r <= pos))[None, :]
    positions = real_len + (pos - prompt_len)
    if tile > 1:
        kv = jnp.repeat(kv, tile, axis=0)
        positions = jnp.repeat(positions, tile, axis=0)
    return kv, positions


def _build_generate_fn(model, batch, prompt_len, static_key):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..nn.layer.layers import functional_state

    (max_new, do_sample, top_k, top_p, eos, pad, has_mask) = static_key
    gpt = model.gpt if hasattr(model, "gpt") else model
    if max_new < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
    if not 0.0 < top_p <= 1.0:
        # top_p=0 would mask EVERY logit to -inf and categorical would
        # silently emit token 0 each step
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    top_k = min(top_k, gpt.cfg.vocab_size)  # lax.top_k caps at vocab
    total_len = prompt_len + max_new
    if total_len > gpt.cfg.max_position_embeddings:
        raise ValueError(
            f"prompt_len+max_new_tokens={total_len} exceeds "
            f"max_position_embeddings={gpt.cfg.max_position_embeddings}")

    def fn(params, buffers, ids, key, temperature, attn_mask):
        with functional_state(model, params, buffers):
            with no_grad_guard():
                dtype = params[next(iter(params))].dtype
                z = jnp.int32(0)
                caches = gpt.init_cache(batch, total_len, dtype)
                if has_mask:
                    # ragged (left-padded) prompts: pads are masked out of
                    # attention forever; logical positions count only real
                    # tokens, so each example decodes at real_len + t
                    key_valid, real_len = _mask_preamble(
                        attn_mask, batch, max_new)
                else:
                    key_valid, real_len = None, None
                hidden, caches = gpt.prefill(
                    Tensor(ids, stop_gradient=True), caches,
                    key_valid=None if key_valid is None
                    else key_valid[:, :prompt_len])
                logits = gpt.logits(hidden)._data[:, 0].astype(jnp.float32)
                key, sub = jax.random.split(key)
                first = _pick_token(logits, sub, do_sample, top_k, top_p,
                                    temperature)
                finished = ((first == eos) if eos is not None
                            else jnp.zeros((batch,), bool))
                tokens = jnp.concatenate(
                    [ids.astype(jnp.int32),
                     jnp.full((batch, max_new), pad, jnp.int32)], axis=1)
                tokens = lax.dynamic_update_slice(
                    tokens, first[:, None], (z, jnp.int32(prompt_len)))

                def cond(state):
                    tokens, caches, pos, finished, key = state
                    return (pos < total_len - 1) & ~jnp.all(finished)

                def body(state):
                    tokens, caches, pos, finished, key = state
                    tok = lax.dynamic_slice(tokens, (z, pos), (batch, 1))
                    if has_mask:
                        kv, positions = _step_mask(
                            key_valid, real_len, prompt_len, total_len,
                            pos)
                    else:
                        kv, positions = None, None
                    hidden, caches = gpt.decode_step(
                        Tensor(tok, stop_gradient=True), caches, pos,
                        key_valid=kv, positions=positions)
                    logits = gpt.logits(hidden)._data[:, 0].astype(
                        jnp.float32)
                    key, sub = jax.random.split(key)
                    nxt = _pick_token(logits, sub, do_sample, top_k, top_p,
                                      temperature)
                    if eos is not None:
                        nxt = jnp.where(finished, pad, nxt)
                        finished = finished | (nxt == eos)
                    tokens = lax.dynamic_update_slice(
                        tokens, nxt[:, None], (z, pos + 1))
                    return tokens, caches, pos + 1, finished, key

                state = (tokens, caches, jnp.int32(prompt_len), finished,
                         key)
                tokens = lax.while_loop(cond, body, state)[0]
        return tokens

    return jax.jit(fn)


def _build_beam_fn(model, batch, prompt_len, static_key):
    """Batched beam search, compiled: beams live as a flattened [B*K]
    batch so the SAME decode_step program serves both strategies; each
    step reorders the KV cache by beam parent with one gather. Finished
    beams stay in the pool with frozen scores (only the pad continuation
    is allowed, at logprob 0). Reference analog:
    python/paddle/nn/decode.py BeamSearchDecoder semantics (tile_beam /
    gather_tree), rebuilt as one XLA program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..nn.layer.layers import functional_state

    (max_new, num_beams, eos, pad, length_penalty, has_mask) = static_key
    gpt = model.gpt if hasattr(model, "gpt") else model
    K = num_beams
    vocab = gpt.cfg.vocab_size
    if max_new < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
    if not 2 <= K <= vocab:
        raise ValueError(f"num_beams must be in [2, vocab], got {K}")
    total_len = prompt_len + max_new
    if total_len > gpt.cfg.max_position_embeddings:
        raise ValueError(
            f"prompt_len+max_new_tokens={total_len} exceeds "
            f"max_position_embeddings={gpt.cfg.max_position_embeddings}")

    def lp(length):
        # GNMT length penalty ((5+len)/6)^alpha; alpha=0 -> pure logprob
        if length_penalty == 0.0:
            return jnp.ones_like(length, jnp.float32)
        return ((5.0 + length.astype(jnp.float32)) / 6.0) ** length_penalty

    def fn(params, buffers, ids, attn_mask):
        with functional_state(model, params, buffers):
            with no_grad_guard():
                dtype = params[next(iter(params))].dtype
                z = jnp.int32(0)
                if has_mask:
                    key_valid, real_len = _mask_preamble(
                        attn_mask, batch, max_new)
                else:
                    key_valid, real_len = None, None
                # prefill once at [B], then tile the caches to [B*K]
                caches = gpt.init_cache(batch, total_len, dtype)
                hidden, caches = gpt.prefill(
                    Tensor(ids, stop_gradient=True), caches,
                    key_valid=None if key_valid is None
                    else key_valid[:, :prompt_len])
                logp0 = jax.nn.log_softmax(
                    gpt.logits(hidden)._data[:, 0].astype(jnp.float32))
                scores, first = lax.top_k(logp0, K)        # [B, K]
                first = first.astype(jnp.int32)
                caches = tuple(
                    (jnp.repeat(ck, K, axis=0), jnp.repeat(cv, K, axis=0))
                    for ck, cv in caches)
                tokens = jnp.concatenate(
                    [ids.astype(jnp.int32),
                     jnp.full((batch, max_new), pad, jnp.int32)], axis=1)
                tokens = jnp.repeat(tokens[:, None, :], K, axis=1)
                tokens = lax.dynamic_update_slice(
                    tokens, first[:, :, None], (z, z, jnp.int32(prompt_len)))
                finished = (first == eos) if eos is not None else \
                    jnp.zeros((batch, K), bool)
                gen_len = jnp.ones((batch, K), jnp.int32)
                # one-hot pad row at -inf elsewhere: the only allowed
                # continuation of a finished beam, contributing logprob 0
                pad_row = jnp.where(jnp.arange(vocab) == pad, 0.0,
                                    -jnp.inf)[None, None, :]
                barange = jnp.arange(batch, dtype=jnp.int32)[:, None] * K

                def cond(state):
                    tokens, caches, scores, finished, gen_len, pos = state
                    return (pos < total_len - 1) & ~jnp.all(finished)

                def body(state):
                    tokens, caches, scores, finished, gen_len, pos = state
                    tok = lax.dynamic_slice(
                        tokens, (z, z, pos), (batch, K, 1)).reshape(
                            batch * K, 1)
                    if has_mask:
                        kv, positions = _step_mask(
                            key_valid, real_len, prompt_len, total_len,
                            pos, tile=K)
                    else:
                        kv, positions = None, None
                    hidden, caches = gpt.decode_step(
                        Tensor(tok, stop_gradient=True), caches, pos,
                        key_valid=kv, positions=positions)
                    logp = jax.nn.log_softmax(
                        gpt.logits(hidden)._data[:, 0].astype(jnp.float32)
                    ).reshape(batch, K, vocab)
                    allowed = jnp.where(finished[:, :, None], pad_row, logp)
                    cand = (scores[:, :, None] + allowed).reshape(
                        batch, K * vocab)
                    scores, idx = lax.top_k(cand, K)       # [B, K]
                    parent = (idx // vocab).astype(jnp.int32)
                    nxt = (idx % vocab).astype(jnp.int32)
                    # reorder beam state by parent
                    tokens = jnp.take_along_axis(
                        tokens, parent[:, :, None], axis=1)
                    finished = jnp.take_along_axis(finished, parent, axis=1)
                    gen_len = jnp.take_along_axis(gen_len, parent, axis=1)
                    fp = (barange + parent).reshape(-1)
                    caches = tuple((ck[fp], cv[fp]) for ck, cv in caches)
                    tokens = lax.dynamic_update_slice(
                        tokens, nxt[:, :, None], (z, z, pos + 1))
                    gen_len = gen_len + (~finished).astype(jnp.int32)
                    if eos is not None:
                        finished = finished | (nxt == eos)
                    return tokens, caches, scores, finished, gen_len, pos + 1

                state = (tokens, caches, scores, finished, gen_len,
                         jnp.int32(prompt_len))
                tokens, _, scores, _, gen_len, _ = lax.while_loop(
                    cond, body, state)
                best = jnp.argmax(scores / lp(gen_len), axis=1)   # [B]
                out = jnp.take_along_axis(
                    tokens, best[:, None, None], axis=1)[:, 0]
        return out

    return jax.jit(fn)


# ---------------------------------------------------------------------------
# the serving steps (consumed by paddle_tpu/serving/engine.py)
# ---------------------------------------------------------------------------

def _append_nonfinite_flag(nxt, logits):
    """Append the per-cycle logits-finite sentinel to the fused step's
    token row: element ``[num_slots]`` is 1 when ANY logit this cycle is
    NaN/Inf, else 0. It rides the scheduler's existing one-per-cycle
    ``_fetch`` (the token indexing ``toks[slot]`` never reaches it), so
    the serving twin of the training numerics audit costs zero extra
    host syncs — the scheduler counts it into
    ``serving/nonfinite_cycles`` and the flight-recorder cycle record."""
    import jax.numpy as jnp
    bad = jnp.any(~jnp.isfinite(logits)).astype(jnp.int32)
    return jnp.concatenate([nxt, bad[None]])


# ---------------------------------------------------------------------------
# the paged block pool's row layout: [L, NB + 1, H, bs, 2 * Dh] — K in
# lanes [0, Dh), V in lanes [Dh, 2 * Dh) of one (bs, 2 * Dh) tile per
# (block, head), so the tile is 128 lanes wide at Dh = 64 (what the fused
# kernel's DMA needs, ops/ragged_paged_attention.py). A model whose K
# heads are wider than its V heads states both in its cache descriptor
# and stores K | zeros | V in whole tiles (_kv_lanes); a model of window
# and global layers has one such array A CACHE GROUP, L then being the
# group's layers (models/decoder_spec.py, serving/paging.py). Quantized pools
# (PagedKVPool(dtype="int8")) keep per-block max-abs scales in a parallel
# [L, 2, NB + 1, H] f32 array (plane 0 = K, 1 = V) — the EQuARX per-chunk
# scheme of the PR-10 gradient wire, applied to KV storage.
#
# Who appends a token's rows how (PR 30):
# * the fused towers (_fused_tower: the fused step and the speculative
#   verify step; _mp_fused_tower: its tensor-parallel twin), full-attention
#   layers, unquantized pool -> the Pallas kernel ops/kv_append.py: only
#   real rows, a token's block for all heads in one DMA each way;
# * quantized pools -> _quant_append (a block requantize, then _write_rows,
#   the XLA scatter — which is also the reference ops/kv_append.py is
#   tested against, bit for bit: tests/test_kv_append.py);
#   latent pools -> _write_latent_rows (one row a token: ~0.5 ms a launch
#   as a scatter, PERF.md finding 29.2). Their needs differ; nothing is
#   shared by force.
# ---------------------------------------------------------------------------

def _kv_lanes(k, v, lanes=0):
    """K|V folded into the lanes: two ``[..., Dh]`` arrays -> one
    ``[..., 2 * Dh]`` pool row. A cache descriptor whose stored row is
    wider than K and V together (``lanes``: K 192 | V 128 in 384) gets
    zeros between them — K first, V last."""
    import jax.numpy as jnp
    gap = int(lanes) - k.shape[-1] - v.shape[-1]
    if gap > 0:
        return jnp.concatenate(
            [k, jnp.zeros(k.shape[:-1] + (gap,), k.dtype), v], axis=-1)
    return jnp.concatenate([k, v], axis=-1)


def _write_rows(pool, li, wb, off, k_rows, v_rows):
    """Write one K|V row per (token, head) into layer ``li`` of the
    pool: ``k_rows``/``v_rows [N, H, Dh]`` land at ``(block wb[n], head
    h, offset off[n])``.

    The quantized append's last step, and the reference of
    ``ops/kv_append.py``, which the fused towers write through (the
    comment above ``_kv_lanes``). As a scatter this is ``N x H`` updates of one row,
    which XLA's TPU scatter walks one by one, pad rows included (0.86 ms
    a layer at 512 rows x 20 heads, PERF.md PR 30).

    Every axis but the lanes is INDEXED (the head axis by an explicit
    ``arange``), so the scatter's window is one 128-lane row — the
    minor-most dim. Written as ``pool.at[li, wb, :, off, :]``, window
    ``(H, 2 * Dh)`` around the offset axis, XLA's TPU layout assignment
    moves the head axis next to the lanes, and every layer then pays a
    relayout COPY of the whole pool on the way into the attention
    kernel, which needs the default layout (seen compiling the step for
    a v5e: a second pool-sized HBM buffer)."""
    import jax.numpy as jnp
    H = pool.shape[2]
    rows = _kv_lanes(k_rows, v_rows).astype(pool.dtype)       # [N,H,2Dh]
    heads = jnp.arange(H, dtype=jnp.int32)[None, :]
    return pool.at[li, wb[:, None], heads, off[:, None], :].set(rows)


def _scale_lanes(sc, dh):
    """Per-plane scales ``[2, ..., H]`` -> the lane-wise multiplier
    ``[..., H, 2 * Dh]`` of a pool row (K scale over the K lanes, V
    scale over the V lanes)."""
    import jax.numpy as jnp
    return jnp.repeat(jnp.moveaxis(sc, 0, -1), dh, axis=-1)


def _quant_append(pool, scales, li, wb, off, k_rows, v_rows, qmax):
    """Scatter per-row K/V values into a QUANTIZED block pool.

    ``k_rows``/``v_rows [N, H, Dh]`` land at ``(block wb[n], offset
    off[n])`` of layer ``li``. Per-block max-abs scales grow
    monotonically: a row whose magnitude exceeds its block's current
    scale bumps the scale (scatter-max) and the touched blocks are
    REQUANTIZED to the new scale in the same step — when the scale is
    unchanged the requantize ratio is exactly 1.0, so steady-state
    appends never erode earlier rows. Duplicate ``wb`` entries (a
    prefill chunk writing several offsets of one block, or pad rows
    aimed at the scratch block) are safe: the scatter-max makes every
    duplicate see the same old/new scales, so their requantized block
    bytes are identical, and the row offsets are distinct by
    construction. Returns ``(pool, scales)``."""
    import jax.numpy as jnp
    rows = jnp.stack([k_rows, v_rows]).astype(jnp.float32)  # [2,N,H,Dh]
    dh = rows.shape[-1]
    rmax = jnp.max(jnp.abs(rows), axis=-1) / qmax             # [2, N, H]
    old = scales[li]                                          # [2,NB+1,H]
    new = old.at[:, wb].max(rmax)
    new_wb = new[:, wb]                                       # [2, N, H]
    nb = jnp.maximum(new_wb, 1e-30)
    ratio = jnp.where(new_wb > 0, old[:, wb] / nb, 1.0)
    blk = pool[li, wb].astype(jnp.float32)                # [N,H,bs,2*Dh]
    requant = jnp.clip(
        jnp.round(blk * _scale_lanes(ratio, dh)[..., None, :]),
        -qmax, qmax).astype(pool.dtype)
    pool = pool.at[li, wb].set(requant)
    qrow = jnp.clip(jnp.round(jnp.where(new_wb[..., None] > 0,
                                        rows / nb[..., None], 0.0)),
                    -qmax, qmax).astype(pool.dtype)
    pool = _write_rows(pool, li, wb, off, qrow[0], qrow[1])
    return pool, scales.at[li].set(new)


def _write_latent_rows(pool, li, wb, off, rows):
    """Write ONE latent row a token into layer ``li`` of a latent pool
    ``[L, NB + 1, 1, bs, lanes]``: ``rows [N, lanes]`` land at ``(block
    wb[n], offset off[n])``. Every axis but the lanes is indexed, as in
    :func:`_write_rows` and for its reason."""
    return pool.at[li, wb, 0, off, :].set(rows.astype(pool.dtype))


class _RowAxes(NamedTuple):
    """The two row axes of a step whose tower runs on fewer rows than
    its attention kernel (:func:`_row_axes`). ``R`` tower rows, ``Q``
    kernel rows, ``S`` slots."""
    start: object        # [S] int32: the sequence's first TOWER row
    row_seq: object      # [R] int32: the tower row's slot; S for none
    to_kernel: object    # [Q] int32: the kernel row's tower row; R: a pad
    from_kernel: object  # [R] int32: the tower row's kernel row


def _row_axes(rows: int, blk_seq, seq_qstart, seq_pos0, kv_len):
    """The launch's TOWER axis against its KERNEL axis, once a launch,
    on the device, from the kernel's own scalar metadata — or ``None``
    where the two are one (``rows`` is the kernel's ``Q``: nothing is
    traced, and the step is the program it was before there were two).

    The kernel wants each sequence's rows padded to whole q blocks
    (``ops/ragged_paged_attention.py``, Layout contract); nothing else in
    a step does. So a step's per-row operands and everything its tower
    computes run on ``rows`` = ``R <= Q`` rows that hold the same
    sequences in the same slot order back to back (sequence ``s`` has
    ``kv_len[s] - seq_pos0[s]`` real rows this launch, so it starts at
    the sum of those before it: no operand says so), pad rows only at the
    end. ``to_kernel`` lays a layer's query rows out for the kernel (a
    row of no sequence reads past the end and is filled with zeros),
    ``from_kernel`` reads the kernel's output back at the real rows (a
    tower pad row reads kernel row 0: finite, and nobody's)."""
    import jax.numpy as jnp

    from ..ops.ragged_paged_attention import BLOCK_Q
    R, S = int(rows), seq_qstart.shape[0]
    Q = blk_seq.shape[0] * BLOCK_Q
    if R == Q:
        return None
    if R > Q:
        raise ValueError(
            f"{R} tower rows for a kernel of {Q}: the tower runs on at "
            f"most the kernel's rows")
    i32 = jnp.int32
    qstart = seq_qstart.astype(i32)
    seq_len = (kv_len - seq_pos0).astype(i32)
    ends = jnp.cumsum(seq_len).astype(i32)
    start = ends - seq_len
    r = jnp.arange(R, dtype=i32)
    # the first sequence that ends after the row: absent ones end where
    # they start and are passed over
    row_seq = jnp.sum(ends[None, :] <= r[:, None], axis=1).astype(i32)
    rs = jnp.minimum(row_seq, S - 1)
    from_kernel = jnp.where(row_seq < S, qstart[rs] + r - start[rs], 0)
    kseq = jnp.repeat(blk_seq.astype(i32), BLOCK_Q)
    ks = jnp.maximum(kseq, 0)
    off = jnp.arange(Q, dtype=i32) - qstart[ks]
    to_kernel = jnp.where((kseq >= 0) & (off < seq_len[ks]),
                          start[ks] + off, R)
    return _RowAxes(start, row_seq, to_kernel, from_kernel)


def _at_rows(v, axes, axis: int, to_kernel: bool = False):
    """``v`` moved between a step's two row axes along ``axis``: the
    tower's rows laid out for the kernel (``to_kernel``: pad rows zero)
    or the kernel's output read back at the tower's. ``axes`` ``None``:
    one axis, ``v`` as it is."""
    import jax.numpy as jnp
    if axes is None:
        return v
    if to_kernel:
        return jnp.take(v, axes.to_kernel, axis=axis, mode="fill",
                        fill_value=0)
    return jnp.take(v, axes.from_kernel, axis=axis, mode="clip")


def _fused_tower(dec, x, positions, pool, scales, write_block, write_off,
                 blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len,
                 quantized, qmax, state=None, axes=None):
    """The fused ragged transformer tower shared by
    :func:`build_fused_step_fn` and :func:`build_spec_verify_fn`, over
    the layers of a decoder spec (``models/decoder_spec.py``; ``dec`` is a
    model's ``serving_decoder()``): per layer, write every real flattened
    row's cache entry through the page table (``ops.kv_append``; quantized
    pools go through :func:`_quant_append`, latent ones through
    :func:`_write_latent_rows`), run the attention kernel of the layer's kind
    over the block pool — ``full``: the ragged paged attention kernel on
    per-head K|V rows; ``latent``: the MLA kernel on the one latent row
    all heads share — and apply the layer's output projection and FFN.
    A spec with more than one CACHE GROUP hands ``pool``, ``write_block``,
    ``tables`` and ``lo`` over as tuples, one entry a group, and each
    layer gets its group's (its window and sinks from its ``LayerSpec``);
    the pools come back as a tuple too.
    A ``routed`` FFN is told which rows are real (pad rows name the
    scratch block 0) and returns its ``ROUTED_COUNTERS`` counters, summed
    over the layers here. A layer whose spec OPENS A ROUTED SHORTCUT
    (``LayerSpec.shortcut`` n) returns its experts' sum beside ``(x,
    counters)``: the value is held HERE, and added to the stream after
    layer ``li + n``'s own ``attn_out``, under that layer's scope and the
    section ``shortcut`` — nothing passes from a layer to a later one but
    through this loop.
    A layer whose spec has a recurrent STATE runs its mixer
    beside the attention: ``state`` is the slots' state arrays (one a
    part of the descriptor, ``[layers with state, slots + 1, ...]``), the
    mixer reads each sequence's row of them and leaves the state after
    its last real row there (``ops/ssm.py``: a sequence at position 0
    starts from zero, pad rows touch nothing). Returns ``(final_norm(x),
    pool, scales, counters, state)``, ``counters`` ``None`` for a model
    without routed layers, ``state`` ``None`` for one without state.
    A layer whose mixer is a state ALONE (its spec has no cache) runs
    ``mixer`` and ``attn_out`` and nothing else: no ``attn_in``, no cache
    write, no move to the kernel's rows and back, no attention call. A
    layer WITHOUT A MIXER (no cache, no state) runs ``ffn_out`` and
    nothing else; a layer without an FFN (``LayerSpec.ffn`` ``"none"``)
    is one of the forms above whose ``attn_out`` ends with its residual —
    nothing stands in for the missing half of either.

    TWO ROW AXES (PR 41). ``x``, ``positions``, ``write_block`` and
    ``write_off`` — and so every op of every layer: norms, projections,
    the cache write, the mixer, the FFN with its ``row_valid`` — are on
    the step's ``R`` TOWER rows; ``blk_seq`` and ``seq_qstart`` describe
    the kernel's ``Q`` rows, and ``seq_pos0``, ``tables``, ``lo``,
    ``kv_len`` are a value a slot. ``axes`` (:func:`_row_axes`, the
    caller's: it reads the sequences' starts from it too) joins the two:
    a layer's query is laid into the kernel's rows just before the
    attention call and the call's output read back at the tower's just
    after it, two gathers a layer (and a third, of the layer's K|V rows,
    where the cache write is ``ops.kv_append``, whose rewrites follow the
    kernel's q blocks). ``axes`` ``None``: ``R == Q``, one axis, no
    gather."""
    import jax.numpy as jnp

    from ..ops.kv_append import kv_append
    from ..ops.mla_paged_attention import mla_paged_attention
    from ..ops.ragged_paged_attention import ragged_paged_attention
    from . import decoder_spec as DS

    grouped = isinstance(pool, tuple)
    pools = list(pool) if grouped else [pool]
    wbs, tabs, los = (write_block, tables, lo) if grouped \
        else ((write_block,), (tables,), (lo,))
    with DS.section(DS.EMBED):
        row_valid = wbs[0] > 0
        if state is not None:
            from ..ops.ragged_paged_attention import BLOCK_Q
            from ..ops.ssm import seq_layout
            # the mixer reads the TOWER's rows: runs of one row at the
            # sequences' compact starts where the tower has its own axis
            layout = seq_layout(blk_seq, seq_qstart, seq_pos0, kv_len,
                                row_valid, BLOCK_Q) if axes is None \
                else seq_layout(axes.row_seq, axes.start, seq_pos0, kv_len,
                                row_valid, 1)
    # ops.kv_append finds a block's rewrites by the kernel's layout (a q
    # block's rows are one sequence's: its module doc), so its write
    # targets and a layer's K|V rows are laid out as the query is; the
    # two XLA scatters take a target a row and run on the tower's rows
    kwbs, koff = wbs, write_off
    if axes is not None and not quantized:
        with DS.section(DS.CACHE_WRITE):
            kwbs = tuple(_at_rows(w, axes, 0, to_kernel=True) for w in wbs)
            koff = _at_rows(write_off, axes, 0, to_kernel=True)
    counters = None
    carried = {}      # closing layer -> the open shortcut's experts' sum

    def landed(li, ls, out, counters):
        """Layer ``li``'s ``attn_out`` result into ``(x, counters)``: a
        routed layer's counters added to the layers' before, an opened
        shortcut's value kept for its closing layer, and the one that
        closes here added to the stream."""
        if ls.shortcut:
            x, c, carried[li + ls.shortcut] = out
        else:
            x, c = out
        if li in carried:
            with DS.section(DS.SHORTCUT):
                x = Tensor(x._data + carried.pop(li)[None],
                           stop_gradient=True)
        if c is None:
            return x, counters
        with DS.section(DS.MOE_SCOPE):
            return x, c if counters is None else tuple(
                u + v for u, v in zip(counters, c))

    for li, (layer, ls) in enumerate(zip(dec.layers, dec.spec.layers)):
        if not ls.has_mixer:
            # the layer is its FFN alone: norm, FFN, residual
            with DS.layer_scope(li):
                x, counters = landed(li, ls, layer.ffn_out(x, row_valid),
                                     counters)
            continue
        if ls.cache is None:
            # the mixer is a state alone: the layer lives on the tower's
            # rows only — nothing is projected for a kernel, written to
            # the pool or read through a page table
            with DS.layer_scope(li):
                mixed, state = layer.mixer(
                    x, layout, state, dec.spec.state_layers.index(li))
                x, counters = landed(li, ls, layer.attn_out(
                    x, None, row_valid, mixed), counters)
            continue
        # the layer's cache group, and its place in the group's array
        g, gi = dec.spec.layer_group(li)
        with DS.layer_scope(li):
            # attn_in and attn_out name their own sections
            q, rows = layer.attn_in(x, positions)
            # row i's entry lands at (write_block[i], write_off[i])
            # through the page table. The two XLA scatters send pad rows
            # to the scratch block nobody reads; kv_append skips them
            if ls.attention == DS.FULL:
                if q.shape[0] != (ls.query_heads or ls.cache.rows):
                    raise ValueError(
                        f"layer {li} hands the kernel {q.shape[0]} query "
                        f"heads and its spec says "
                        f"{ls.query_heads or ls.cache.rows}: the engine "
                        f"counts the kernel's walks from the spec")
                with DS.section(DS.CACHE_WRITE):
                    if quantized:
                        pools[g], scales = _quant_append(
                            pools[g], scales, gi, wbs[g], write_off, *rows,
                            qmax)
                    else:
                        pools[g] = kv_append(
                            pools[g], gi, kwbs[g], koff, _at_rows(
                                _kv_lanes(*rows, ls.cache.lanes), axes, 0,
                                to_kernel=True))
                with DS.section(DS.ATTENTION):
                    a = _at_rows(ragged_paged_attention(
                        _at_rows(q, axes, 1, to_kernel=True), pools[g], gi,
                        blk_seq, seq_qstart, seq_pos0,
                        tabs[g], los[g], kv_len, scales=scales,
                        mask_block=dec.spec.generation.block_length,
                        window=ls.window,
                        sinks=layer.sinks if ls.sinks else None,
                        v_lanes=ls.cache.v_lanes if ls.cache.k_lanes else 0),
                        axes, 1)
            else:
                with DS.section(DS.CACHE_WRITE):
                    pools[g] = _write_latent_rows(pools[g], gi, wbs[g],
                                                  write_off, rows)
                with DS.section(DS.ATTENTION):
                    a = _at_rows(mla_paged_attention(
                        _at_rows(q, axes, 0, to_kernel=True), pools[g], gi,
                        blk_seq, seq_qstart, seq_pos0,
                        tabs[g], los[g], kv_len, v_lanes=ls.cache.v_lanes,
                        scale=dec.attention_scale), axes, 0)
            if ls.state is not None:
                # the mixer names its own sections too
                mixed, state = layer.mixer(
                    x, layout, state, dec.spec.state_layers.index(li))
                out = layer.attn_out(x, a, row_valid, mixed)
            else:
                out = layer.attn_out(x, a, row_valid)
            x, counters = landed(li, ls, out, counters)
    if counters is not None:
        with DS.section(DS.MOE_SCOPE):
            counters = jnp.stack(counters).astype(jnp.int32)
    with DS.section(DS.NORM):
        x = dec.final_norm(x)
    return (x, tuple(pools) if grouped else pools[0], scales, counters,
            state)


def _named(fn, name: str):
    """``fn`` called ``name``: ``jax.jit`` names the compiled module for
    the function (``jit_<name>``), which is what a launch is called on
    the device's ``XLA Modules`` line of a profiler trace and in the
    flight recorder's ``launch_program``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _tokens_from_prev(token_ids, prev_tokens, token_src):
    """The fused step's input tokens with the rows the host could not
    fill: row ``i`` takes ``prev_tokens[token_src[i]]`` — the token the
    launch before this one picked for that slot, still un-fetched —
    where ``token_src[i] >= 0``, and keeps ``token_ids[i]`` elsewhere.
    It is what lets the scheduler dispatch a launch before it has
    fetched the one in flight (``serving/scheduler.py``)."""
    import jax.numpy as jnp
    return jnp.where(token_src >= 0,
                     prev_tokens[jnp.maximum(token_src, 0)], token_ids)


# the block state of generation by diffusion over blocks
# (models/decoder_spec.py GenerationRule): beside a block's token ids, the
# pass in which each position was fixed — BLOCK_GIVEN for a position the
# prompt filled, BLOCK_UNFIXED while it is open. Whether a position is
# fixed is read from HERE, never by comparing an id with the mask id (a
# prompt may hold that id)
BLOCK_UNFIXED, BLOCK_GIVEN = -2, -1

def block_result_layout(num_slots: int, block_length: int, routed: bool):
    """Where a block-generation step's result holds what: ``(fixed_at,
    sentinel_at, counters_at, tokens_at, passes_at, size)`` into the one
    int32 array a launch returns — ``[S]`` the position each slot fixed
    last this pass (-1: none), the logits-finite sentinel, a routed
    model's ``ROUTED_COUNTERS`` counters (the same places as in a
    one-token step's result, so the scheduler's readers of those are one), then the block
    state: ``[S * B]`` token ids and ``[S * B]`` the pass each position
    was fixed in."""
    from .decoder_spec import ROUTED_COUNTERS
    S, B = int(num_slots), int(block_length)
    tokens_at = S + 1 + (ROUTED_COUNTERS if routed else 0)
    return (0, S, S + 1, tokens_at, tokens_at + S * B,
            tokens_at + 2 * S * B)


def _unmask(dec, x, blk_row0, blk_tok, blk_pass, pass_idx, rule):
    """The last stage of a block-generation step, on the device: logits
    on the B rows of every slot's block (from row ``blk_row0[s]`` on: the
    head never runs on the commit rows a riding slot holds before them),
    argmax and its softmax probability (the confidence) at every
    position, and for each slot in a denoising pass (``pass_idx >= 0``)
    the ``fixed_per_pass`` most confident UNFIXED positions take their
    argmax and are stamped with the pass. The mask id is never chosen:
    its logit is out of the argmax and of the softmax alike. Ties go to
    the lowest position. Returns ``(blk_tok, blk_pass, fixed_pos [S],
    logits_bad)``. The caller holds it under the section ``unmask``."""
    import jax
    import jax.numpy as jnp
    S, B = blk_tok.shape
    rows = (blk_row0[:, None]
            + jnp.arange(B, dtype=jnp.int32)[None, :]).reshape(-1)
    logits = dec.logits(Tensor(x._data[0, rows][:, None, :]))._data[
        :, 0].astype(jnp.float32)                     # [S * B, V]
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(vocab == rule.mask_token_id, -jnp.inf, logits)
    top = jnp.max(logits, axis=-1)
    best = jnp.argmax(logits, axis=-1).astype(jnp.int32).reshape(S, B)
    denom = jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)
    conf = (1.0 / denom).reshape(S, B)        # softmax prob of argmax
    bad = jnp.any(~jnp.isfinite(top)) | jnp.any(~jnp.isfinite(denom))
    denoise = (pass_idx >= 0)[:, None]
    fixed_pos = jnp.full((S,), -1, jnp.int32)
    cols = jnp.arange(B, dtype=jnp.int32)[None, :]
    for _ in range(rule.fixed_per_pass):
        open_ = (blk_pass == BLOCK_UNFIXED) & denoise
        c = jnp.where(open_, conf, -1.0)
        j = jnp.argmax(c, axis=-1).astype(jnp.int32)          # [S]
        take = (cols == j[:, None]) & open_
        blk_tok = jnp.where(take, best, blk_tok)
        blk_pass = jnp.where(take, pass_idx[:, None], blk_pass)
        fixed_pos = jnp.where(jnp.any(take, axis=-1), j, fixed_pos)
    return blk_tok, blk_pass, fixed_pos, bad.astype(jnp.int32)


def _build_block_step_fn(model, dec, S, Q, T, probe):
    """:func:`build_fused_step_fn` for a spec whose generation rule has
    ``block_length`` B > 1. A decode slot contributes the B rows of its
    current block, at the block's positions: the step embeds the block's
    state (fixed ids, the mask id elsewhere), appends the rows' K/V over
    the block's previous ones, runs the tower and :func:`_unmask`. A
    prompt chunk is the same program: its slot says ``pass_idx`` -1 and
    its state passes through.

    So is the COMMIT of a finished block, which RIDES with the next
    block's first denoising pass: the slot says ``ride`` 1 and holds 2 B
    rows at consecutive positions — the finished block's, which show its
    final tokens (their K/V, written under the block mask over the
    committed text, are the ones the cache keeps), then the next
    block's, all the mask id (the host fills them in: ``row_blk`` -1).
    The state the slot names is the FINISHED block's; the one
    :func:`_unmask` works on, B rows further down, and the result holds
    is the next block's, all open before this pass. A finished block
    whose slot holds its B rows only (``ride`` 0, ``pass_idx`` -1) is the
    same ride with no next rows: a commit alone.

    ``fn(params, buffers, pool, token_ids, qpos, write_block, write_off,
    blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len, prev_result,
    state_src [S], blk_tok [S, B], blk_pass [S, B], row_blk [R],
    pass_idx [S], ride [S], key) -> (pool, result, key)`` (the per-row
    operands on the step's ``R`` TOWER rows, as in
    :func:`build_fused_step_fn`; a slot's block is found from its first
    tower row): ``row_blk``
    names, for a row that shows the slot's state, ``slot * B + position
    in the block`` (-1: the row keeps its ``token_ids``);
    ``state_src[s] >= 0`` takes slot ``s``'s block state from
    ``prev_result`` — the launch before this one, UN-fetched — in place
    of ``blk_tok``/``blk_pass``; ``result`` is laid out by
    :func:`block_result_layout`."""
    import jax.numpy as jnp

    from ..framework import trace_probe as _probe
    from ..nn.layer.layers import functional_state
    from . import decoder_spec as DS

    rule = dec.spec.generation
    B = int(rule.block_length)
    routed = any(ls.routes for ls in dec.spec.layers)
    _, _, _, tokens_at, passes_at, size = block_result_layout(S, B, routed)

    def fn(params, buffers, pool, token_ids, qpos, write_block, write_off,
           blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len, prev_result,
           state_src, blk_tok, blk_pass, row_blk, pass_idx, ride, key):
        if probe is not None:  # runs at trace time only (jit caches)
            probe.record(_probe.sig_of([pool, token_ids, tables]),
                         {"q": Q, "table": T})
        with functional_state(model, params, buffers):
            with no_grad_guard():
                with DS.section(DS.EMBED):
                    from_prev = (state_src >= 0)[:, None]
                    blk_tok = jnp.where(
                        from_prev,
                        prev_result[tokens_at:passes_at].reshape(S, B),
                        blk_tok)
                    blk_pass = jnp.where(
                        from_prev,
                        prev_result[passes_at:size].reshape(S, B), blk_pass)
                    shown = jnp.where(blk_pass == BLOCK_UNFIXED,
                                      jnp.int32(rule.mask_token_id),
                                      blk_tok).reshape(-1)
                    token_ids = jnp.where(
                        row_blk >= 0, shown[jnp.maximum(row_blk, 0)],
                        token_ids)
                    x = dec.embed_tokens(token_ids, qpos)
                    axes = _row_axes(token_ids.shape[0], blk_seq,
                                     seq_qstart, seq_pos0, kv_len)
                x, new_pool, _, counters, _ = _fused_tower(
                    dec, x, qpos, pool, None, write_block, write_off,
                    blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len,
                    False, 0.0, axes=axes)
                with DS.section(DS.UNMASK_SCOPE):
                    # a riding slot's rows have shown its finished block;
                    # the block it denoises is the next, B rows down, all
                    # open
                    riding = (ride > 0)[:, None]
                    blk_tok = jnp.where(riding, 0, blk_tok)
                    blk_pass = jnp.where(riding, BLOCK_UNFIXED, blk_pass)
                    first = seq_qstart if axes is None else axes.start
                    blk_tok, blk_pass, fixed_pos, bad = _unmask(
                        dec, x, first + B * ride, blk_tok, blk_pass,
                        pass_idx, rule)
                    parts = [fixed_pos, bad[None]]
                    if counters is not None:
                        parts.append(counters)
                    parts += [blk_tok.reshape(-1), blk_pass.reshape(-1)]
                    result = jnp.concatenate(parts).astype(jnp.int32)
        return new_pool, result, key

    return _named(fn, f"block_step_q{Q}_t{T}")


def build_fused_step_fn(model, num_slots, q_rows, table_len, block_size,
                        top_k=0, top_p=1.0, probe=None, quantized=False,
                        qmax=127.0):
    """Build THE fused ragged serving step: one jitted program that
    advances a RAGGED batch of mixed prefill-chunk and decode rows
    through every layer with the fused paged-attention Pallas kernel
    (ops/ragged_paged_attention.py) — no gathered KV window, the kernel
    walks each sequence's page table directly in HBM. This is
    ``GenerationEngine``'s step; ``models.generate`` is the oracle its
    greedy output is held to, token for token.

    Returns ``fn(params, buffers, pool, token_ids, qpos, write_block,
    write_off, blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len,
    last_row, prev_tokens, token_src, sample_mask, temperature, key) ->
    (pool, next_tokens, key)`` over the block pool ``[layers,
    num_blocks + 1, heads, block_size, 2 * head_dim]`` (for a spec whose
    layers hold a recurrent state, ``pool`` is the pair ``(block pool,
    state arrays)``: ``PagedKVPool.state_data``, one array a part, a row
    a slot; ``next_tokens``
    ``[num_slots + 1]`` — the last element is the logits-finite sentinel
    of :func:`_append_nonfinite_flag`; a routed model appends its four
    counters):

    * ``token_ids``/``qpos``/``write_block``/``write_off`` ``[R]``
      int32 — the flattened ragged batch on the step's TOWER rows: each
      row's token, virtual cache position, and page-table-resolved
      physical write block/offset (pad rows name the scratch block 0);
      every real row's K/V are appended to the pool BEFORE the attention
      kernel runs, so a chunk row attends causally to its own chunk
      prefix. ``R`` is READ FROM THESE OPERANDS' SHAPE, ``R <= q_rows``:
      the slots' real rows back to back in slot order, pad rows at the
      end (:func:`_row_axes`) — everything of the step but the
      attention call runs on them. ``R == q_rows`` is the padded layout
      of ``ops.ragged_paged_attention.ragged_layout`` itself, one axis
      for tower and kernel, and then nothing is moved between the two;
      ``serving/engine.py:_tower_rows`` says which ``R`` a ``q_rows``
      bucket's programs have;
    * ``blk_seq [q_rows / 8]`` and ``seq_qstart [num_slots]`` — the
      KERNEL's ``q_rows`` rows, each slot's padded to whole q blocks —
      and ``seq_pos0``/``lo``/``kv_len`` ``[num_slots]``, ``tables
      [num_slots, table_len]``: the kernel's scalar-prefetch metadata;
    * ``last_row [num_slots]`` int32 — the TOWER row of each slot's
      LAST real token this launch: its hidden state produces the slot's
      next-token logits, so a slot whose final feed chunk lands this
      cycle gets its first generated token from the SAME launch that
      prefilled the tail (rows of slots mid-chunk or absent produce
      garbage the scheduler ignores);
    * ``prev_tokens`` — the ``next_tokens`` of the launch before this
      one, handed back UN-fetched (zeros when none is in flight) — and
      ``token_src [R]`` int32: a decode row whose input token is
      still on the device names its slot there, every other row says -1
      and keeps its ``token_ids`` (:func:`_tokens_from_prev`);
    * ``sample_mask``/``temperature`` ``[num_slots]`` are traced (one
      program serves mixed greedy/sampled batches); the caller jits
      with ``donate_argnums`` on ``pool`` and the engine's ``analyze()``
      must report the program donation-safe and host-sync-free.

    A model whose generation rule has ``block_length`` > 1 (generation by
    diffusion over blocks) gets the step of :func:`_build_block_step_fn`:
    the same tower over the same operands, its last stage ``_unmask`` in
    place of the one argmax a slot.

    One trace per ``(q_rows bucket, table bucket)``, watched by ``probe``.
    ``quantized=True`` threads the per-block scale array beside the
    pool (``fn(params, buffers, pool, scales, token_ids, ...) ->
    (pool, scales, next_tokens, key)``): rows scatter through
    :func:`_quant_append` and the kernel dequantizes in-register off
    the scale array riding its scalar-prefetch metadata.
    """
    import jax
    import jax.numpy as jnp

    from ..framework import trace_probe as _probe
    from ..nn.layer.layers import functional_state
    from ..ops.ragged_paged_attention import BLOCK_Q
    from . import decoder_spec as DS

    dec = DS.serving_decoder(model)
    S, Q, T, bs = (int(num_slots), int(q_rows), int(table_len),
                   int(block_size))
    if S < 1:
        raise ValueError(f"num_slots must be >= 1, got {S}")
    if Q < BLOCK_Q or Q % BLOCK_Q:
        raise ValueError(
            f"q_rows must be a positive multiple of {BLOCK_Q}, got {Q}")
    if T < 1:
        raise ValueError(f"table_len must be >= 1, got {T}")
    top_k = min(int(top_k), dec.spec.vocab_size)
    if dec.spec.generation.block_length > 1:
        if quantized:
            raise ValueError(
                "block generation over int8/fp8 blocks is not built")
        return _build_block_step_fn(model, dec, S, Q, T, probe)

    stateful = bool(dec.spec.state_layers)
    if stateful and quantized:
        raise ValueError(
            "a recurrent state beside int8/fp8 blocks is not built")

    def fn(params, buffers, pool, *rest):
        (scales, token_ids, qpos, write_block, write_off, blk_seq,
         seq_qstart, seq_pos0, tables, lo, kv_len, last_row,
         prev_tokens, token_src, sample_mask, temperature, key) = \
            rest if quantized else (None,) + rest
        state = None
        if stateful:
            # the pool operand holds the slots' state arrays too: one
            # donated pytree, back as it came
            pool, state = pool
        if probe is not None:  # runs at trace time only (jit caches)
            probe.record(_probe.sig_of(jax.tree_util.tree_leaves(
                [pool, token_ids, tables])), {"q": Q, "table": T})
        with functional_state(model, params, buffers):
            with no_grad_guard():
                with DS.section(DS.EMBED):
                    token_ids = _tokens_from_prev(token_ids, prev_tokens,
                                                  token_src)
                    # logical positions == virtual positions (paged
                    # sequences are aligned at virtual 0; lo is the mask
                    # floor, not a pad offset)
                    x = dec.embed_tokens(token_ids, qpos)
                    axes = _row_axes(token_ids.shape[0], blk_seq,
                                     seq_qstart, seq_pos0, kv_len)
                x, new_pool, new_scales, counters, state = _fused_tower(
                    dec, x, qpos, pool, scales, write_block, write_off,
                    blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len,
                    quantized, qmax, state, axes)
                if stateful:
                    new_pool = (new_pool, state)
                with DS.section(DS.HEAD):
                    last = x._data[0, last_row]             # [S, E]
                    logits = dec.logits(
                        Tensor(last[:, None, :]))._data[:, 0].astype(
                            jnp.float32)
                with DS.section(DS.SAMPLE):
                    key, sub = jax.random.split(key)
                    greedy = _pick_token(logits, sub, False, top_k, top_p,
                                         1.0)
                    sampled = _pick_token(logits, sub, True, top_k, top_p,
                                          temperature[:, None])
                    nxt = jnp.where(sample_mask, sampled, greedy)
                    nxt = _append_nonfinite_flag(nxt, logits)
                    if counters is not None:
                        # the routed layers' counters ride the same
                        # fetch, after the sentinel: [S + 1 : S + 5]
                        nxt = jnp.concatenate([nxt, counters])
        if quantized:
            return new_pool, new_scales, nxt, key
        return new_pool, nxt, key

    return _named(fn, f"fused_step_q{Q}_t{T}")


# ---------------------------------------------------------------------------
# tensor-parallel serving steps (GenerationEngine(mesh=..., mp_axis="mp")):
# the per-device Megatron twins of the paged/fused steps above, wrapped in
# shard_map over a 1-D mp mesh. The block pool is head-partitioned
# ([L, NB+1, H/mp, bs, 2*Dh] per device); page tables, free lists and the
# prefix trie stay replicated host-side, so the allocator/COW/preemption
# logic never sees the mesh. Column-parallel projections slice the
# replicated bias to their local output columns; row-parallel projections
# join their partial products with ONE psum per projection (two per layer
# plus nothing at the LM head — post-psum activations are replicated, and
# the tied embedding weight is too).
# ---------------------------------------------------------------------------


def _mp_col_linear(lin, h, mp_axis):
    """Column-parallel Linear: replicated ``h [.., in]`` in, LOCAL
    ``[.., out/mp]`` out. Inside ``shard_map`` the module's swapped-in
    weight IS the local column shard; the bias is replicated full-width
    (``shard_params_megatron`` leaves 1-D params alone), so this
    device's output columns slice it at ``axis_index * out/mp`` — the
    module call itself would add a ``[out]`` bias to a ``[.., out/mp]``
    product and fail."""
    from jax import lax
    w = lin.weight._data                        # [in, out/mp] local
    b = lin.bias._data                          # [out] replicated
    n = w.shape[1]
    i = lax.axis_index(mp_axis) * n
    return h @ w + lax.dynamic_slice(b, (i,), (n,))


def _mp_row_linear(lin, h_local, mp_axis):
    """Row-parallel Linear: LOCAL ``[.., in/mp]`` in, replicated
    ``[.., out]`` out. The local product is a PARTIAL sum over the
    input dim; one ``psum`` joins the shards and the replicated bias is
    added exactly once, post-sum."""
    from jax import lax
    return lax.psum(h_local @ lin.weight._data, mp_axis) \
        + lin.bias._data


def _mp_qkv(block, x, mp, mp_axis):
    """Per-device :meth:`GPTBlock._qkv`: ln_1 on the replicated
    activations, column-parallel q/k/v projections, heads reshaped to
    the LOCAL head count (``_split_heads`` reshapes by the global
    ``num_heads`` attribute, so the split happens manually here).
    Returns local ``q/k/v [B, L, H/mp, Dh]`` ndarrays."""
    from . import decoder_spec as DS
    with DS.section(DS.NORM):
        h = block.ln_1(x)._data
    attn = block.attn
    hl = attn.num_heads // mp
    dh = attn.head_dim

    def proj(lin):
        y = _mp_col_linear(lin, h, mp_axis)
        return y.reshape(y.shape[0], y.shape[1], hl, dh)

    with DS.section(DS.QKV):
        return proj(attn.q_proj), proj(attn.k_proj), proj(attn.v_proj)


def _mp_tail(block, x, a_local, mp_axis):
    """Per-device :meth:`GPTBlock._tail`: merge the LOCAL heads,
    row-parallel out-proj (the psum joins the head shards' attention
    outputs), residual, then the column/row-parallel MLP with its own
    psum — the Megatron two-collectives-per-layer count. ``a_local`` is
    a ``[B, L, H/mp, Dh]`` ndarray; returns the replicated Tensor."""
    from ..nn import functional as F
    from . import decoder_spec as DS
    with DS.section(DS.O_PROJ):
        a = a_local.reshape(a_local.shape[0], a_local.shape[1], -1)
        attn_out = _mp_row_linear(block.attn.out_proj, a, mp_axis)
        x = x + block.dropout(Tensor(attn_out, stop_gradient=True))
    with DS.section(DS.NORM):
        h = block.ln_2(x)._data
    with DS.section(DS.MLP):
        g = F.gelu(Tensor(_mp_col_linear(block.mlp_fc, h, mp_axis),
                          stop_gradient=True), approximate=True)
        m = _mp_row_linear(block.mlp_proj, g._data, mp_axis)
        return x + block.dropout(Tensor(m, stop_gradient=True))


def _mp_fused_tower(gpt, x, pool, write_block, write_off, blk_seq,
                    seq_qstart, seq_pos0, tables, lo, kv_len, mp,
                    mp_axis):
    """Per-device fused ragged tower: each device appends its OWN
    heads' K/V to its pool shard (``ops.kv_append``) and launches the
    ragged Pallas kernel over its local head range — heads are a batch
    dimension of both kernels, so the per-shard calls are the UNMODIFIED
    kernels on an ``[H/mp, ...]`` slice with the replicated
    scalar-prefetch metadata.
    Returns ``(ln_f(x), pool)``."""
    import jax.numpy as jnp

    from ..ops.kv_append import kv_append
    from ..ops.ragged_paged_attention import ragged_paged_attention
    from . import decoder_spec as DS

    for li, block in enumerate(gpt.blocks):
        with DS.layer_scope(li):
            q, k, v = _mp_qkv(block, x, mp, mp_axis)
            with DS.section(DS.CACHE_WRITE):
                pool = kv_append(pool, li, write_block, write_off,
                                 _kv_lanes(k[0], v[0]))
            with DS.section(DS.ATTENTION):
                qh = jnp.transpose(q, (0, 2, 1, 3))[0]   # [H/mp, Q, Dh]
                a = ragged_paged_attention(
                    qh, pool, li, blk_seq, seq_qstart, seq_pos0, tables,
                    lo, kv_len)
                a = jnp.transpose(a[None], (0, 2, 1, 3))  # [1,Q,H/mp,Dh]
            x = _mp_tail(block, x, a, mp_axis)
    with DS.section(DS.NORM):
        x = gpt.ln_f(x)
    return x, pool


def _mp_pool_spec(mp_axis):
    """The head-partitioned PartitionSpec of the paged block pool
    ``[L, NB+1, H, bs, 2*Dh]`` — axis 2 (heads) over ``mp_axis``."""
    from jax.sharding import PartitionSpec as P
    return P(None, None, mp_axis, None, None)


def _mp_mesh_check(model, mesh, mp_axis):
    """Validate the serving mesh and return its mp degree. The serving
    shard_maps are manual over EVERY mesh axis, so a 1-D mesh is
    required (dp replication belongs to EngineFleet, one engine per
    replica)."""
    if mp_axis not in mesh.axis_names:
        raise ValueError(
            f"mp_axis {mp_axis!r} not in mesh axes {mesh.axis_names}")
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"serving mesh must be 1-D over {mp_axis!r}, got axes "
            f"{mesh.axis_names} (replicate with EngineFleet instead)")
    mp = int(mesh.shape[mp_axis])
    H = (model.gpt if hasattr(model, "gpt") else model
         ).cfg.num_attention_heads
    if H % mp:
        raise ValueError(
            f"num_attention_heads {H} not divisible by mesh "
            f"{mp_axis}={mp}")
    return mp


def build_sharded_fused_step_fn(model, num_slots, q_rows, table_len,
                                block_size, mesh, mp_axis="mp", top_k=0,
                                top_p=1.0, probe=None):
    """Tensor-parallel :func:`build_fused_step_fn` (non-quantized): THE
    fused ragged serving step under ``shard_map`` over the 1-D ``mp``
    mesh. Each device launches the ragged Pallas kernel on its own
    heads against its own pool shard (the kernel's per-head grid makes
    the per-shard call the unmodified kernel); the row-parallel
    projections contribute the only collectives — one psum per
    out-proj/MLP-out joining attention outputs before the replicated
    LM head feeds :func:`_pick_token`, so the picked token is identical
    on every device (and so is ``prev_tokens``, the replicated result
    handed back). Signature, bucket discipline and the
    ``donate_argnums`` contract on the (now head-partitioned GLOBAL)
    pool are unchanged from the single-device builder — the donated
    pool stays donated through the shard_map boundary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..framework import trace_probe as _probe
    from ..nn.layer.layers import functional_state
    from ..ops.ragged_paged_attention import BLOCK_Q
    from . import decoder_spec as DS

    gpt = model.gpt if hasattr(model, "gpt") else model
    S, Q, T, bs = (int(num_slots), int(q_rows), int(table_len),
                   int(block_size))
    if S < 1:
        raise ValueError(f"num_slots must be >= 1, got {S}")
    if Q < BLOCK_Q or Q % BLOCK_Q:
        raise ValueError(
            f"q_rows must be a positive multiple of {BLOCK_Q}, got {Q}")
    if T < 1:
        raise ValueError(f"table_len must be >= 1, got {T}")
    mp = _mp_mesh_check(gpt, mesh, mp_axis)
    top_k = min(int(top_k), gpt.cfg.vocab_size)

    def body(params, buffers, pool, token_ids, qpos, write_block,
             write_off, blk_seq, seq_qstart, seq_pos0, tables, lo,
             kv_len, last_row, prev_tokens, token_src, sample_mask,
             temperature, key):
        with functional_state(model, params, buffers):
            with no_grad_guard():
                with DS.section(DS.EMBED):
                    token_ids = _tokens_from_prev(token_ids, prev_tokens,
                                                  token_src)
                    x = gpt.wte(Tensor(token_ids[None, :],
                                       stop_gradient=True)) \
                        + gpt.wpe(Tensor(qpos[None, :]))
                x, new_pool = _mp_fused_tower(
                    gpt, x, pool, write_block, write_off, blk_seq,
                    seq_qstart, seq_pos0, tables, lo, kv_len, mp,
                    mp_axis)
                with DS.section(DS.HEAD):
                    last = x._data[0, last_row]             # [S, E]
                    logits = gpt.logits(
                        Tensor(last[:, None, :]))._data[:, 0].astype(
                            jnp.float32)
                with DS.section(DS.SAMPLE):
                    key, sub = jax.random.split(key)
                    greedy = _pick_token(logits, sub, False, top_k, top_p,
                                         1.0)
                    sampled = _pick_token(logits, sub, True, top_k, top_p,
                                          temperature[:, None])
                    nxt = jnp.where(sample_mask, sampled, greedy)
                    nxt = _append_nonfinite_flag(nxt, logits)
        return new_pool, nxt, key

    rep = P()
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(megatron_param_specs(model, mp_axis), rep,
                  _mp_pool_spec(mp_axis)) + (rep,) * 16,
        out_specs=(_mp_pool_spec(mp_axis), rep, rep), check_vma=False)

    def fn(params, buffers, pool, token_ids, qpos, write_block,
           write_off, blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len,
           last_row, prev_tokens, token_src, sample_mask, temperature,
           key):
        if probe is not None:  # runs at trace time only (jit caches)
            probe.record(_probe.sig_of([pool, token_ids, tables]),
                         {"q": Q, "table": T, "mp": mp})
        return sm(params, buffers, pool, token_ids, qpos, write_block,
                  write_off, blk_seq, seq_qstart, seq_pos0, tables, lo,
                  kv_len, last_row, prev_tokens, token_src, sample_mask,
                  temperature, key)

    return _named(fn, f"fused_step_q{Q}_t{T}_mp{mp}")


# ---------------------------------------------------------------------------
# speculative decoding (the draft-propose / fused-verify pair consumed by
# GenerationEngine(spec_draft=..., spec_k=...) — see serving/engine.py)
# ---------------------------------------------------------------------------

def build_spec_verify_fn(model, num_slots, q_rows, spec_k, table_len,
                         block_size, top_k=0, top_p=1.0, probe=None,
                         quantized=False, qmax=127.0):
    """The multi-row-per-slot VERIFY variant of
    :func:`build_fused_step_fn`: one fused ragged launch where each
    speculating slot contributes its candidate rows (``[last_token,
    d_1, ..., d_{n-1}]`` — draft candidates are just extra ragged rows,
    exactly like a prefill chunk) and the per-row logits drive
    :func:`_spec_accept`'s standard rejection sampling, with exact
    greedy parity as the degenerate case. Slots mid-prefill keep
    chunking through the same launch (``n_spec == 0`` rows are plain
    feed rows whose last-row pick is the non-speculative path).

    Returns ``fn(params, buffers, pool, [scales,] token_ids, qpos,
    write_block, write_off, blk_seq, seq_qstart, seq_pos0, tables, lo,
    kv_len, last_row, n_spec, draft_toks, draft_probs, sample_mask,
    temperature, key) -> (pool, [scales,] out, key)`` where

    * ``n_spec [S]`` int32 — candidates verified per slot this launch
      (0 = plain feed/decode rows);
    * ``draft_toks [S, spec_k]`` int32 / ``draft_probs [S, spec_k, V]``
      f32 — the DEVICE-side proposals of the draft loop (the host never
      fetched them); rows ``first + 1 + j`` of ``token_ids`` (``first``
      a slot's first TOWER row: the per-row operands are ``[R]`` as in
      :func:`build_fused_step_fn`) are
      overlaid with ``draft_toks[:, j]`` in-trace, because those token
      values only exist on the device;
    * ``out [2S + S*spec_k + 1]`` int32 — ``[accepted (S) | corrected
      token (S) | echoed draft tokens (S*spec_k) | logits-finite
      sentinel]``: everything the scheduler needs from its ONE fetch
      per cycle (accepted drafts are emitted host-side from the echo).

    One trace per (q bucket, table bucket), same as the fused step.
    """
    import jax
    import jax.numpy as jnp

    from ..framework import trace_probe as _probe
    from ..nn.layer.layers import functional_state
    from ..ops.ragged_paged_attention import BLOCK_Q

    from . import decoder_spec as DS
    dec = DS.serving_decoder(model)
    S, Q, K, T = (int(num_slots), int(q_rows), int(spec_k),
                  int(table_len))
    if S < 1:
        raise ValueError(f"num_slots must be >= 1, got {S}")
    if K < 1:
        raise ValueError(f"spec_k must be >= 1, got {K}")
    if Q < BLOCK_Q or Q % BLOCK_Q:
        raise ValueError(
            f"q_rows must be a positive multiple of {BLOCK_Q}, got {Q}")
    if T < 1:
        raise ValueError(f"table_len must be >= 1, got {T}")
    top_k = min(int(top_k), dec.spec.vocab_size)
    if dec.spec.generation.block_length > 1:
        if quantized:
            raise ValueError(
                "block generation over int8/fp8 blocks is not built")
        return _build_block_step_fn(model, dec, S, Q, T, probe)

    def fn(params, buffers, pool, *rest):
        (scales, token_ids, qpos, write_block, write_off, blk_seq,
         seq_qstart, seq_pos0, tables, lo, kv_len, last_row, n_spec,
         draft_toks, draft_probs, sample_mask, temperature, key) = \
            rest if quantized else (None,) + rest
        if probe is not None:  # runs at trace time only (jit caches)
            probe.record(_probe.sig_of([pool, token_ids, tables,
                                        draft_toks]),
                         {"q": Q, "table": T, "k": K})
        with functional_state(model, params, buffers):
            with no_grad_guard():
                # overlay the device-side draft tokens into their
                # verify rows: row qstart + 1 + j carries candidate
                # d_{j+1}'s PREDECESSOR d_j... i.e. the fed token at
                # verify position j+1 is draft_toks[:, j]; invalid
                # (j >= n_spec - 1) overlays are dropped out of bounds
                with DS.section(DS.EMBED):
                    # the slots' first TOWER rows (R of them: Q, or fewer)
                    R = token_ids.shape[0]
                    axes = _row_axes(R, blk_seq, seq_qstart, seq_pos0,
                                     kv_len)
                    first = seq_qstart if axes is None else axes.start
                    rows = first[:, None] + 1 + jnp.arange(K)[None, :]
                    ok = jnp.arange(K)[None, :] < (n_spec[:, None] - 1)
                    safe = jnp.where(ok, rows, R)     # R = out of range
                    token_ids = token_ids.at[safe.reshape(-1)].set(
                        draft_toks.reshape(-1), mode="drop")
                    x = dec.embed_tokens(token_ids, qpos)
                x, new_pool, new_scales, _, _ = _fused_tower(
                    dec, x, qpos, pool, scales, write_block, write_off,
                    blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len,
                    quantized, qmax, axes=axes)
                with DS.section(DS.HEAD):
                    # gather the rows whose logits are actually read —
                    # the S*K verify rows plus each slot's last row —
                    # BEFORE the LM head: running the [vocab] matmul
                    # over all Q padded ragged rows would cost
                    # Q/(S*(K+1))x more for nothing (a chunk-heavy cycle
                    # reads none of its chunk rows' logits)
                    vrows = jnp.clip(
                        first[:, None] + jnp.arange(K)[None, :],
                        0, R - 1)                      # [S, K]
                    sel = x._data[0][jnp.concatenate(
                        [vrows.reshape(-1), last_row])]  # [S*K+S, E]
                    logits = dec.logits(
                        Tensor(sel[:, None, :]))._data[:, 0].astype(
                            jnp.float32)               # [S*K+S, V]
                with DS.section(DS.SAMPLE):
                    p = _sample_probs(
                        logits[:S * K],
                        jnp.repeat(sample_mask, K),
                        top_k, top_p,
                        jnp.repeat(temperature, K)).reshape(S, K, -1)
                    base = _sample_probs(logits[S * K:], sample_mask,
                                         top_k, top_p, temperature)
                    key, sub = jax.random.split(key)
                    accepted, token = _spec_accept(
                        p, draft_probs, draft_toks, n_spec, base, sub)
                    bad = jnp.any(~jnp.isfinite(logits)).astype(jnp.int32)
                    out = jnp.concatenate([
                        accepted.astype(jnp.int32), token,
                        draft_toks.astype(jnp.int32).reshape(-1),
                        bad[None]])
        if quantized:
            return new_pool, new_scales, out, key
        return new_pool, out, key

    return _named(fn, f"spec_verify_q{Q}_t{T}_k{K}")


def build_draft_prefill_fn(model, bucket_len, max_len, probe=None):
    """Context prefill into the DRAFT model's dense slot pool
    (speculative decoding): when a slot starts decoding, the draft's
    KV cache must cover the target's context ``[0, pos)`` before it
    can propose. Prompts are RIGHT-padded to the bucket (virtual index
    0 — the draft mirrors the paged pool's alignment, so ``lo == 0``
    and draft positions equal target positions token for token).

    Returns ``fn(params, buffers, pool, ids, key_valid, slot) ->
    pool`` over the draft pool ``[draft_layers, 2, slots, draft_heads,
    max_len, draft_head_dim]``; no token is sampled — proposals come
    from the :func:`build_draft_propose_scan_fn` program that follows. The
    caller jits with ``donate_argnums`` on ``pool``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..framework import trace_probe as _probe
    from ..nn.layer.layers import functional_state

    gpt = model.gpt if hasattr(model, "gpt") else model
    Lb = int(bucket_len)
    if Lb < 1:
        raise ValueError(f"bucket_len must be >= 1, got {Lb}")
    if Lb > int(max_len):
        raise ValueError(f"bucket_len {Lb} exceeds pool max_len {max_len}")
    if Lb > gpt.cfg.max_position_embeddings:
        raise ValueError(
            f"bucket_len {Lb} exceeds max_position_embeddings="
            f"{gpt.cfg.max_position_embeddings}")

    def fn(params, buffers, pool, ids, key_valid, slot):
        if probe is not None:  # runs at trace time only (jit caches)
            probe.record(_probe.sig_of([pool, ids, key_valid]),
                         {"bucket": Lb})
        with functional_state(model, params, buffers):
            with no_grad_guard():
                caches = gpt.init_cache(1, Lb, pool.dtype)
                _, caches = gpt.prefill(
                    Tensor(ids, stop_gradient=True), caches,
                    key_valid=key_valid)
                z = jnp.int32(0)
                s = jnp.asarray(slot, jnp.int32).reshape(())
                new_pool = pool
                for li, (ck, cv) in enumerate(caches):
                    kvb = jnp.stack([jnp.swapaxes(ck[0], 0, 1),
                                     jnp.swapaxes(cv[0], 0, 1)])
                    new_pool = lax.dynamic_update_slice(
                        new_pool, kvb[None, :, None].astype(new_pool.dtype),
                        (jnp.int32(li), z, s, z, z, z))
        return new_pool

    return fn


def build_draft_propose_scan_fn(model, num_slots, max_len, spec_k,
                                top_k=0, top_p=1.0, probe=None):
    """The WHOLE draft proposal loop as one compiled program:
    ``lax.scan`` over a one-token draft step (feed the previous proposal,
    write its K/V at the slot's next position of the draft's dense
    per-slot pool, attend over ``[lo, pos]``, pick) — ONE dispatch a
    cycle for ``spec_k`` proposals.

    Returns ``fn(params, buffers, pool, feed_tok, pos, lo, sample_mask,
    temperature, key) -> (pool, proposals [S, spec_k],
    probs [S, spec_k, V], key)``:

    * ``feed_tok [S]`` int32 — each slot's last accepted token (the
      loop's step-0 feed); later steps feed the previous step's
      device-side proposal through the scan carry;
    * step ``j`` writes at position ``min(pos + j, max_len - 1)``;
    * the caller jits with ``donate_argnums`` on ``pool``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..framework import trace_probe as _probe
    from ..nn import functional as F
    from ..nn.layer.layers import functional_state

    gpt = model.gpt if hasattr(model, "gpt") else model
    S = int(num_slots)
    L = int(max_len)
    K = int(spec_k)
    if S < 1:
        raise ValueError(f"num_slots must be >= 1, got {S}")
    if K < 1:
        raise ValueError(f"spec_k must be >= 1, got {K}")
    if L > gpt.cfg.max_position_embeddings:
        raise ValueError(
            f"max_len {L} exceeds max_position_embeddings="
            f"{gpt.cfg.max_position_embeddings}")
    top_k = min(int(top_k), gpt.cfg.vocab_size)

    def fn(params, buffers, pool, feed_tok, pos, lo, sample_mask,
           temperature, key):
        if probe is not None:  # runs at trace time only (jit caches)
            probe.record(_probe.sig_of([pool, feed_tok, pos, lo,
                                        temperature]),
                         {"slots": S, "k": K})
        with functional_state(model, params, buffers):
            with no_grad_guard():
                r = jnp.arange(L)
                sl = jnp.arange(S)

                def step(carry, j):
                    new_pool, feed, key = carry
                    pj = jnp.minimum(pos + j, L - 1)
                    logical = (pj - lo)[:, None]
                    x = gpt.wte(Tensor(feed[:, None],
                                       stop_gradient=True)) \
                        + gpt.wpe(Tensor(logical))
                    key_valid = (r[None, :] >= lo[:, None]) \
                        & (r[None, :] <= pj[:, None])
                    mask = Tensor(key_valid[:, None, None, :])
                    for li, block in enumerate(gpt.blocks):
                        q, k, v = block._qkv(x)
                        kh = k._data[:, 0].astype(new_pool.dtype)
                        vh = v._data[:, 0].astype(new_pool.dtype)
                        new_pool = new_pool.at[
                            li, 0, sl, :, pj, :].set(kh)
                        new_pool = new_pool.at[
                            li, 1, sl, :, pj, :].set(vh)
                        k_full = Tensor(
                            jnp.swapaxes(new_pool[li, 0], 1, 2),
                            stop_gradient=True)
                        v_full = Tensor(
                            jnp.swapaxes(new_pool[li, 1], 1, 2),
                            stop_gradient=True)
                        a = F.scaled_dot_product_attention(
                            q, k_full, v_full, attn_mask=mask)
                        x = block._tail(x, a)
                    x = gpt.ln_f(x)
                    logits = gpt.logits(x)._data[:, 0].astype(
                        jnp.float32)
                    probs = _sample_probs(logits, sample_mask, top_k,
                                          top_p, temperature)
                    key, sub = jax.random.split(key)
                    prop = _categorical_probs(sub, probs)
                    return (new_pool, prop, key), (prop, probs)

                (new_pool, _, key), (props, probs) = lax.scan(
                    step,
                    (pool, jnp.asarray(feed_tok, jnp.int32), key),
                    jnp.arange(K))
        return (new_pool, jnp.swapaxes(props, 0, 1),
                jnp.swapaxes(probs, 0, 1), key)

    return fn


def make_draft_model(model, num_layers=2):
    """Build the default speculative-decoding draft: a GPT with the
    target's config truncated to ``num_layers`` blocks, SHARING the
    target's token/position embeddings (the same ``Parameter`` objects
    — zero extra embedding memory, and the tied LM head stays aligned
    with the target's vocabulary) and initializing its blocks and
    final LayerNorm from the target's first ``num_layers`` blocks —
    the cheapest draft that agrees with the target more often than
    chance. Any user model exposing the same GPT surface (and vocab)
    can be passed to ``GenerationEngine(spec_draft=...)`` instead.
    """
    from dataclasses import replace

    from .gpt import GPTModel

    gpt = model.gpt if hasattr(model, "gpt") else model
    n = int(num_layers)
    if not 1 <= n <= gpt.cfg.num_hidden_layers:
        raise ValueError(
            f"num_layers must be in [1, {gpt.cfg.num_hidden_layers}], "
            f"got {num_layers}")
    draft = GPTModel(replace(gpt.cfg, num_hidden_layers=n))
    draft.wte = gpt.wte            # SHARED parameters, not copies
    draft.wpe = gpt.wpe
    for i in range(n):
        src = dict(gpt.blocks[i].named_parameters())
        for name, p in draft.blocks[i].named_parameters():
            p._data = src[name]._data
    src = dict(gpt.ln_f.named_parameters())
    for name, p in draft.ln_f.named_parameters():
        p._data = src[name]._data
    draft.eval()
    return draft


class _UnsetType:
    """Per-kwarg sentinel for generate(): distinguishes 'not passed'
    from 'explicitly passed its default', so an explicit kwarg always
    conflicts with config= (value comparison silently let config
    override e.g. an explicit temperature=1.0)."""

    def __repr__(self):
        return "<unset>"


_UNSET = _UnsetType()

# signature defaults of generate(), applied when neither the kwarg nor a
# config supplies a value
_GEN_DEFAULTS = {
    "max_new_tokens": 32, "do_sample": False, "temperature": 1.0,
    "top_k": 0, "top_p": 1.0, "eos_token_id": None, "pad_token_id": 0,
    "seed": None, "num_beams": 1, "length_penalty": 0.0,
}


def generate(model, input_ids, max_new_tokens=_UNSET, do_sample=_UNSET,
             temperature=_UNSET, top_k=_UNSET, top_p=_UNSET,
             eos_token_id=_UNSET, pad_token_id=_UNSET, seed=_UNSET,
             num_beams=_UNSET, length_penalty=_UNSET,
             attention_mask=None, config=None):
    """Generate ``max_new_tokens`` continuations of ``input_ids`` [B, S].

    Returns a Tensor [B, S+max_new_tokens]; positions after an
    ``eos_token_id`` are filled with ``pad_token_id``. Ragged prompts are
    supported via ``attention_mask`` [B, S] (1 = real token, 0 = pad):
    prompts must be LEFT-padded so the last column is each example's
    final real token; pads are invisible to attention and position
    embeddings (each example decodes at its own logical positions). A
    ``GenerationConfig`` may be passed as ``config=`` instead of the
    individual kwargs. ``num_beams > 1`` selects compiled beam search
    (deterministic; ``length_penalty`` is the GNMT alpha applied at final
    selection; ragged masks compose with beams).
    """
    import jax
    import jax.numpy as jnp

    from ..nn.layer.layers import get_buffers_tree

    passed = {
        "max_new_tokens": max_new_tokens, "do_sample": do_sample,
        "temperature": temperature, "top_k": top_k, "top_p": top_p,
        "eos_token_id": eos_token_id, "pad_token_id": pad_token_id,
        "seed": seed, "num_beams": num_beams,
        "length_penalty": length_penalty,
    }
    explicit = sorted(k for k, v in passed.items() if v is not _UNSET)
    if config is not None:
        # sentinel check, not value comparison: an explicitly passed
        # default (e.g. temperature=1.0) is a conflict too — silently
        # letting config win would override what the caller wrote
        if explicit:
            raise ValueError(
                f"pass either config= or individual kwargs, not both "
                f"(got config plus {explicit})")
        resolved = {k: getattr(config, k) for k in passed}
    else:
        resolved = {k: (_GEN_DEFAULTS[k] if v is _UNSET else v)
                    for k, v in passed.items()}
    max_new_tokens = resolved["max_new_tokens"]
    do_sample = resolved["do_sample"]
    temperature = resolved["temperature"]
    top_k = resolved["top_k"]
    top_p = resolved["top_p"]
    eos_token_id = resolved["eos_token_id"]
    pad_token_id = resolved["pad_token_id"]
    seed = resolved["seed"]
    num_beams = resolved["num_beams"]
    length_penalty = resolved["length_penalty"]

    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if num_beams > 1:
        if do_sample:
            raise ValueError("num_beams > 1 requires do_sample=False "
                             "(deterministic beam search)")
        ignored = [n for n, c in (("temperature", temperature != 1.0),
                                  ("top_k", top_k != 0),
                                  ("top_p", top_p != 1.0),
                                  ("seed", seed is not None)) if c]
        if ignored:
            raise ValueError(f"{ignored} have no effect with "
                             f"num_beams > 1 (beam search is deterministic)")
    elif length_penalty != 0.0:
        raise ValueError("length_penalty requires num_beams > 1")

    ids = input_ids._data if isinstance(input_ids, Tensor) else \
        jnp.asarray(np.asarray(input_ids))
    if ids.ndim == 1:
        ids = ids[None, :]
    batch, prompt_len = ids.shape
    mask = None
    if attention_mask is not None:
        m = attention_mask._data if isinstance(attention_mask, Tensor) \
            else np.asarray(attention_mask)
        m = np.asarray(m)
        if m.shape != (batch, prompt_len):
            raise ValueError(
                f"attention_mask shape {m.shape} != input_ids shape "
                f"{(batch, prompt_len)}")
        # decode logits come from the LAST prompt column, so real tokens
        # must be right-aligned (left padding, the batched-serve layout)
        if (np.diff(m.astype(np.int8), axis=1) < 0).any():
            raise ValueError(
                "attention_mask must be left-padded (0s then 1s per row)")
        if (m.sum(axis=1) < 1).any():
            raise ValueError("attention_mask has an all-pad row")
        if not m.all():  # an all-ones mask is just the uniform path
            mask = jnp.asarray(m.astype(np.int32))
    if num_beams > 1:
        static_key = ("beam", int(max_new_tokens), int(num_beams),
                      None if eos_token_id is None else int(eos_token_id),
                      int(pad_token_id), float(length_penalty),
                      mask is not None)
        builder = _build_beam_fn
    else:
        static_key = (int(max_new_tokens), bool(do_sample), int(top_k),
                      float(top_p),
                      None if eos_token_id is None else int(eos_token_id),
                      int(pad_token_id), mask is not None)
        builder = _build_generate_fn
    cache = getattr(model, "_generate_fns", None)
    if cache is None:
        cache = model._generate_fns = {}
    fn_key = (batch, prompt_len) + static_key
    if fn_key not in cache:
        cache[fn_key] = builder(
            model, batch, prompt_len,
            static_key[1:] if num_beams > 1 else static_key)
    was_training = model.training
    model.eval()
    try:
        params = {k: p._data for k, p in model.named_parameters()}
        buffers = get_buffers_tree(model)
        if num_beams > 1:
            out = cache[fn_key](params, buffers, ids,
                                jnp.int32(0) if mask is None else mask)
        else:
            if not do_sample:
                # greedy never consumes the key; a fixed one avoids
                # advancing the global generator (would desync seed-pinned
                # experiments)
                key = jax.random.PRNGKey(0)
            elif seed is None:
                # fresh draw per call, controlled by paddle.seed(): an
                # unseeded sampling loop must not return identical
                # "samples" every call
                from ..framework import random as _random
                key = _random.next_key()
                if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
                    # normalize new-style typed keys to the legacy uint32
                    # form so seeded and unseeded calls share ONE program
                    key = jax.random.key_data(key)
            else:
                key = jax.random.PRNGKey(int(seed))
            out = cache[fn_key](params, buffers, ids, key,
                                jnp.float32(temperature),
                                jnp.int32(0) if mask is None else mask)
    finally:
        if was_training:
            model.train()
    return Tensor(out, stop_gradient=True)
