"""The plain reference: GPT-2 (Radford et al. 2019) in straightforward
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``.

No kernel, no cache, no paging, no batching policy, and nothing imported
from the program. Pre-LN blocks, learned positions, tanh-GELU, a head
tied to the token embedding, causal softmax attention with 1/sqrt(d).
Weights are the canonical tree of ``lib/weights.py`` (upcast here).

``quant`` computes every linear layer (the tied head included) in a
lower precision, for the CONTROL that a cell's limits must reject:
``"int8"`` rounds weights per output channel and activations per row to
symmetric 8-bit integers (serving's W8A8); ``"fp8"`` is fp8 training as
published (Micikevicius et al. 2022): e4m3 operands forward, e5m2
gradients backward, one scale per tensor; ``"bf16"`` rounds operands to
bfloat16. The benchmark's own runs never set it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _round_int8(x, axis):
    """Symmetric 8-bit rounding along ``axis``; the gradient passes
    straight through (the control for training differentiates it)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(x / scale).clip(-127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _round_fp8(x, dtype):
    """Round to an fp8 ``dtype`` under one scale per tensor."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x)) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_matmul(x, w):
    return jnp.matmul(_round_fp8(x, jnp.float8_e4m3fn),
                      _round_fp8(w, jnp.float8_e4m3fn))


def _fp8_fwd(x, w):
    xq = _round_fp8(x, jnp.float8_e4m3fn)
    wq = _round_fp8(w, jnp.float8_e4m3fn)
    return jnp.matmul(xq, wq), (xq, wq)


def _fp8_bwd(res, dy):
    xq, wq = res
    dyq = _round_fp8(dy, jnp.float8_e5m2)
    dx = jnp.matmul(dyq, wq.T)
    dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                    dyq.reshape(-1, dyq.shape[-1]))
    return dx, dw


_fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def _linear(x, w, b, quant):
    """``x @ w + b`` with ``w`` [in, out]."""
    if quant == "int8":
        x = _round_int8(x, axis=-1)          # per row (token)
        w = _round_int8(w, axis=0)           # per output channel
    elif quant == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        y = _fp8_matmul(x, w)
        return y if b is None else y + b
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    y = jnp.matmul(x, w)
    return y if b is None else y + b


def _layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _block(x, lw, heads, quant):
    B, S, E = x.shape
    D = E // heads
    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    q = _linear(h, lw["q_w"], lw["q_b"], quant).reshape(B, S, heads, D)
    k = _linear(h, lw["k_w"], lw["k_b"], quant).reshape(B, S, heads, D)
    v = _linear(h, lw["v_w"], lw["v_b"], quant).reshape(B, S, heads, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, E)
    x = x + _linear(a, lw["o_w"], lw["o_b"], quant)
    h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    m = _gelu_tanh(_linear(h, lw["fc_w"], lw["fc_b"], quant))
    return x + _linear(m, lw["proj_w"], lw["proj_b"], quant)


def hidden_states(w, ids, heads: int, quant=None, remat: bool = False):
    """Final-LayerNorm hidden states ``[B, S, E]`` of token ids
    ``[B, S]`` (float32)."""
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    S = ids.shape[1]
    x = f32(w["wte"])[ids] + f32(w["wpe"])[:S][None]

    def body(x, lw):
        return _block(x, f32(lw), heads, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["blocks"])
    return _layer_norm(x, f32(w["lnf_g"]), f32(w["lnf_b"]))


def logits_of(w, hidden, quant=None):
    return _linear(hidden, w["wte"].astype(jnp.float32).T, None, quant)


@partial(jax.jit, static_argnames=("heads", "quant"))
def served_margins(w, ids, positions, served, *, heads, quant=None):
    """Teacher-forced margins of served text.

    ``ids`` [B, S] holds prompt + served tokens, right-padded (a causal
    model never looks right); ``positions`` [B, n] are the positions
    whose logits PREDICT each served token (its index minus one) and
    ``served`` [B, n] those tokens. Returns, per served token: how far
    its reference logit lies below the reference's best (``gap``, 0 for
    the reference's own choice), that row's logit standard deviation,
    and — with ``quant`` — the same gap for the token the LOWER
    precision puts first at that position (``control_gap``).
    """
    with jax.default_matmul_precision("highest"):
        rows = jnp.arange(ids.shape[0])[:, None]
        hid = hidden_states(w, ids, heads)[rows, positions]
        logits = logits_of(w, hid)                       # [B, n, V]
        top = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
        out = {"gap": top - got, "std": jnp.std(logits, axis=-1),
               "argmax": jnp.argmax(logits, axis=-1)}
        if quant is not None:
            hid_c = hidden_states(w, ids, heads, quant)[rows, positions]
            first = jnp.argmax(logits_of(w, hid_c, quant), axis=-1)
            got_c = jnp.take_along_axis(logits, first[..., None],
                                        axis=-1)[..., 0]
            out["control_gap"] = top - got_c
        return out


def lm_loss(w, ids, labels, heads: int, quant=None):
    """Mean next-token cross-entropy of ``ids`` [B, S] against
    ``labels`` [B, S] (already shifted)."""
    hid = hidden_states(w, ids, heads, quant, remat=True)
    logits = logits_of(w, hid, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def adamw_step(params, grads, m, v, step, *, lr, beta1=0.9, beta2=0.999,
               eps=1e-8, weight_decay=0.01):
    """One AdamW update (Loshchilov & Hutter 2019: decay decoupled from
    the gradient, applied to every leaf), all in float32."""
    t = jnp.float32(step)

    def leaf(p, g, m, v):
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        mhat = m / (1 - beta1 ** t)
        vhat = v / (1 - beta2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p), m, v

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)
