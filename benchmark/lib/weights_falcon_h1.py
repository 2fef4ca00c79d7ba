"""Seeded Falcon-H1 weights, made on the device ONE LAYER at a time.

At the cell's size a layer is 430 M parameters (0.86 GB in bfloat16, 1.7
in float32) and the embedding and the head 1,337 M each (2.67 GB), so
nothing here holds the whole model, and NOTHING here holds a float32
``[vocab, hidden]``: every array is drawn and rounded inside one jitted
function, the two vocabulary-sized ones a 64th of their rows at a time
(``lax.map``), so what reaches the device's memory is the bfloat16 result
and one block's float32 (PERF.md section 7 had the eager form leave 1.25
GB behind on ``sdar``; here it would be 5.35 GB and the cell would not
fit). ``layer_leaves`` makes the leaves of one layer from ``(seed,
layer)`` alone; the program's model is built from them
(``lib/family_falcon_h1.py`` hands them to its ``param_init``) and the
plain reference reads the same leaves, layer by layer. Values are drawn
in float32 and rounded once to the serving dtype; the reference upcasts
those values.

Scales (``model["weight_scales"]``, listed under ``assumed`` in the
configuration file). The configuration's fourteen multipliers are muP's:
the trained weights they multiply are as much LARGER as the multiplier is
small. So every matrix ``[in, out]`` whose product a multiplier ``m``
scales is ``N(0, (gain / (sqrt(in) m))^2)`` — weight times multiplier
has the spread ``gain / sqrt(in)`` a plain initialisation gives — with
the gains below, chosen for conditioning as PERF.md 33.1 taught (six
layers must neither be a chaotic map nor so flat that a wrong branch
moves no token):

* residual: the embedding is ``N(0, (embed_gain / embedding_multiplier)
  ^2)``, so a row enters with RMS ``embed_gain`` 1; norm gains are ``1 + N(0, norm_std^2)``;
* attention: ``W_q`` and ``W_k`` (times ``key_multiplier``) with gain
  ``qk_gain`` 1.5 — a score ``q . k / sqrt(128)`` has a spread of 2.25,
  as on ``sdar``; ``W_v`` gain 1; ``W_o`` (times
  ``attention_out_multiplier``) gain ``out_gain``;
* mixer: ``W_in`` (times ``ssm_in_multiplier`` and the muP vector's
  segment) gain 1 on z and x, ``bc_gain`` on B and C (the state's term of
  ``y`` against the ``D`` skip), ``dt_gain`` on dt; the convolution's taps
  ``N(0, (1 / sqrt(d_conv))^2)``, its bias ``N(0, norm_std^2)``; ``A_log =
  log(1 .. heads)``, ``D = 1`` and ``dt_bias`` the inverse softplus of
  steps spaced geometrically over ``[1e-3, 1e-1]``, as the family
  initialises them; ``W_out`` (times ``ssm_out_multiplier``) gain
  ``out_gain`` on a gated norm's unit rows;
* FFN: ``W_gate`` (times ``mlp_multipliers[0]``) and ``W_up`` gain 1,
  ``W_down`` (times ``mlp_multipliers[1]``) gain ``down_gain``;
* head (times ``lm_head_multiplier``) gain 1: logits of spread ~1.

Linear weights are ``[in, out]``; the convolution's taps ``[d_conv,
channels]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .weights import seed_key

_EMBED, _FINAL, _HEAD, _LAYERS = 0, 1, 2, 3
ROW_BLOCKS = 64             # the vocabulary-sized arrays are drawn in 64 parts


def _normal(key, shape, std, dtype, mean=0.0):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def dims(model: dict) -> dict:
    return dict(E=int(model["hidden_size"]),
                I=int(model["intermediate_size"]),
                H=int(model["num_attention_heads"]),
                Hkv=int(model["num_key_value_heads"]),
                Dh=int(model["head_dim"]),
                D=int(model["mamba_d_ssm"]), Hs=int(model["mamba_n_heads"]),
                G=int(model["mamba_n_groups"]),
                N=int(model["mamba_d_state"]),
                K=int(model["mamba_d_conv"]))


@partial(jax.jit, static_argnames=("dims_", "mults", "scales", "dtype"))
def _layer(key, *, dims_, mults, scales, dtype):
    d, m, sc = dict(dims_), dict(mults), dict(scales)
    E, I, H, Hkv, Dh = d["E"], d["I"], d["H"], d["Hkv"], d["Dh"]
    D, Hs, G, N, K = d["D"], d["Hs"], d["G"], d["N"], d["K"]
    names = ["attn_norm", "wq", "wk", "wv", "wo", "ssm_in", "conv_w",
             "conv_b", "ssm_norm", "ssm_out", "ffn_norm", "gate", "up",
             "down"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    lin = lambda name, i, o, gain=1.0, mult=1.0: _normal(
        k[name], (i, o), gain / (i ** 0.5 * mult), dtype)
    gains = lambda name, n: _normal(k[name], (n,), sc["norm_std"], dtype, 1.0)
    # the in-projection's columns by segment: z, x, B, C, dt
    seg_gain = (1.0, 1.0, sc["bc_gain"], sc["bc_gain"], sc["dt_gain"])
    widths = (D, D, G * N, G * N, Hs)
    col_std = jnp.concatenate([
        jnp.full((w,), g / (E ** 0.5 * m["ssm_in"] * mv), jnp.float32)
        for w, g, mv in zip(widths, seg_gain, m["ssm"])])
    steps = jnp.exp(jnp.linspace(np.log(1e-3), np.log(1e-1), Hs))
    return {
        "attn_norm": gains("attn_norm", E),
        "wq": lin("wq", E, H * Dh, sc["qk_gain"], m["attention_in"]),
        "wk": lin("wk", E, Hkv * Dh, sc["qk_gain"],
                  m["attention_in"] * m["key"]),
        "wv": lin("wv", E, Hkv * Dh, 1.0, m["attention_in"]),
        "wo": lin("wo", H * Dh, E, sc["out_gain"], m["attention_out"]),
        "ssm_in": (col_std[None, :] * jax.random.normal(
            k["ssm_in"], (E, sum(widths)), jnp.float32)).astype(dtype),
        "conv_w": _normal(k["conv_w"], (K, D + 2 * G * N), K ** -0.5, dtype),
        "conv_b": _normal(k["conv_b"], (D + 2 * G * N,), sc["norm_std"],
                          dtype),
        "dt_bias": (steps + jnp.log(-jnp.expm1(-steps))).astype(dtype),
        "A_log": jnp.log(jnp.arange(1, Hs + 1, dtype=jnp.float32)
                         ).astype(dtype),
        "D": jnp.ones((Hs,), dtype),
        "ssm_norm": gains("ssm_norm", D),
        "ssm_out": lin("ssm_out", D, E, sc["out_gain"], m["ssm_out"]),
        "ffn_norm": gains("ffn_norm", E),
        "gate": lin("gate", E, I, 1.0, m["mlp_gate"]),
        "up": lin("up", E, I),
        "down": lin("down", I, E, sc["down_gain"], m["mlp_down"]),
    }


def multipliers(model: dict) -> dict:
    """The configuration's multipliers under the names ``_layer`` reads."""
    return {"attention_in": float(model["attention_in_multiplier"]),
            "attention_out": float(model["attention_out_multiplier"]),
            "key": float(model["key_multiplier"]),
            "ssm_in": float(model["ssm_in_multiplier"]),
            "ssm_out": float(model["ssm_out_multiplier"]),
            "ssm": tuple(float(v) for v in model["ssm_multipliers"]),
            "mlp_gate": float(model["mlp_multipliers"][0]),
            "mlp_down": float(model["mlp_multipliers"][1])}


def layer_leaves(seed: int, layer: int, model: dict, dtype: str) -> dict:
    """The leaves of layer ``layer`` of configuration ``model`` (the
    ``model`` group of a configs/*.json file) for ``seed``, in ``dtype``."""
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _LAYERS),
                             int(layer))
    return _layer(key, dims_=tuple(sorted(dims(model).items())),
                  mults=tuple(sorted(multipliers(model).items())),
                  scales=tuple(sorted(
                      (k, float(v)) for k, v in
                      model["weight_scales"].items())),
                  dtype=jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("rows", "cols", "std", "dtype"))
def _by_row_blocks(key, *, rows, cols, std, dtype):
    """``[rows, cols]`` of ``N(0, std^2)`` drawn a ``ROW_BLOCKS``-th of the
    rows at a time: block ``i`` depends on ``(key, i)`` only, and no
    float32 array of the whole shape is ever made."""
    n = ROW_BLOCKS if rows % ROW_BLOCKS == 0 else 1
    one = lambda i: _normal(jax.random.fold_in(key, i), (rows // n, cols),
                            std, dtype)
    return jax.lax.map(one, jnp.arange(n)).reshape(rows, cols)


def embed(seed: int, model: dict, dtype: str):
    """``[vocab, hidden]``; a row times ``embedding_multiplier`` has RMS
    ``embed_gain``."""
    return _by_row_blocks(
        jax.random.fold_in(seed_key(seed), _EMBED),
        rows=int(model["vocab_size"]), cols=int(model["hidden_size"]),
        std=float(model["weight_scales"]["embed_gain"])
        / float(model["embedding_multiplier"]), dtype=jnp.dtype(dtype))


def final_norm(seed: int, model: dict, dtype: str):
    return _normal(jax.random.fold_in(seed_key(seed), _FINAL),
                   (int(model["hidden_size"]),),
                   float(model["weight_scales"]["norm_std"]),
                   jnp.dtype(dtype), 1.0)


def head(seed: int, model: dict, dtype: str):
    """``[hidden, vocab]`` (untied)."""
    E = int(model["hidden_size"])
    return _by_row_blocks(
        jax.random.fold_in(seed_key(seed), _HEAD), rows=E,
        cols=int(model["vocab_size"]),
        std=1.0 / (E ** 0.5 * float(model["lm_head_multiplier"])),
        dtype=jnp.dtype(dtype))
