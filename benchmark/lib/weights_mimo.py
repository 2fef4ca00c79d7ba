"""Seeded MiMo-V2-Flash weights, made on the device ONE LAYER at a time.

At the cell's size a window layer with 16 held experts is 498 M
parameters, 1.0 GB in bfloat16 and 2.0 GB in float32, so nothing here
ever holds the whole model: ``layer_leaves`` makes the leaves of one
layer from ``(seed, layer)`` alone, and ``embed``/``final_norm``/``head``
the rest. The program's model is built from these leaves
(``lib/family_mimo.py`` hands them to its ``param_init``) and the plain
reference reads the same leaves, layer by layer, so neither takes
anything the other made. Values are drawn in float32 and rounded once to
the serving dtype; the reference upcasts those values.

An expert's weights depend on ``(seed, layer, expert index)`` only, so a
share that holds experts ``lo .. hi - 1`` has, for each of them, exactly
the values the whole layer has (the shares-add-up test rests on it).

Scales (``model["weight_scales"]``, listed under ``assumed`` in the
configuration file): every matrix ``[in, out]`` is ``N(0, (gain /
sqrt(in))^2)``, norm gains are ``1 + N(0, norm_std^2)``, the embedding is
``N(0, embed_std^2)``. The rest make the NEW mathematics decide the
token without making seven layers a chaotic map (PERF.md 33.1):

* ``qk_gain`` / ``swa_qk_gain``: ``W_q`` and ``W_k`` of a global / window
  layer are ``N(0, (that gain / sqrt(in))^2)`` — there is no q/k norm to
  carry it — so a score ``q . k / sqrt(192)`` has a spread of the gain
  squared. A global layer averages over thousands of keys and needs a
  sharper softmax to say anything (1.5: spread 2.25, a few dozen keys
  carry the row); a window layer has 128 keys and a softer one (1.2:
  spread 1.44, ~16 keys) leaves its output a vector of RMS ~0.2 without
  tripling every difference in q and k.
* ``sink_mean`` / ``sink_std``: a window layer's sink logits are ``N(mean,
  std^2)`` a head. The 128 keys' ``sum exp(s)`` is ~e^5.9 at spread 1.44,
  so sinks around 4.5 take 10-45% of the mass: leave them out and every
  window layer's output grows by that share.
* ``router_bias_std``: the score-correction bias is ``N(0, std^2)`` an
  expert. Sigmoid scores of unit-spread logits lie ~0.007 apart around
  the eighth place of 256, so 0.02 reorders the places around it in most
  rows: the choice by ``s + b`` differs from the choice by ``s``.

Linear weights are ``[in, out]``; the router is ``[experts, hidden]``;
held experts are stacked ``[held, in, out]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .weights import seed_key

_EMBED, _FINAL, _HEAD, _LAYERS = 0, 1, 2, 3


def _normal(key, shape, std, dtype, mean=0.0):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def is_window(model: dict, layer: int) -> bool:
    return int(model["hybrid_layer_pattern"][layer]) == 1


def is_routed(model: dict, layer: int) -> bool:
    return int(layer) >= int(model["first_k_dense_replace"])


def attention_dims(model: dict, window: bool) -> tuple:
    """``(query heads, KV heads, K lanes, V lanes)`` of a layer kind."""
    p = "swa_" if window else ""
    return (int(model[p + "num_attention_heads"]),
            int(model[p + "num_key_value_heads"]),
            int(model[p + "head_dim"]), int(model[p + "v_head_dim"]))


@partial(jax.jit, static_argnames=("E", "att", "window", "routed", "width",
                                   "experts", "held", "scales", "dtype"))
def _layer(key, *, E, att, window, routed, width, experts, held, scales,
           dtype):
    sc = dict(scales)
    H, Hkv, Dk, Dv = att
    gain = sc["gain"]
    names = ["attn_norm", "wq", "wk", "wv", "wo", "sink", "ffn_norm",
             "router", "router_bias", "experts", "gate", "up", "down"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    lin = lambda name, i, o, g=gain: _normal(k[name], (i, o), g / i ** 0.5,
                                             dtype)
    gains = lambda name, n: _normal(k[name], (n,), sc["norm_std"], dtype, 1.0)
    qk = sc["swa_qk_gain"] if window else sc["qk_gain"]
    out = {
        "attn_norm": gains("attn_norm", E),
        "wq": lin("wq", E, H * Dk, qk), "wk": lin("wk", E, Hkv * Dk, qk),
        "wv": lin("wv", E, Hkv * Dv), "wo": lin("wo", H * Dv, E),
        "ffn_norm": gains("ffn_norm", E),
    }
    if window:
        out["sink"] = _normal(k["sink"], (H,), sc["sink_std"], dtype,
                              sc["sink_mean"])
    if not routed:
        out.update(gate=lin("gate", E, width), up=lin("up", E, width),
                   down=lin("down", width, E))
        return out
    out["router"] = _normal(k["router"], (experts, E),
                            sc["router_gain"] / E ** 0.5, dtype)
    out["router_bias"] = _normal(k["router_bias"], (experts,),
                                 sc["router_bias_std"], dtype)

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(k["experts"], e), 3)
        return (_normal(kg, (E, width), gain / E ** 0.5, dtype),
                _normal(ku, (E, width), gain / E ** 0.5, dtype),
                _normal(kd, (width, E), sc["expert_gain"] / width ** 0.5,
                        dtype))

    g, u, dn = jax.lax.map(expert, jnp.arange(held[0], held[1]))
    out.update(experts_gate=g, experts_up=u, experts_down=dn)
    return out


def held_range(model: dict) -> tuple:
    lo, hi = model.get("experts_held", (0, int(model["n_routed_experts"])))
    return int(lo), int(hi)


def layer_leaves(seed: int, layer: int, model: dict, dtype: str) -> dict:
    """The leaves of layer ``layer`` of configuration ``model`` (the
    ``model`` group of a configs/*.json file) for ``seed``, in ``dtype``."""
    window, routed = is_window(model, layer), is_routed(model, layer)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _LAYERS),
                             int(layer))
    return _layer(
        key, E=int(model["hidden_size"]), att=attention_dims(model, window),
        window=window, routed=routed,
        width=int(model["moe_intermediate_size" if routed
                        else "intermediate_size"]),
        experts=int(model["n_routed_experts"]), held=held_range(model),
        scales=tuple(sorted((k, float(v)) for k, v in
                            model["weight_scales"].items())),
        dtype=jnp.dtype(dtype))


def embed(seed: int, model: dict, dtype: str):
    """``[vocab, hidden]``."""
    return _normal(jax.random.fold_in(seed_key(seed), _EMBED),
                   (int(model["vocab_size"]), int(model["hidden_size"])),
                   float(model["weight_scales"]["embed_std"]),
                   jnp.dtype(dtype))


def final_norm(seed: int, model: dict, dtype: str):
    return _normal(jax.random.fold_in(seed_key(seed), _FINAL),
                   (int(model["hidden_size"]),),
                   float(model["weight_scales"]["norm_std"]),
                   jnp.dtype(dtype), 1.0)


def head(seed: int, model: dict, dtype: str):
    """``[hidden, vocab]`` (untied)."""
    E = int(model["hidden_size"])
    return _normal(jax.random.fold_in(seed_key(seed), _HEAD),
                   (E, int(model["vocab_size"])),
                   float(model["weight_scales"]["gain"]) / E ** 0.5,
                   jnp.dtype(dtype))
