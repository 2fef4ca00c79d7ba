"""Seeded LFM2-MoE weights, made on the device ONE LAYER at a time.

At the cell's size a routed layer is 604 M parameters in its 64 experts
(1.21 GB in bfloat16, 2.4 in float32) and the embedding 134 M, so nothing
here holds the whole model, and NOTHING here holds a float32 ``[vocab,
hidden]`` or a float32 expert stack: every array is drawn and rounded
inside one jitted function, the experts one at a time and the embedding a
64th of its rows at a time (``lax.map``), so what reaches the device's
memory is the bfloat16 result and one block's float32 (PERF.md section 7:
the eager form left 1.25 GB behind on ``sdar``). ``layer_leaves`` makes
the leaves of one layer from ``(seed, layer)`` alone; the program's model
is built from them (``lib/family_lfm2.py`` hands them to its
``param_init``) and the plain reference reads the same leaves, layer by
layer. Values are drawn in float32 and rounded once to the serving dtype;
the reference upcasts those values. The head IS the embedding (tied: one
array).

An expert's weights depend on ``(seed, layer, expert index)`` only.

Scales (``model["weight_scales"]``, listed under ``assumed`` in the
configuration file), chosen for conditioning as PERF.md 33.1 taught (ten
layers must neither be a chaotic map nor so flat that a wrong operator
moves no token). Every matrix ``[in, out]`` is ``N(0, (gain /
sqrt(in))^2)`` with gain 1 unless said:

* residual: the embedding is ``N(0, embed_std^2)`` with ``embed_std``
  0.05. The head is the SAME array, so the logit of the token a row just
  read carries that row's own square: ``sqrt(hidden) embed_std / r``
  logit spreads above the others, ``r`` the last residual's RMS (~1.6
  after ten layers that each add 0.3-0.5). At ``embed_std`` 1 that is 28
  spreads: every row repeats its input and no layer can move a token; at
  0.05 it is ~1.4, a mild pull as trained tied models have. The first
  ``operator_norm`` brings the row to RMS 1 for layer 0. Norm gains are
  ``1 + N(0, norm_std^2)`` — but ``embedding_norm``, the FINAL norm: its
  gain is ``(head_gain / (sqrt(hidden) embed_std)) (1 + N(0,
  norm_std^2))``, so that logits have the spread ``head_gain`` 1 the
  untied heads of the other families give;
* ``conv`` operator: ``W_in`` gain ``conv_in_gain`` on all of B, C and z
  (each of RMS that gain; ``g = B z`` and ``y = C c`` are products of
  them, heavy-tailed as products of Gaussians are); the taps ``N(0, 1 /
  K)`` a channel; ``W_out`` gain ``out_gain``: the operator adds ~0.5 to
  a residual of RMS 1-2;
* attention: the gains of ``q_layernorm`` and ``k_layernorm`` are
  ``qk_gain + N(0, norm_std^2)`` — at 1.5 a score ``q . k / sqrt(64)``
  has a spread of 2.25, as on ``sdar``: a few dozen of thousands of keys
  carry a row; ``W_o`` gain ``out_gain``;
* FFN: the dense ``W_2`` gain ``down_gain``; an expert's down projection
  gain ``expert_gain``; the router ``N(0, (router_gain / sqrt(hidden))
  ^2)`` and ``expert_bias`` ``N(0, expert_bias_std^2)``: sigmoid scores of
  unit-spread logits lie ~0.02 apart around the fourth place of 64, so a
  bias of that spread moves the choice away from the plain top-4 in a
  real share of rows (``tests/test_lfm2.py`` counts it) without emptying
  an expert.

Linear weights are ``[in, out]``; the router ``[experts, hidden]``; the
taps ``[K, channels]``; held experts stacked ``[held, in, out]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .weights import seed_key
# the generic drawers: N(mean, std^2) rounded once, and a [rows, cols]
# array drawn a 64th of its rows at a time
from .weights_falcon_h1 import _by_row_blocks, _normal

_EMBED, _FINAL, _LAYERS = 0, 1, 3
CONV = "conv"


def is_conv(model: dict, layer: int) -> bool:
    return model["layer_types"][int(layer)] == CONV


def is_routed(model: dict, layer: int) -> bool:
    return int(layer) >= int(model["num_dense_layers"])


def held_range(model: dict) -> tuple:
    lo, hi = model.get("experts_held", (0, int(model["num_experts"])))
    return int(lo), int(hi)


def head_dim(model: dict) -> int:
    return int(model.get("head_dim") or int(model["hidden_size"])
               // int(model["num_attention_heads"]))


@partial(jax.jit, static_argnames=("E", "att", "K", "conv", "routed",
                                   "width", "experts", "held", "scales",
                                   "dtype"))
def _layer(key, *, E, att, K, conv, routed, width, experts, held, scales,
           dtype):
    sc = dict(scales)
    H, Hkv, Dh = att
    names = ["operator_norm", "conv_in", "conv_w", "conv_out", "wq", "wk",
             "wv", "q_norm", "k_norm", "wo", "ffn_norm", "router",
             "expert_bias", "experts", "gate", "up", "down"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    lin = lambda name, i, o, g=1.0: _normal(k[name], (i, o), g / i ** 0.5,
                                            dtype)
    gains = lambda name, n, mean=1.0: _normal(k[name], (n,), sc["norm_std"],
                                              dtype, mean)
    out = {"operator_norm": gains("operator_norm", E),
           "ffn_norm": gains("ffn_norm", E)}
    if conv:
        out.update(conv_in=lin("conv_in", E, 3 * E, sc["conv_in_gain"]),
                   conv_w=_normal(k["conv_w"], (K, E), K ** -0.5, dtype),
                   conv_out=lin("conv_out", E, E, sc["out_gain"]))
    else:
        out.update(wq=lin("wq", E, H * Dh), wk=lin("wk", E, Hkv * Dh),
                   wv=lin("wv", E, Hkv * Dh),
                   q_norm=gains("q_norm", Dh, sc["qk_gain"]),
                   k_norm=gains("k_norm", Dh, sc["qk_gain"]),
                   wo=lin("wo", H * Dh, E, sc["out_gain"]))
    if not routed:
        out.update(gate=lin("gate", E, width), up=lin("up", E, width),
                   down=lin("down", width, E, sc["down_gain"]))
        return out
    out["router"] = _normal(k["router"], (experts, E),
                            sc["router_gain"] / E ** 0.5, dtype)
    out["expert_bias"] = _normal(k["expert_bias"], (experts,),
                                 sc["expert_bias_std"], dtype)

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(k["experts"], e), 3)
        return (_normal(kg, (E, width), E ** -0.5, dtype),
                _normal(ku, (E, width), E ** -0.5, dtype),
                _normal(kd, (width, E), sc["expert_gain"] / width ** 0.5,
                        dtype))

    g, u, dn = jax.lax.map(expert, jnp.arange(held[0], held[1]))
    out.update(experts_gate=g, experts_up=u, experts_down=dn)
    return out


def layer_leaves(seed: int, layer: int, model: dict, dtype: str) -> dict:
    """The leaves of layer ``layer`` of configuration ``model`` (the
    ``model`` group of a configs/*.json file) for ``seed``, in ``dtype``."""
    routed = is_routed(model, layer)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _LAYERS),
                             int(layer))
    return _layer(
        key, E=int(model["hidden_size"]),
        att=(int(model["num_attention_heads"]),
             int(model["num_key_value_heads"]), head_dim(model)),
        K=int(model["conv_L_cache"]), conv=is_conv(model, layer),
        routed=routed,
        width=int(model["moe_intermediate_size" if routed
                        else "intermediate_size"]),
        experts=int(model["num_experts"]), held=held_range(model),
        scales=tuple(sorted((k, float(v)) for k, v in
                            model["weight_scales"].items())),
        dtype=jnp.dtype(dtype))


def embed(seed: int, model: dict, dtype: str):
    """``[vocab, hidden]``: the embedding AND the head."""
    return _by_row_blocks(
        jax.random.fold_in(seed_key(seed), _EMBED),
        rows=int(model["vocab_size"]), cols=int(model["hidden_size"]),
        std=float(model["weight_scales"]["embed_std"]),
        dtype=jnp.dtype(dtype))


def final_norm(seed: int, model: dict, dtype: str):
    """``embedding_norm``: gains around ``head_gain / sqrt(hidden)`` (the
    tied head's scale: module doc)."""
    sc, E = model["weight_scales"], int(model["hidden_size"])
    mean = float(sc["head_gain"]) / (E ** 0.5 * float(sc["embed_std"]))
    return _normal(jax.random.fold_in(seed_key(seed), _FINAL), (E,),
                   float(sc["norm_std"]) * mean, jnp.dtype(dtype), mean)
