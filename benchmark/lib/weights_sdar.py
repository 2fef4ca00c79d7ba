"""Seeded SDAR-MoE weights, made on the device ONE LAYER at a time.

At the cell's size a layer is 623 M parameters (128 experts of 4.7 M),
1.25 GB in bfloat16 and 2.5 GB in float32, so nothing here ever holds the
whole model: ``layer_leaves`` makes the leaves of one layer from ``(seed,
layer)`` alone, and ``embed``/``final_norm``/``head`` the rest. The
program's model is built from these leaves (``lib/family_sdar.py`` hands
them to its ``param_init``) and the plain reference reads the same
leaves, layer by layer, so neither takes anything the other made. Values
are drawn in float32 and rounded once to the serving dtype; the reference
upcasts those values.

An expert's weights depend on ``(seed, layer, expert index)`` only, so a
share that holds experts ``lo .. hi - 1`` has, for each of them, exactly
the values the whole layer has (the shares-add-up test rests on it).

Scales (``model["weight_scales"]``, listed under ``assumed`` in the
configuration file): every matrix ``[in, out]`` is ``N(0, (gain /
sqrt(in))^2)``, norm gains are ``1 + N(0, norm_std^2)``, the embedding is
``N(0, embed_std^2)``. Three more are there because six layers of sharp
softmax attention and top-8-of-128 routing at unit scales are a CHAOTIC
map — a difference of 0.2% (bfloat16's rounding) after one layer is one of
40% after six, and the float32 reference then says nothing about a
bfloat16 program (PERF.md finding 33.1). ``qk_gain``: the gains of
``q_norm`` and ``k_norm`` are ``qk_gain + N(0, norm_std^2)``; at 1.5 a score
``q . k / sqrt(128)`` has a spread of 2.25, the attention output is a
vector of RMS ~0.3 (unit gains over a thousand keys give a near-uniform
average of RMS 0.03 that no check could see go wrong; 2 gives a softmax
on a handful of keys that triples every difference in q and k).
``router_gain``: the router is ``N(0, (router_gain / sqrt(hidden))^2)``;
at 2 the eighth expert's renormalised weight is ~4%, so a flip at the
eighth place moves little, and the weights are not yet so peaked that
they amplify a difference themselves. ``expert_gain``: the experts' down
projections are ``N(0, (expert_gain / sqrt(width))^2)``; at 0.5 an expert
layer adds a vector of RMS ~0.2 to a residual of RMS ~1, so what a flip
changes is a tenth of that. Every masked position of a block enters with
the SAME embedding (the mask id's); six layers adding ~0.35 each decide
which token comes out of it, and the head is untied.

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


def _dims(model: dict) -> dict:
    return dict(E=int(model["hidden_size"]),
                H=int(model["num_attention_heads"]),
                Hkv=int(model["num_key_value_heads"]),
                Dh=int(model["head_dim"]),
                moe=int(model["moe_intermediate_size"]),
                experts=int(model["n_routed_experts"]))


@partial(jax.jit, static_argnames=("dims", "held", "gain", "norm_std",
                                   "qk_gain", "router_gain", "expert_gain",
                                   "dtype"))
def _layer(key, *, dims, held, gain, norm_std, qk_gain, router_gain,
           expert_gain, dtype):
    d = dict(dims)
    E, H, Hkv, Dh, I = d["E"], d["H"], d["Hkv"], d["Dh"], d["moe"]
    names = ["attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
             "ffn_norm", "router", "experts"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    lin = lambda name, i, o: _normal(k[name], (i, o), gain / i ** 0.5, dtype)
    gains = lambda name, n, mean=1.0: _normal(k[name], (n,), norm_std,
                                              dtype, mean)
    out = {
        "attn_norm": gains("attn_norm", E),
        "wq": lin("wq", E, H * Dh), "wk": lin("wk", E, Hkv * Dh),
        "wv": lin("wv", E, Hkv * Dh),
        "q_norm": gains("q_norm", Dh, qk_gain),
        "k_norm": gains("k_norm", Dh, qk_gain),
        "wo": lin("wo", H * Dh, E),
        "ffn_norm": gains("ffn_norm", E),
        "router": _normal(k["router"], (d["experts"], E),
                          router_gain / E ** 0.5, dtype),
    }

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(k["experts"], e), 3)
        return (_normal(kg, (E, I), gain / E ** 0.5, dtype),
                _normal(ku, (E, I), gain / E ** 0.5, dtype),
                _normal(kd, (I, E), expert_gain / I ** 0.5, dtype))

    g, u, dn = jax.lax.map(expert, jnp.arange(held[0], held[1]))
    out.update(experts_gate=g, experts_up=u, experts_down=dn)
    return out


def held_range(model: dict) -> tuple:
    lo, hi = model.get("experts_held", (0, int(model["n_routed_experts"])))
    return int(lo), int(hi)


def layer_leaves(seed: int, layer: int, model: dict, dtype: str) -> dict:
    """The leaves of layer ``layer`` of configuration ``model`` (the
    ``model`` group of a configs/*.json file) for ``seed``, in ``dtype``."""
    sc = model["weight_scales"]
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _LAYERS),
                             int(layer))
    return _layer(key, dims=tuple(sorted(_dims(model).items())),
                  held=held_range(model), gain=float(sc["gain"]),
                  norm_std=float(sc["norm_std"]),
                  qk_gain=float(sc["qk_gain"]),
                  router_gain=float(sc["router_gain"]),
                  expert_gain=float(sc["expert_gain"]),
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
