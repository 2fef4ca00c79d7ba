"""Seeded LongCat-Flash weights, made on the device ONE SUB-BLOCK at a
time.

A published layer is two sub-blocks (``MLA -> dense FFN``) and the routed
experts the first of them opens a shortcut with: at the cell's size 1,243 M
parameters, 2.5 GB in bfloat16 and 5 GB in float32, so nothing here ever
holds a whole layer, let alone the model: ``layer_leaves`` makes the
leaves of one SUB-BLOCK from ``(seed, sub-block)`` alone — an even one's
hold its published layer's router, bias and held experts too — and
``embed``/``final_norm``/``head`` the rest. The program's model is built
from these leaves (``lib/family_longcat.py`` hands them to its
``param_init``) and the plain reference reads the same leaves, sub-block
by sub-block, so neither takes anything the other made. Values are drawn
in float32 and rounded once to the serving dtype; the reference upcasts
those values.

An expert's weights depend on ``(seed, sub-block, expert index)`` only, so
a share that holds experts ``lo .. hi - 1`` has, for each of them, exactly
the values the uncut layer has (the shares-add-up test rests on it).

Scales (``model["weight_scales"]``, listed under ``assumed`` in the
configuration file): every matrix ``[in, out]`` is ``N(0, (gain /
sqrt(in))^2)``, norm gains are ``1 + N(0, norm_std^2)``, the embedding is
``N(0, embed_std^2)``, and

* ``W_qb`` is drawn at ``qk_gain / q_scale`` and ``W_kvb`` at ``1 /
  kv_scale`` of that — the two MLA multipliers (2 and sqrt(12) at the
  published ranks) exist to undo the low ranks' small products, and with
  unit-gain matrices they would give a score a spread of ~5.7, a softmax
  that is an argmax and eight sub-blocks that are a chaotic map. As
  drawn, ``q`` and ``k_nope``/``v`` have unit RMS after the multipliers
  and a score's spread is ``qk_gain`` (1.5: a few dozen keys of ~1 k
  carry a row);
* ``router_gain``: the router is ``N(0, (router_gain / sqrt(E))^2)``.
  Softmax scores over 768 outputs of logits of spread 1.5 put ~0.25 of
  the mass on the 12 chosen, so their weights ``6 g`` sum to ~1.5 a row:
  the shortcut's sum is of an FFN's size, a third of it the row itself;
* ``router_bias_std``: the score-correction bias ``b`` is ``N(0, std^2)``
  an output. The chosen scores lie 0.011-0.055, ~0.001 apart around the
  twelfth place, so 0.001 reorders the places around it: the choice by
  ``g + b`` differs from the choice by ``g`` on ~7 rows in 10 (a constant
  here; the source steers it with a controller during training).

Linear weights are ``[in, out]``; the router is ``[outputs, hidden]``;
held experts are stacked ``[held, in, out]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .weights import seed_key

_EMBED, _FINAL, _HEAD, _LAYERS = 0, 1, 2, 3


@partial(jax.jit, static_argnames=("shape", "std", "dtype", "mean"))
def _normal(key, shape, std, dtype, mean=0.0):
    """Jitted: the float32 draw of a vocabulary-sized array never stands on
    the device beside its rounded copy (PERF.md §7: an eager one stayed)."""
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def mla_scales(model: dict) -> tuple:
    """``(q_scale, kv_scale)``: the values of the two multipliers the
    config switches on (``assumed``: the public modeling code's)."""
    E = float(model["hidden_size"])
    return ((E / float(model["q_lora_rank"])) ** 0.5
            if model["mla_scale_q_lora"] else 1.0,
            (E / float(model["kv_lora_rank"])) ** 0.5
            if model["mla_scale_kv_lora"] else 1.0)


def _dims(model: dict) -> dict:
    H = int(model["num_attention_heads"])
    q_scale, kv_scale = mla_scales(model)
    return dict(
        E=int(model["hidden_size"]), H=H,
        q_rank=int(model["q_lora_rank"]), kv_rank=int(model["kv_lora_rank"]),
        nope=int(model["qk_nope_head_dim"]), rope=int(model["qk_rope_head_dim"]),
        v=int(model["v_head_dim"]), dense=int(model["ffn_hidden_size"]),
        moe=int(model["moe_intermediate_size"]),
        outputs=int(model["n_routed_experts"]), q_scale=q_scale,
        kv_scale=kv_scale)


@partial(jax.jit, static_argnames=("dims", "opens", "held", "scales",
                                   "dtype"))
def _sub_block(key, *, dims, opens, held, scales, dtype):
    d, sc = dict(dims), dict(scales)
    E, H, gain = d["E"], d["H"], sc["gain"]
    names = ["attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
             "wkv_b", "wo", "ffn_norm", "gate", "up", "down", "router",
             "router_bias", "experts"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    lin = lambda name, i, o, g=gain: _normal(k[name], (i, o), g / i ** 0.5,
                                             dtype)
    gains = lambda name, n: _normal(k[name], (n,), sc["norm_std"], dtype, 1.0)
    out = {
        "attn_norm": gains("attn_norm", E),
        "wq_a": lin("wq_a", E, d["q_rank"]),
        "q_norm": gains("q_norm", d["q_rank"]),
        "wq_b": lin("wq_b", d["q_rank"], H * (d["nope"] + d["rope"]),
                    sc["qk_gain"] / d["q_scale"]),
        "wkv_a": lin("wkv_a", E, d["kv_rank"] + d["rope"]),
        "kv_norm": gains("kv_norm", d["kv_rank"]),
        "wkv_b": lin("wkv_b", d["kv_rank"], H * (d["nope"] + d["v"]),
                     gain / d["kv_scale"]),
        "wo": lin("wo", H * d["v"], E),
        "ffn_norm": gains("ffn_norm", E),
        "gate": lin("gate", E, d["dense"]), "up": lin("up", E, d["dense"]),
        "down": lin("down", d["dense"], E),
    }
    if not opens:
        return out
    I = d["moe"]
    out["router"] = _normal(k["router"], (d["outputs"], E),
                            sc["router_gain"] / E ** 0.5, dtype)
    out["router_bias"] = _normal(k["router_bias"], (d["outputs"],),
                                 sc["router_bias_std"], dtype)

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(k["experts"], e), 3)
        return (_normal(kg, (E, I), gain / E ** 0.5, dtype),
                _normal(ku, (E, I), gain / E ** 0.5, dtype),
                _normal(kd, (I, E), gain / I ** 0.5, dtype))

    g, u, dn = jax.lax.map(expert, jnp.arange(held[0], held[1]))
    out.update(experts_gate=g, experts_up=u, experts_down=dn)
    return out


def real_experts(model: dict) -> int:
    """Experts WITH weights: the router's outputs less the identity ones."""
    return int(model["n_routed_experts"]) - int(model["zero_expert_num"])


def held_range(model: dict) -> tuple:
    lo, hi = model.get("experts_held", (0, real_experts(model)))
    return int(lo), int(hi)


def sub_blocks(model: dict) -> int:
    return 2 * int(model["num_hidden_layers"])


def opens_shortcut(sub_block: int) -> bool:
    """An even sub-block holds its published layer's routed experts."""
    return int(sub_block) % 2 == 0


def layer_leaves(seed: int, sub_block: int, model: dict, dtype: str) -> dict:
    """The leaves of sub-block ``sub_block`` (``2 l + s``) of configuration
    ``model`` (the ``model`` group of a configs/*.json file) for ``seed``,
    in ``dtype``."""
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _LAYERS),
                             int(sub_block))
    return _sub_block(
        key, dims=tuple(sorted(_dims(model).items())),
        opens=opens_shortcut(sub_block), held=held_range(model),
        scales=tuple(sorted((k, float(v))
                            for k, v in model["weight_scales"].items())),
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
