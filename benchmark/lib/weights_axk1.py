"""Seeded A.X-K1 weights, made on the device ONE LAYER at a time.

At the cell's size the weights are 9.7 GB in bfloat16 and 19.4 GB in
float32, so nothing here ever holds the whole model: ``layer_leaves``
makes the leaves of one layer from ``(seed, layer)`` alone, and
``embed``/``final``/``head`` the rest. The program's model is built from
these leaves (``lib/family_axk1.py`` hands them to its ``param_init``)
and the plain reference reads the same leaves, layer by layer, so
neither takes anything the other made. Values are drawn in float32 and
rounded once to the serving dtype; the reference upcasts those values.

An expert's weights depend on ``(seed, layer, expert index)`` only, so a
share that holds experts ``lo .. hi - 1`` has, for each of them, exactly
the values the uncut model has (the shares-add-up test rests on it).

Scales (``model["weight_scales"]``, listed under ``assumed`` in the
configuration file): every matrix ``[in, out]`` is ``N(0, (gain /
sqrt(in))^2)`` so each projection keeps a unit-RMS input at about unit
RMS, the router included (sigmoid scores then spread over about 0.25 to
0.75); norm gains are ``1 + N(0, norm_std^2)``; the embedding is ``N(0,
embed_std^2)``. With ``embed_std`` 1 and seven layers each adding a
vector of RMS about 0.6, the blocks and not the embedding decide the next
token (PERF.md finding 26.2), and the head is untied besides.

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
    H = int(model["num_attention_heads"])
    return dict(
        E=int(model["hidden_size"]), H=H,
        q_rank=int(model["q_lora_rank"]), kv_rank=int(model["kv_lora_rank"]),
        nope=int(model["qk_nope_head_dim"]), rope=int(model["qk_rope_head_dim"]),
        v=int(model["v_head_dim"]), dense=int(model["intermediate_size"]),
        moe=int(model["moe_intermediate_size"]),
        experts=int(model["n_routed_experts"]))


@partial(jax.jit, static_argnames=("dims", "routed", "held", "gain",
                                   "norm_std", "dtype"))
def _layer(key, *, dims, routed, held, gain, norm_std, dtype):
    d = dict(dims)
    E, H = d["E"], d["H"]
    names = ["attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
             "wkv_b", "wo", "ffn_norm", "gate", "up", "down", "router",
             "shared_gate", "shared_up", "shared_down", "experts"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    lin = lambda name, i, o: _normal(k[name], (i, o), gain / i ** 0.5, dtype)
    gains = lambda name, n: _normal(k[name], (n,), norm_std, dtype, 1.0)
    out = {
        "attn_norm": gains("attn_norm", E),
        "wq_a": lin("wq_a", E, d["q_rank"]),
        "q_norm": gains("q_norm", d["q_rank"]),
        "wq_b": lin("wq_b", d["q_rank"], H * (d["nope"] + d["rope"])),
        "wkv_a": lin("wkv_a", E, d["kv_rank"] + d["rope"]),
        "kv_norm": gains("kv_norm", d["kv_rank"]),
        "wkv_b": lin("wkv_b", d["kv_rank"], H * (d["nope"] + d["v"])),
        "wo": lin("wo", H * d["v"], E),
        "ffn_norm": gains("ffn_norm", E),
    }
    if not routed:
        out.update(gate=lin("gate", E, d["dense"]), up=lin("up", E, d["dense"]),
                   down=lin("down", d["dense"], E))
        return out
    I = d["moe"]
    out["router"] = _normal(k["router"], (d["experts"], E), gain / E ** 0.5,
                            dtype)
    out.update(shared_gate=lin("shared_gate", E, I),
               shared_up=lin("shared_up", E, I),
               shared_down=lin("shared_down", I, E))

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(k["experts"], e), 3)
        return (_normal(kg, (E, I), gain / E ** 0.5, dtype),
                _normal(ku, (E, I), gain / E ** 0.5, dtype),
                _normal(kd, (I, E), gain / I ** 0.5, dtype))

    g, u, dn = jax.lax.map(expert, jnp.arange(held[0], held[1]))
    out.update(experts_gate=g, experts_up=u, experts_down=dn)
    return out


def held_range(model: dict) -> tuple:
    lo, hi = model.get("experts_held", (0, int(model["n_routed_experts"])))
    return int(lo), int(hi)


def is_routed(model: dict, layer: int) -> bool:
    return int(layer) >= int(model["first_k_dense_replace"])


def layer_leaves(seed: int, layer: int, model: dict, dtype: str) -> dict:
    """The leaves of layer ``layer`` of configuration ``model`` (the
    ``model`` group of a configs/*.json file) for ``seed``, in ``dtype``."""
    sc = model["weight_scales"]
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _LAYERS),
                             int(layer))
    return _layer(key, dims=tuple(sorted(_dims(model).items())),
                  routed=is_routed(model, layer), held=held_range(model),
                  gain=float(sc["gain"]), norm_std=float(sc["norm_std"]),
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
