"""Seeded Nemotron-H weights, made on the device ONE BLOCK at a time.

A published block is ONE of a Mamba-2 mixer (``M``, 110 M parameters), an
attention (``*``, 36 M) or a LatentMoE (``E``: 55 M outside the routed
experts and 5.5 M an expert — 759 M with the 128 this chip holds, 1.5 GB
in bfloat16 and 3 GB in float32), so nothing here ever holds the model:
``layer_leaves`` makes the leaves of one block from ``(seed, block)``
alone, an ``E`` block's experts one at a time (``lax.map``), and
``embed``/``final_norm``/``head`` the rest. The program's model is built
from these leaves (``lib/family_nemotron_h.py`` hands them to its
``param_init``) and the plain reference reads the same leaves, block by
block, so neither takes anything the other made. Values are drawn in
float32 and rounded once to the serving dtype; the reference upcasts
those values.

An expert's weights depend on ``(seed, block, expert index)`` only, so a
share that holds experts ``lo .. hi - 1`` has, for each of them, exactly
the values the uncut block has (the shares-add-up test rests on it).

Scales (``model["weight_scales"]``, each listed under ``assumed.weights``
in the configuration file). Every matrix ``[in, out]`` is ``N(0, (gain /
sqrt(in))^2)``; the gains are chosen for conditioning, as PERF.md 33.1
taught — eleven blocks must neither be a chaotic map nor so flat that a
wrong branch moves no token — and read off the float32 reference at the
published widths (PERF.md, PR 50):

* residual: the embedding is ``N(0, embed_std^2)``, a row enters with RMS
  1; norm gains are ``1 + N(0, norm_std^2)``;
* ``M``: ``W_in`` gain 1 on z and x, ``bc_gain`` on B and C, ``dt_gain``
  on dt. What makes a wrong STATE move tokens is the size of the state's
  term ``H C`` of ``y`` against the ``D`` skip's ``D x``: at ``bc_gain``
  3 it is 6x the skip's (at 2: 2.2x; the configuration file gives the
  readings).
  The convolution's taps are ``N(0, 1 / conv_kernel)``, its bias ``N(0,
  norm_std^2)``; ``A_log = log(1 .. heads)``, ``D = 1`` and ``dt_bias``
  the inverse softplus of steps spaced geometrically over
  ``[time_step_min, time_step_max]``, as the family initialises them (the
  three ``time_step_*`` keys do nothing else); ``W_out`` gain ``out_gain``
  on a gated norm's unit rows;
* ``*``: ``W_q`` and ``W_k`` gain ``qk_gain`` 1.5 — a score ``q . k /
  sqrt(128)`` has a spread of 2.25, as on ``sdar`` and ``falcon-h1``;
  ``W_v`` gain 1; ``W_o`` gain ``out_gain``;
* ``E``: the router is ``N(0, (router_gain / sqrt(E))^2)``: sigmoid scores
  of logits of spread 1, the 22 chosen of 512 at 0.80-0.99 and 0.003 apart
  around the twenty-second place; the score-correction bias is ``N(0,
  router_bias_std^2)`` an output with ``router_bias_std`` 0.006, so the
  choice by ``s + b`` is NOT the order of the weights ``s`` (it differs
  from the choice by ``s`` on 3 rows in 4: the reference's test counts
  them at toy size). The chosen weights sum to ``routed_scaling_factor``
  5 whatever the router draws (``norm_topk_prob``), and ``relu(z)^2`` of a unit
  normal has mean 0.5 and RMS 1.22: ``W1`` of the experts and of the
  shared one gain 1, the two latent projections gain 1, and ``W2`` of
  both gain ``down_gain`` 0.4, so that the shared expert adds ~0.5 and the
  22 routed ones together ~0.5 (this chip's quarter of them ~0.26) to a
  residual of RMS 1-2;
* head gain 1: logits of spread ~1.

Linear weights are ``[in, out]``; the router is ``[experts, hidden]``;
the convolution's taps ``[conv_kernel, channels]``; held experts are
stacked ``[held, in, out]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .weights import seed_key

_EMBED, _FINAL, _HEAD, _LAYERS = 0, 1, 2, 3
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


@partial(jax.jit, static_argnames=("shape", "std", "dtype", "mean"))
def _normal(key, shape, std, dtype, mean=0.0):
    """Jitted: the float32 draw of a large array never stands on the
    device beside its rounded copy (PERF.md §7: an eager one stayed)."""
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def pattern(model: dict) -> str:
    """The kinds of the blocks held: the first ``num_hidden_layers``
    characters of the published pattern."""
    return str(model["hybrid_override_pattern"])[
        :int(model["num_hidden_layers"])]


def kind(model: dict, block: int) -> str:
    return pattern(model)[int(block)]


def held_range(model: dict) -> tuple:
    lo, hi = model.get("experts_held", (0, int(model["n_routed_experts"])))
    return int(lo), int(hi)


def dims(model: dict) -> dict:
    Hs, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    return dict(
        E=int(model["hidden_size"]), H=int(model["num_attention_heads"]),
        Hkv=int(model["num_key_value_heads"]), Dh=int(model["head_dim"]),
        D=Hs * P, Hs=Hs, G=int(model["n_groups"]),
        N=int(model["ssm_state_size"]), K=int(model["conv_kernel"]),
        experts=int(model["n_routed_experts"]),
        L=int(model["moe_latent_size"]),
        I=int(model["moe_intermediate_size"]),
        Is=int(model["moe_shared_expert_intermediate_size"]),
        dt_min=float(model.get("time_step_min", 1e-3)),
        dt_max=float(model.get("time_step_max", 1e-1)))


@partial(jax.jit, static_argnames=("dims_", "kind_", "held", "scales",
                                   "dtype"))
def _block(key, *, dims_, kind_, held, scales, dtype):
    d, sc = dict(dims_), dict(scales)
    E = d["E"]
    names = ["norm", "a", "b", "c", "d", "e", "f", "g", "experts"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    lin = lambda name, i, o, gain=1.0: _normal(k[name], (i, o),
                                               gain / i ** 0.5, dtype)
    gains = lambda name, n: _normal(k[name], (n,), sc["norm_std"], dtype, 1.0)
    out = {"norm": gains("norm", E)}
    if kind_ == MAMBA:
        D, Hs, G, N, K = d["D"], d["Hs"], d["G"], d["N"], d["K"]
        # the in-projection's columns by segment: z, x, B, C, dt
        seg_gain = (1.0, 1.0, sc["bc_gain"], sc["bc_gain"], sc["dt_gain"])
        widths = (D, D, G * N, G * N, Hs)
        col_std = jnp.concatenate([
            jnp.full((w,), g / E ** 0.5, jnp.float32)
            for w, g in zip(widths, seg_gain)])
        steps = jnp.exp(jnp.linspace(np.log(d["dt_min"]),
                                     np.log(d["dt_max"]), Hs))
        out.update(
            ssm_in=(col_std[None, :] * jax.random.normal(
                k["a"], (E, sum(widths)), jnp.float32)).astype(dtype),
            conv_w=_normal(k["b"], (K, D + 2 * G * N), K ** -0.5, dtype),
            conv_b=_normal(k["c"], (D + 2 * G * N,), sc["norm_std"], dtype),
            dt_bias=(steps + jnp.log(-jnp.expm1(-steps))).astype(dtype),
            A_log=jnp.log(jnp.arange(1, Hs + 1, dtype=jnp.float32)
                          ).astype(dtype),
            D=jnp.ones((Hs,), dtype),
            ssm_norm=gains("d", D),
            ssm_out=lin("e", D, E, sc["out_gain"]))
    elif kind_ == ATTENTION:
        H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
        out.update(wq=lin("a", E, H * Dh, sc["qk_gain"]),
                   wk=lin("b", E, Hkv * Dh, sc["qk_gain"]),
                   wv=lin("c", E, Hkv * Dh),
                   wo=lin("d", H * Dh, E, sc["out_gain"]))
    else:
        L, I, Is = d["L"], d["I"], d["Is"]
        out.update(
            router=_normal(k["a"], (d["experts"], E),
                           sc["router_gain"] / E ** 0.5, dtype),
            router_bias=_normal(k["b"], (d["experts"],),
                                sc["router_bias_std"], dtype),
            latent_down=lin("c", E, L), latent_up=lin("d", L, E),
            shared_up=lin("e", E, Is),
            shared_down=lin("f", Is, E, sc["down_gain"]))

        def expert(e):
            k1, k2 = jax.random.split(jax.random.fold_in(k["experts"], e))
            return (_normal(k1, (L, I), 1.0 / L ** 0.5, dtype),
                    _normal(k2, (I, L), sc["down_gain"] / I ** 0.5, dtype))

        up, down = jax.lax.map(expert, jnp.arange(held[0], held[1]))
        out.update(experts_up=up, experts_down=down)
    return out


def layer_leaves(seed: int, block: int, model: dict, dtype: str) -> dict:
    """The leaves of block ``block`` of configuration ``model`` (the
    ``model`` group of a configs/*.json file) for ``seed``, in ``dtype``."""
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _LAYERS),
                             int(block))
    return _block(
        key, dims_=tuple(sorted(dims(model).items())),
        kind_=kind(model, block), held=held_range(model),
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
                   (E, int(model["vocab_size"])), 1.0 / E ** 0.5,
                   jnp.dtype(dtype))
