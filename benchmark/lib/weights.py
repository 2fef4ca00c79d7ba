"""Seeded GPT-2 weights, made on the device in one jitted call.

The benchmark owns the weights: the program's model is loaded from this
tree and the plain reference reads the same tree, so neither takes
anything the other made. Values are drawn in float32 and rounded once to
the dtype they are served or trained in (the checkpoint's dtype); the
reference upcasts those same values, so what is compared is the
arithmetic and not a rounding of the weights.

Layout: per-layer leaves are stacked on a leading ``[L]`` axis (the
reference scans over it); linear weights are ``[in, out]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's exceed 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


@partial(jax.jit, static_argnames=(
    "vocab", "hidden", "layers", "inter", "positions", "std", "dtype"))
def _make(key, *, vocab, hidden, layers, inter, positions, std, dtype):
    names = ["wte", "wpe", "ln1_g", "ln1_b", "q_w", "q_b", "k_w", "k_b",
             "v_w", "v_b", "o_w", "o_b", "ln2_g", "ln2_b", "fc_w", "fc_b",
             "proj_w", "proj_b", "lnf_g", "lnf_b"]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    L, E, I = layers, hidden, inter
    resid = std / (2.0 * L) ** 0.5      # GPT-2's scaled residual projections

    def normal(name, shape, scale, mean=0.0):
        x = mean + scale * jax.random.normal(keys[name], shape, jnp.float32)
        return x.astype(dtype)

    blocks = {
        "ln1_g": normal("ln1_g", (L, E), std, 1.0),
        "ln1_b": normal("ln1_b", (L, E), std),
        "q_w": normal("q_w", (L, E, E), std),
        "q_b": normal("q_b", (L, E), std),
        "k_w": normal("k_w", (L, E, E), std),
        "k_b": normal("k_b", (L, E), std),
        "v_w": normal("v_w", (L, E, E), std),
        "v_b": normal("v_b", (L, E), std),
        "o_w": normal("o_w", (L, E, E), resid),
        "o_b": normal("o_b", (L, E), std),
        "ln2_g": normal("ln2_g", (L, E), std, 1.0),
        "ln2_b": normal("ln2_b", (L, E), std),
        "fc_w": normal("fc_w", (L, E, I), std),
        "fc_b": normal("fc_b", (L, I), std),
        "proj_w": normal("proj_w", (L, I, E), resid),
        "proj_b": normal("proj_b", (L, E), std),
    }
    return {"wte": normal("wte", (vocab, E), std),
            "wpe": normal("wpe", (positions, E), std),
            "blocks": blocks,
            "lnf_g": normal("lnf_g", (E,), std, 1.0),
            "lnf_b": normal("lnf_b", (E,), std)}


def make_weights(seed: int, model: dict, dtype: str):
    """The canonical weight tree of configuration ``model`` (the ``model``
    group of a configs/*.json file) for ``seed``, in ``dtype``."""
    return _make(seed_key(seed), vocab=int(model["vocab_size"]),
                 hidden=int(model["hidden_size"]),
                 layers=int(model["num_hidden_layers"]),
                 inter=int(model["intermediate_size"]),
                 positions=int(model["max_position_embeddings"]),
                 std=float(model["weight_std"]),
                 dtype=jnp.dtype(dtype))


# canonical stacked leaf -> the program's per-layer parameter name
_BLOCK_NAMES = {
    "ln1_g": "ln_1.weight", "ln1_b": "ln_1.bias",
    "q_w": "attn.q_proj.weight", "q_b": "attn.q_proj.bias",
    "k_w": "attn.k_proj.weight", "k_b": "attn.k_proj.bias",
    "v_w": "attn.v_proj.weight", "v_b": "attn.v_proj.bias",
    "o_w": "attn.out_proj.weight", "o_b": "attn.out_proj.bias",
    "ln2_g": "ln_2.weight", "ln2_b": "ln_2.bias",
    "fc_w": "mlp_fc.weight", "fc_b": "mlp_fc.bias",
    "proj_w": "mlp_proj.weight", "proj_b": "mlp_proj.bias",
}


def program_state(tree, prefix: str = "gpt.") -> dict:
    """The tree as the program names its parameters
    (``GPTForPretraining.named_parameters()``), unstacked on the device
    in one jitted call."""

    @jax.jit
    def unstack(t):
        out = {prefix + "wte.weight": t["wte"], prefix + "wpe.weight": t["wpe"],
               prefix + "ln_f.weight": t["lnf_g"],
               prefix + "ln_f.bias": t["lnf_b"]}
        layers = t["blocks"]["ln1_g"].shape[0]
        for i in range(layers):
            for leaf, name in _BLOCK_NAMES.items():
                out[f"{prefix}blocks.{i}.{name}"] = t["blocks"][leaf][i]
        return out

    return unstack(tree)


def canonical_leaf_names(layers: int, prefix: str = "gpt.") -> dict:
    """program parameter name -> (canonical leaf, layer index or None):
    how a per-leaf reading of the program's state is lined up with the
    reference's stacked leaves."""
    out = {prefix + "wte.weight": ("wte", None),
           prefix + "wpe.weight": ("wpe", None),
           prefix + "ln_f.weight": ("lnf_g", None),
           prefix + "ln_f.bias": ("lnf_b", None)}
    for i in range(layers):
        for leaf, name in _BLOCK_NAMES.items():
            out[f"{prefix}blocks.{i}.{name}"] = (leaf, i)
    return out
