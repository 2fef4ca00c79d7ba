"""Training's comparison with the plain reference, and its control.

The program's first three steps (through the window's own call and feed,
on rows that all differ) are followed by the reference: float32 forward,
``jax.grad``, plain AdamW. Compared, each against a limit from the
configuration file:

* the loss of each step;
* the norm of the first gradient as the optimizer gets it, recovered
  from Adam's first moment after one step (m1 = (1 - beta1) g), by the
  worst leaf;
* the norm of the parameters' change after the three steps, by the worst
  leaf.

The reference runs in blocks of rows (the mean loss is the mean of the
blocks' means), rematerialising each layer, so it fits beside nothing
and after everything.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_gpt2 as R
from . import weights as W


@partial(jax.jit, static_argnames=("heads", "quant", "rows", "hyper"))
def _reference_step(w, m, v, step, ids, labels, *, heads, quant, rows, hyper):
    """One step: the mean loss and gradient over blocks of ``rows`` rows
    (a scan, so one block's activations live at a time), then AdamW."""
    lr, beta1, beta2, eps, wd = hyper
    nb = ids.shape[0] // rows
    blocks = (ids.reshape(nb, rows, -1), labels.reshape(nb, rows, -1))

    def body(acc, xs):
        loss, g = jax.value_and_grad(R.lm_loss)(w, xs[0], xs[1], heads, quant)
        return (acc[0] + loss / nb, jax.tree_util.tree_map(
            lambda a, b: a + b / nb, acc[1], g)), None

    with jax.default_matmul_precision("highest"):
        zero = jax.tree_util.tree_map(jnp.zeros_like, w)
        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0), zero), blocks)
        w, m, v = R.adamw_step(w, grads, m, v, step, lr=lr, beta1=beta1,
                               beta2=beta2, eps=eps, weight_decay=wd)
    return loss, grads, w, m, v


def _stacked_norms(tree) -> dict:
    """{canonical leaf, or leaf[i] for layer i: its norm}."""
    out = {}
    for name in ("wte", "wpe", "lnf_g", "lnf_b"):
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
            tree[name].astype(jnp.float32)))))
    for leaf, stacked in tree["blocks"].items():
        x = jnp.square(stacked.astype(jnp.float32))
        norms = np.asarray(jnp.sqrt(jnp.sum(
            x.reshape(x.shape[0], -1), axis=1)))
        for i, n in enumerate(norms):
            out[f"{leaf}[{i}]"] = float(n)
    return out


def reference_steps(seed: int, model: dict, recipe: dict, batches: list,
                    rows_per_block: int, quant=None) -> dict:
    """``len(batches)`` AdamW steps of the plain reference from the seed's
    weights. Returns the losses, the first gradient's leaf norms and the
    leaf norms of the parameters' change."""
    hyper = tuple(float(recipe[k]) for k in
                  ("lr", "beta1", "beta2", "epsilon", "weight_decay"))
    w0 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        W.make_weights(seed, model, recipe["dtype"]))
    w = w0
    m = v = jax.tree_util.tree_map(jnp.zeros_like, w0)
    losses, grad_norms = [], None
    for step, (ids, labels) in enumerate(batches, start=1):
        if ids.shape[0] % rows_per_block:
            raise ValueError(f"batch {ids.shape[0]} is not a multiple of "
                             f"rows_per_block {rows_per_block}")
        loss, grads, w, m, v = _reference_step(
            w, m, v, step, jnp.asarray(ids), jnp.asarray(labels),
            heads=int(model["num_attention_heads"]), quant=quant,
            rows=rows_per_block, hyper=hyper)
        losses.append(float(loss))
        if step == 1:
            grad_norms = _stacked_norms(grads)
        del grads
    change = jax.tree_util.tree_map(jnp.subtract, w, w0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _stacked_norms(change)}


def program_leaf_norms(arrays: dict, layers: int, prefix: str,
                       scale: float = 1.0, minus: dict = None) -> dict:
    """{canonical leaf name: norm} of the program's per-parameter arrays
    (optionally of ``array - minus[name]``), reduced on the device in one
    call."""
    names = W.canonical_leaf_names(layers, prefix)

    @jax.jit
    def norms(arrs, base):
        out = {}
        for k, a in arrs.items():
            a = a.astype(jnp.float32)
            if base is not None:
                a = a - base[k].astype(jnp.float32)
            out[k] = jnp.sqrt(jnp.sum(jnp.square(a))) * scale
        return out

    got = norms({k: arrays[k] for k in names},
                None if minus is None else {k: minus[k] for k in names})
    out = {}
    for k, (leaf, i) in names.items():
        out[leaf if i is None else f"{leaf}[{i}]"] = float(got[k])
    return out
