"""The comparison that decides ``correct``.

Serving: once the window has closed, a sample (drawn from the seed, the
longest in it) of the requests the window finished goes through the
plain reference ONCE, teacher-forced over prompt + served tokens. For
each served token we read how far its reference logit lies below the
reference's best, in units of that row's logit standard deviation. A
greedy engine that rounds differently picks near-ties (small gaps); a
wrong kernel, a wrong cache row or a lower precision picks tokens the
reference ranks clearly lower. Two numbers are held to limits from the
configuration file: the MEAN gap (steady from seed to seed) and the
WIDEST gap (swings by nature, so its limit is gross).

Training: see ``train_check``.
"""
from __future__ import annotations

import numpy as np

from . import traffic as T


def pick_sample(records: list, seed: int, count: int) -> list:
    """``count`` finished requests, the longest (prompt + served) first,
    the rest drawn from the seed."""
    done = sorted(records, key=lambda r: r["index"])
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                       -r["index"]))
    rest = [r for r in done if r is not longest]
    rng = T.seed_rng(seed, 5)
    take = rng.permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def served_gaps(weights, heads: int, sample: list, seed: int, vocab: int,
                width: int, rows_per_call: int, quant=None) -> dict:
    """Normalised gaps of every served token of ``sample``, through
    ``reference_gpt2.served_margins`` in blocks of ``rows_per_call``
    sequences of ``width`` positions (one compiled shape). With
    ``quant`` also the control's gaps."""
    import jax.numpy as jnp
    from . import reference_gpt2 as R
    n_max = max(len(r["tokens"]) for r in sample)
    n_pad = -(-n_max // 64) * 64
    gaps, control = [], []
    for i in range(0, len(sample), rows_per_call):
        block = sample[i:i + rows_per_call]
        B = rows_per_call
        ids = np.zeros((B, width), np.int32)
        pos = np.zeros((B, n_pad), np.int32)
        served = np.zeros((B, n_pad), np.int32)
        valid = np.zeros((B, n_pad), bool)
        for b, r in enumerate(block):
            prompt = T.prompt_tokens(seed, r["index"], r["prompt_len"], vocab)
            toks = r["tokens"]
            text = prompt + toks
            if len(text) > width:
                raise ValueError(f"request {r['index']}: {len(text)} tokens "
                                 f"exceed the reference width {width}")
            ids[b, :len(text)] = text
            n = len(toks)
            pos[b, :n] = len(prompt) - 1 + np.arange(n)
            served[b, :n] = toks
            valid[b, :n] = True
        out = R.served_margins(weights, jnp.asarray(ids), jnp.asarray(pos),
                               jnp.asarray(served), heads=heads, quant=quant)
        std = np.asarray(out["std"])
        gaps.append((np.asarray(out["gap"]) / std)[valid])
        if quant is not None:
            control.append((np.asarray(out["control_gap"]) / std)[valid])
    res = {"gaps": np.concatenate(gaps)}
    if quant is not None:
        res["control_gaps"] = np.concatenate(control)
    return res


def gap_summary(gaps: np.ndarray) -> dict:
    return {"tokens": int(gaps.size), "mean_gap": float(np.mean(gaps)),
            "max_gap": float(np.max(gaps)),
            "not_argmax_share": float(np.mean(gaps > 0))}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, lines): every number in ``limits`` printed beside its
    limit; correct only if each is at or under it."""
    ok, lines = True, []
    for name, limit in limits.items():
        value = numbers[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok &= good
        lines.append(f"check {name}: {value:.6g} (limit {limit:.6g}) "
                     f"{'ok' if good else 'FAILED'}")
    return ok, lines


def window_requests_ok(records: list, vocab: int) -> tuple:
    """What timing cannot change: every request that finished did so
    with exactly the tokens it asked for, all inside the vocabulary.
    Returns (ok, problems)."""
    problems = []
    for r in records:
        if r["done"] is None:
            continue
        if len(r["tokens"]) != r["max_tokens"] or r["finish"] != "length":
            problems.append(f"request {r['index']}: {len(r['tokens'])} of "
                            f"{r['max_tokens']} tokens, finish {r['finish']!r}")
        elif not all(0 <= t < vocab for t in r["tokens"]):
            problems.append(f"request {r['index']}: token outside [0, {vocab})")
    return not problems, problems


# -- training ---------------------------------------------------------------

def live_leaves(grad_norms: dict, floor: float = 1e-3) -> list:
    """Leaves the loss depends on: reference gradient norm at least
    ``floor`` of the median leaf's. A key bias shifts every score of a
    row alike and softmax cancels it, so its gradient is rounding noise —
    which Adam's m/sqrt(v) turns into a full-size update in any
    precision. Such a leaf's UPDATE says nothing about the step."""
    med = float(np.median(list(grad_norms.values())))
    return sorted(n for n, v in grad_norms.items() if v >= floor * med)


def worst_leaf_gap(program: dict, reference: dict, names=None) -> float:
    """Worst leaf of |program norm - reference norm| over the larger of
    the reference's norm of that leaf and of the median leaf (some
    gradients are all but zero)."""
    names = sorted(reference) if names is None else list(names)
    ref = np.asarray([reference[n] for n in names], np.float64)
    got = np.asarray([program[n] for n in names], np.float64)
    floor = np.median(ref)
    return float(np.max(np.abs(got - ref) / np.maximum(ref, floor)))
