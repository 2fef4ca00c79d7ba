"""The one general traffic generator: reads a mix's parameters
(``traffic/<name>.json``) and a seed, returns what to send and when.

numpy only — the load-generator child imports this and never jax.

Every seed gets the SAME set of sizes and gaps in another order: lengths
are the mid-quantiles of the stated distribution and inter-arrival gaps
the mid-quantiles of the exponential, both permuted by the seed. Two
seeds then differ in order and in token values, not in the amount of
work, so run-to-run spread measures the system and not the draw.
"""
from __future__ import annotations

import math

import numpy as np


def seed_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def log_uniform_sizes(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` integers at the mid-quantiles of log-uniform [lo, hi]."""
    u = _mid_quantiles(n)
    return np.rint(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
                   ).astype(np.int64).clip(lo, hi)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` gaps at the mid-quantiles of Exp(rate), rescaled so they sum
    to exactly ``n / rate`` seconds."""
    g = -np.log1p(-_mid_quantiles(n))
    return g * (n / rate_per_s) / g.sum()


def length_pairs(spec: dict, seed: int, n: int) -> list:
    """``n`` (prompt_len, max_tokens) pairs for a mix: each marginal is
    the fixed mid-quantile set, paired by two seed-driven permutations."""
    rng = seed_rng(seed, 1)
    p = log_uniform_sizes(spec["prompt_tokens"][0], spec["prompt_tokens"][1], n)
    o = log_uniform_sizes(spec["output_tokens"][0], spec["output_tokens"][1], n)
    return list(zip(rng.permutation(p).tolist(), rng.permutation(o).tolist()))


def prompt_tokens(seed: int, index: int, length: int, vocab: int,
                  stream: int = 2) -> list:
    """Request ``index``'s prompt: both the generator and the checker
    call this, so prompts never travel between processes. Ids avoid 0
    (the engine's pad id) and stay under ``vocab``. Warm-up prompts take
    another ``stream`` and so share no prefix with the window's."""
    rng = np.random.default_rng([int(seed), int(stream), int(index)])
    return rng.integers(1, int(vocab), size=int(length)).tolist()


def open_loop_schedule(spec: dict, seed: int, seconds: float) -> list:
    """Open-loop arrivals: ``[(due_offset_s, prompt_len, max_tokens)]``
    with offsets from the START OF THE LEAD-IN (the window opens
    ``lead_s`` later). Same count, same gaps, same sizes for every
    seed."""
    total_s = float(spec["lead_s"]) + float(seconds)
    n = int(round(float(spec["rate_per_s"]) * total_s))
    gaps = seed_rng(seed, 3).permutation(
        exponential_gaps(float(spec["rate_per_s"]), n))
    due = np.cumsum(gaps) - gaps[0] / 2.0
    return [(float(t), int(p), int(o))
            for t, (p, o) in zip(due, length_pairs(spec, seed, n))]


def backlog_plan(spec: dict) -> dict:
    """Closed-loop backlog: a pool of length pairs that client ``c``
    walks at stride ``clients`` (request k of client c is pool entry
    ``(k * clients + c) % pool``), and the share of its output length
    each client's FIRST request keeps, so the window opens on a steady
    mix of ages instead of every row in lockstep.

    The plan is drawn from ``spec["plan_seed"]``, NOT from the run's seed:
    a request lives for hundreds of cycles and the window sees some
    fifty, so which requests end (and which prompts are prefilled)
    inside it is decided by the order alone. Another order is another
    amount of work; the run's seed changes the tokens and the weights."""
    clients = int(spec["clients"])
    plan_seed = int(spec["plan_seed"])
    pool = length_pairs(spec, plan_seed, int(spec["pool"]))
    stagger = seed_rng(plan_seed, 4).permutation(_mid_quantiles(clients))
    return {"pool": pool, "stagger": stagger.tolist()}


def backlog_request(plan: dict, clients: int, client: int, k: int) -> tuple:
    """(request index, prompt_len, max_tokens) of client's k-th request."""
    index = k * clients + client
    p, o = plan["pool"][index % len(plan["pool"])]
    if k == 0:
        o = max(1, int(round(o * plan["stagger"][client])))
    return index, int(p), int(o)
