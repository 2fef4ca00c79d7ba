#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at GPT-2 124M (``GPTConfig.gpt2_small()``: 12 layers, hidden 768,
12 heads x 64, vocab 50304, 1,024 positions; random weights from a seed):

    python3 chip_smoke.py            # one chip: train, eager, serve
    python3 chip_smoke.py --chips 4  # four chips: TP serving, ZeRO training
                                     # and what each is compared with — no
                                     # one-chip phase

* **train** — the ``bench_gpt2`` recipe (chunked tied-head loss, bf16 AMP
  O2, AdamW with fp32 masters) through ``paddle.Model(...).prepare(...)
  .fit(...)`` on a repeated seeded batch: loss finite and falling, one
  compile of the step, flash-attention and LayerNorm kernels IN the
  compiled step.
* **eager** — README-quickstart steps (``loss.backward(); opt.step();
  opt.clear_grad()``) on the full model: what runs ``fused_adamw`` on the
  (50304, 768) embedding.
* **serve** — ``FrontDoor(GenerationEngine(...))`` with a pool that takes
  a real share of HBM, real HTTP on loopback (plain and SSE ``/v1/completions``, two prompts sharing
  a 256-token prefix, ``/metrics``), every completion compared with a
  plain full-sequence forward + argmax that shares neither the engine's
  kernel nor its cache.

It refuses to start unless jax reports a TPU, a phase that raises makes it
exit non-zero, and it times nothing for the record: the seconds it prints
are information. The phases are plain functions of a config and sizes —
``tests/test_chip_smoke.py`` calls them on the CPU at ``GPTConfig.tiny()``;
the command line has no size option.

Last line of stdout: ``{"ok": true, "device": {"platform": "tpu", "kind":
"...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
import urllib.request

SEED = 0

# persistent-compile-cache traffic of this process, counted from jax's own
# monitoring events once main() has registered the listener
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                "/jax/compilation_cache/cache_misses": 0}


def _count_cache_event(event: str, **_) -> None:
    if event in CACHE_EVENTS:
        CACHE_EVENTS[event] += 1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    """A failed check fails the phase, and with it the script."""
    if not cond:
        raise AssertionError(what)
    log(f"ok: {what}")


# ---------------------------------------------------------------------------
# what the process, the registry and the device report
# ---------------------------------------------------------------------------

def memory_stats(dev=None) -> dict:
    """``memory_stats()`` of one device — empty where the backend reports
    none (the CPU)."""
    import jax
    return (dev or jax.devices()[0]).memory_stats() or {}


def compile_totals() -> tuple:
    """(compiles, compile seconds) over every site of the program
    registry so far."""
    from paddle_tpu.framework import program_registry
    recs = program_registry.snapshot().values()
    return (sum(r["compiles"] for r in recs),
            sum(r["compile_ms_total"] for r in recs) / 1e3)


def sites_since(before: set, prefix: str) -> list:
    """Registry sites named ``prefix...`` that appeared after ``before``
    (a set of site names) — the sites THIS phase's model or engine owns."""
    from paddle_tpu.framework import program_registry
    return sorted(s for s in program_registry.snapshot()
                  if s.startswith(prefix) and s not in before)


def site_names() -> set:
    from paddle_tpu.framework import program_registry
    return set(program_registry.snapshot())


def step_text_report(sites: list, kernels: tuple) -> str:
    """Look INSIDE the compiled programs of ``sites``: on a TPU every one
    must contain its Pallas kernels as ``tpu_custom_call`` (enabled is not
    the same as used), and each name in ``kernels`` must appear. Reports
    how much f64 the programs carry. Returns the text."""
    import jax
    from paddle_tpu.framework import program_registry
    texts = {s: program_registry.compiled_text(s) for s in sites}
    check(all(texts.values()), f"compiled text held for {len(sites)} "
                               f"site(s)")
    text = "\n".join(texts.values())
    log(f"  f64 values in the compiled step(s): {text.count('f64[')} "
        f"occurrences of 'f64[' in {len(text):,} chars of HLO")
    if jax.default_backend() == "tpu":
        for s, t in texts.items():
            check("tpu_custom_call" in t,
                  f"{s}: contains tpu_custom_call "
                  f"({t.count('tpu_custom_call')} mentions)")
        for k in kernels:
            check(k in text, f"kernel {k!r} is in the compiled step")
    return text


class PhaseMeter:
    """Per-phase set-up facts: wall seconds, compiles and compile seconds,
    persistent-cache entries added, peak device memory."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from paddle_tpu.framework import compile_cache
        log(f"=== phase {self.name} ===")
        self.t0 = time.perf_counter()
        self.c0 = compile_totals()
        self.e0 = compile_cache.entries()
        self.h0 = tuple(CACHE_EVENTS.values())
        return self

    def __exit__(self, exc_type, exc, tb):
        from paddle_tpu.framework import compile_cache
        if exc_type is not None:
            log(f"phase {self.name} FAILED: {exc_type.__name__}: {exc}")
            return False
        n, secs = compile_totals()
        hits, misses = (a - b for a, b in zip(CACHE_EVENTS.values(), self.h0))
        peak = memory_stats().get("peak_bytes_in_use")
        log(f"phase {self.name} done: {time.perf_counter() - self.t0:.1f} s "
            f"wall, {n - self.c0[0]} compiles taking "
            f"{secs - self.c0[1]:.1f} s, persistent cache {hits} hits / "
            f"{misses} misses, entries {self.e0} -> "
            f"{compile_cache.entries()}, device peak so far "
            f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}")
        return False


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _lm_trainer(cfg, seed: int, multi_precision: bool = True):
    """The bench_gpt2 recipe as a ``paddle.Model``: chunked tied-head
    loss, bf16 AMP O2, AdamW with fp32 master weights. The network's
    output IS its loss (the chunked loss never materialises logits), so
    ids and labels are both inputs and the loss function only takes the
    mean — of a ``[1]`` vector, because ``fit(zero=1)`` concatenates the
    outputs of its dp shards and cannot do that to a scalar."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.models.gpt import GPTForPretraining
    from paddle_tpu.static import InputSpec

    class NextTokenLoss(nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids, labels):
            return self.lm(ids, labels=labels)[0].reshape([1])

    paddle.seed(seed)
    lm = GPTForPretraining(cfg, lm_loss_chunks=8)
    amp.decorate(lm, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, weight_decay=0.01,
        parameters=lm.parameters(), multi_precision=multi_precision)
    model = paddle.Model(
        NextTokenLoss(lm),
        inputs=[InputSpec([None, None], "int32", "ids"),
                InputSpec([None, None], "int32", "labels")])
    model.prepare(opt, loss=lambda loss: loss.mean())
    return model


def _repeated_batch(cfg, batch: int, seq: int, steps: int):
    """``steps`` copies of ONE seeded next-token batch: a loss that does
    not fall on it is a broken step, not a hard dataset."""
    import numpy as np
    from paddle_tpu.io import TensorDataset
    rng = np.random.RandomState(SEED)
    tokens = rng.randint(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    ids = np.tile(tokens[:, :-1], (steps, 1))
    labels = np.tile(tokens[:, 1:], (steps, 1))
    return TensorDataset([ids, labels])


def _fit_losses(model, data, batch: int, **fit_kwargs) -> list:
    import paddle_tpu as paddle

    class Tap(paddle.callbacks.Callback):
        def __init__(self):
            super().__init__()
            self.losses = []

        def on_train_batch_end(self, step, logs=None):
            self.losses.append(float(logs["loss"]))

    tap = Tap()
    model.fit(data, batch_size=batch, epochs=1, shuffle=False, log_freq=1,
              verbose=0, callbacks=[tap], **fit_kwargs)
    return tap.losses


def phase_train(cfg, batch: int, seq: int, steps: int) -> dict:
    import numpy as np
    from paddle_tpu.framework import program_registry
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.ops import autotune_cache, pallas_kernels

    before = site_names()
    model = _lm_trainer(cfg, SEED)
    n_params = sum(int(np.prod(p.shape)) for p in model.network.parameters())
    log(f"train: {n_params:,} parameters, b{batch} x s{seq}, {steps} steps "
        f"of paddle.Model.fit on one repeated batch")
    t0 = time.perf_counter()
    losses = _fit_losses(model, _repeated_batch(cfg, batch, seq, steps),
                         batch)
    log(f"  fit returned after {time.perf_counter() - t0:.1f} s; losses "
        f"{[round(v, 4) for v in losses]}")
    check(len(losses) == steps and bool(np.all(np.isfinite(losses))),
          f"{steps} finite losses")
    check(losses[-1] < losses[0], f"loss fell on the repeated batch "
                                  f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    # does block_until_ready wait for the device? (bench.py's timed
    # regions end in it.) Queue a few more steps without blocking: if it
    # waits, nearly all the time passes before it returns and the value
    # fetch after it is instant
    ids, labels = (a[:batch] for a in
                   _repeated_batch(cfg, batch, seq, 1).tensors)
    t0 = time.perf_counter()
    for _ in range(4):
        last = model.train_batch([ids, labels], return_numpy=False)
    t_enqueued = time.perf_counter() - t0
    last.block_until_ready()
    t_ready = time.perf_counter() - t0
    value = float(last)
    t_fetched = time.perf_counter() - t0
    log(f"  4 more steps queued in {t_enqueued * 1e3:.1f} ms; "
        f"block_until_ready returned at {t_ready * 1e3:.1f} ms; the value "
        f"({value:.4f}) was fetched {(t_fetched - t_ready) * 1e3:.2f} ms "
        f"after that")
    check(np.isfinite(value) and value < losses[-1],
          "the loss kept falling over the queued steps")
    sites = sites_since(before, "hapi/train_step[")
    check(len(sites) == 1, f"one train-step site ({sites})")
    rec = program_registry.get(sites[0])
    check(rec.compiles == 1, f"the step compiled once for {steps + 4} "
                             f"steps (zero recompiles after the first)")
    build = rec.builds[-1]
    log(f"  step build: trace {build['trace_ms'] / 1e3:.1f}, lower "
        f"{build['lower_ms'] / 1e3:.1f}, compile "
        f"{build['compile_ms'] / 1e3:.1f} s ({build['cache_hits']} cache "
        f"hits, {build['cache_misses']} misses), first call "
        f"{build['first_call_ms'] / 1e3:.1f} s; XLA temp "
        f"{rec.temp_bytes}, arguments {rec.argument_bytes} bytes")
    text = step_text_report(sites,
                            kernels=("flash_attention_fwd", "flash_attention_bwd",
                                     "fused_layer_norm_fwd",
                                     "fused_layer_norm_bwd"))
    check(bool(flag_value("FLAGS_use_pallas")),
          "FLAGS_use_pallas is still true after fit")
    # which attention path the dispatcher chose, and why: a measured
    # entry of the autotune cache beats the seq >= FLASH_MIN_SEQ default,
    # so a cold and a warm machine can differ — say which this was
    st = autotune_cache.stats()
    path = autotune_cache.cache_path()
    log(f"  attention in the step: "
        f"{'flash (Pallas)' if 'flash_attention_fwd' in text else 'lax'}; "
        f"autotune cache {path} "
        f"{'present' if os.path.exists(path) else 'absent (cold)'}, "
        f"{st['hits']} hits / {st['misses']} misses -> "
        + ("a measured entry decided" if st["hits"] else
           f"the heuristic default decided (seq {seq} vs FLASH_MIN_SEQ "
           f"{pallas_kernels.FLASH_MIN_SEQ})" if st["misses"] else
           "the cache was not consulted (no Pallas tier off the TPU)"))
    return {"losses": losses, "site": sites[0]}


# ---------------------------------------------------------------------------
# eager
# ---------------------------------------------------------------------------

def phase_eager(cfg, batch: int, seq: int, steps: int = 2) -> dict:
    import jax
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.gpt import GPTForPretraining
    from paddle_tpu.ops import pallas_kernels

    paddle.seed(SEED + 1)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    rng = np.random.RandomState(SEED + 1)
    tokens = rng.randint(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    x = paddle.to_tensor(tokens[:, :-1])
    y = paddle.to_tensor(tokens[:, 1:].astype(np.int64))
    log(f"eager: b{batch} x s{seq}, {steps} quickstart steps "
        f"(loss.backward(); opt.step(); opt.clear_grad()); fused AdamW "
        f"kernel available: {pallas_kernels.fused_adamw_available()}")
    if jax.default_backend() == "tpu":
        check(pallas_kernels.fused_adamw_available(),
              "opt.step() takes the fused AdamW kernel on the TPU")
    losses = []
    for _ in range(steps):
        logits = model(x)
        loss = F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), y.reshape([-1]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    log(f"  losses {[round(v, 4) for v in losses]}")
    check(bool(np.all(np.isfinite(losses))), "finite eager losses")
    check(losses[-1] < losses[0], "the eager loss fell on the repeated batch")
    wte = model.gpt.wte.weight
    check(bool(np.isfinite(float(paddle.abs(wte).sum()))),
          f"the {tuple(wte.shape)} embedding is finite after "
          f"{steps} updates")
    return {"losses": losses}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _post_completion(url: str, prompt, max_tokens: int, stream: bool):
    """One real HTTP request. Returns (token ids, seconds to the first
    token, seconds to the end)."""
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_tokens": int(max_tokens),
                       "stream": bool(stream)}).encode()
    req = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=900) as resp:
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}")
        if not stream:
            doc = json.loads(resp.read())
            dt = time.perf_counter() - t0
            choice = doc["choices"][0]
            if choice["finish_reason"] != "length":
                raise RuntimeError(f"finish_reason {choice!r}")
            return choice["token_ids"], None, dt
        tokens, ttft, done = [], None, False
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                break
            choice = json.loads(line[6:])["choices"][0]
            if choice["token_id"] is not None:
                if ttft is None:
                    ttft = time.perf_counter() - t0
                tokens.append(int(choice["token_id"]))
            elif choice["finish_reason"] != "length":
                raise RuntimeError(f"finish_reason {choice!r}")
        if not done:
            raise RuntimeError("SSE stream ended without [DONE]")
        return tokens, ttft, time.perf_counter() - t0


def reference_margins(model, prompts, served, width: int):
    """The plain reference, step by step over the text the engine
    actually produced: for every position of every completion, a
    full-sequence forward of the WHOLE text so far (prompt + the served
    tokens before it) + argmax — no KV cache, no page table, no ragged
    kernel, so nothing the engine is made of. One jitted program serves
    every step: sequences sit right-padded in a ``[B, width]`` batch
    (causal attention never looks right), and only the hidden state at
    each sequence's last token goes through the LM head.

    Returns ``(top, margin)``, both ``[B, n_new]``: the reference's greedy
    token, and how far below it the SERVED token's reference logit lies
    (0 where they are the same token)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.framework.tensor import Tensor, no_grad_guard
    from paddle_tpu.nn.layer.layers import (functional_state,
                                            get_buffers_tree,
                                            get_params_tree)

    gpt = model.gpt
    B = len(prompts)
    served = np.asarray(served, np.int32)
    n_new = served.shape[1]
    ids = np.zeros((B, width), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p

    @jax.jit
    def step_fn(params, buffers, ids, last, picked):
        with functional_state(model, params, buffers), no_grad_guard():
            hidden = gpt(Tensor(ids, stop_gradient=True))._data
            h_last = hidden[jnp.arange(B), last]
            logits = gpt.logits(Tensor(h_last[:, None]))._data[:, 0]
        logits = logits.astype(jnp.float32)
        top = jnp.argmax(logits, axis=-1)
        rows = jnp.arange(B)
        return top, logits[rows, top] - logits[rows, picked]

    params, buffers = get_params_tree(model), get_buffers_tree(model)
    top = np.zeros((B, n_new), np.int32)
    margin = np.zeros((B, n_new), np.float32)
    for step in range(n_new):
        t, m = step_fn(params, buffers, jnp.asarray(ids),
                       jnp.asarray(lens - 1), jnp.asarray(served[:, step]))
        top[:, step], margin[:, step] = np.asarray(t), np.asarray(m)
        ids[np.arange(B), lens] = served[:, step]
        lens = lens + 1
    return top, margin


# A served token is GREEDY when it is the reference's argmax or a near-tie
# of it: its reference logit lies within NEAR_TIE of the top one. On the
# chip the engine (16-token KV blocks, ragged rows) and the reference
# (1,024-wide rows, 128-token flash blocks) sum and round in different
# orders through bf16 MXU passes, and with random weights the top two of
# 50,304 logits (|logit| ~ 2.3) are often that close: on the v5e two of
# 416 served tokens were runners-up, 0.00024 and 0.00076 below the top
# (the second is 0.0065 below it in exact f32 on the CPU). Unlike the
# position budget of
# ``__graft_entry__._dryrun_tp_decode`` (anything goes in the last quarter
# after a flip), EVERY token is held to this, wherever it stands.
NEAR_TIE = 0.01


def check_greedy(served, top, margin, what: str) -> dict:
    """Every served token must be the reference's greedy choice up to a
    near-tie (``margin <= NEAR_TIE``), near-tie picks may be at most one
    token in twenty, and at least one sequence must match the reference
    token for token (the clause ``_dryrun_tp_decode`` keeps against "all
    of them drifted")."""
    import numpy as np
    served, top, margin = (np.asarray(a) for a in (served, top, margin))
    differs = served != top
    exact = int((~differs).all(axis=1).sum())
    picks = [(int(i), int(j), round(float(margin[i, j]), 5))
             for i, j in zip(*np.nonzero(differs))]
    log(f"{what}: {exact}/{len(served)} sequences token-identical; "
        f"{len(picks)} of {served.size} tokens differ from the reference's "
        f"argmax (sequence, position, margin): {picks}")
    wrong = [p for p in picks if not p[2] <= NEAR_TIE]
    if wrong:
        raise AssertionError(
            f"{what}: {len(wrong)} served token(s) are not the reference's "
            f"greedy choice — margin above NEAR_TIE={NEAR_TIE}: {wrong}")
    if len(picks) > max(1, served.size // 20):
        raise AssertionError(
            f"{what}: {len(picks)} near-tie picks in {served.size} tokens "
            f"is more than one in twenty")
    if exact < 1:
        raise AssertionError(f"{what}: no sequence matches token for token")
    log(f"ok: {what}: every token greedy within NEAR_TIE={NEAR_TIE}")
    return {"exact": exact, "near_ties": picks}


def _seeded_prompts(cfg, lens, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, n).astype(np.int32) for n in lens]


def _shared_prefix_pair(cfg, prefix: int, tail: int, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    head = rng.randint(1, cfg.vocab_size, prefix).astype(np.int32)
    return [np.concatenate(
        [head, rng.randint(1, cfg.vocab_size, tail).astype(np.int32)])
        for _ in range(2)]


def phase_serve(cfg, *, max_len: int, block_size: int, num_slots: int,
                num_blocks: int, prompt_lens, prefix: int, tail: int,
                burst_lens, new_tokens: int) -> dict:
    """Sequential cold pass, sequential warm pass (same lengths, new
    seeds: it must compile nothing), then a concurrent burst whose
    prefill chunks and decode rows share launches. Each pass ends with
    two prompts that share a ``prefix``-token head."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import program_registry
    from paddle_tpu.models.gpt import GPTForPretraining
    from paddle_tpu.serving import FrontDoor, GenerationEngine

    paddle.seed(SEED + 2)
    model = GPTForPretraining(cfg)
    model.eval()
    before = site_names()
    m0 = memory_stats()
    engine = GenerationEngine(
        model, block_size=block_size,
        max_len=max_len, num_slots=num_slots, num_blocks=num_blocks)
    m1 = memory_stats()
    st = engine.stats()
    accounted = st["kv_bytes"]["blocks"] / (st["num_blocks"] + 1)
    log(f"serve: engine dtype {st['kv_dtype']}, {st['num_blocks']} blocks "
        f"of {st['block_size']} tokens, pool "
        f"{st['kv_pool_capacity_bytes'] / 2**30:.2f} GiB, "
        f"{num_slots} slots, max_len {max_len}")
    if m0.get("bytes_in_use") is not None:
        measured = (m1["bytes_in_use"] - m0["bytes_in_use"]) \
            / (st["num_blocks"] + 1)
        log(f"  HBM per pool block: measured {measured:,.0f} bytes "
            f"(memory_stats before/after allocation), accounted "
            f"{accounted:,.0f} bytes (PagedKVPool ledger) — ratio "
            f"{measured / accounted:.3f}; device limit "
            f"{m1.get('bytes_limit', 0) / 2**30:.2f} GiB")
        check(0.9 < measured / accounted < 1.25,
              "a pool block occupies in HBM what the ledger accounts")
    else:
        log(f"  HBM per pool block: accounted {accounted:,.0f} bytes; "
            f"measured: not reported by this backend")

    pool = _pool_array(cfg, block_size, num_blocks, sharded=False)
    log(f"  the pool {pool.shape} {pool.dtype} lives on "
        f"{sorted(str(d) for d in pool.devices())}; the parameters on "
        f"{sorted({str(d) for p in model.parameters() for d in p._data.devices()})}")
    del pool
    def fused_programs():
        """(sites, compiles) of this engine's fused step programs."""
        sites = sites_since(before, "serving/fused[")
        return sites, sum(program_registry.get(s).compiles for s in sites)

    door = FrontDoor(engine)
    srv = door.start()
    served, want_prompts = [], []
    try:
        def run_pass(seed: int, label: str):
            prompts = _seeded_prompts(cfg, prompt_lens, seed) \
                + _shared_prefix_pair(cfg, prefix, tail, seed + 1000)
            ttfts = []
            for i, p in enumerate(prompts):
                toks, ttft, dt = _post_completion(
                    srv.url, p, new_tokens, stream=bool(i % 2))
                check(len(toks) == new_tokens,
                      f"{label} request {i}: {len(p)} prompt tokens -> "
                      f"{len(toks)} new tokens in {dt:.2f} s"
                      + ("" if ttft is None else f" (SSE, first token "
                                                 f"after {ttft:.2f} s)"))
                if ttft is not None:
                    ttfts.append(ttft)
                served.append(toks)
                want_prompts.append(p)
            return ttfts

        hits0 = engine.stats()["prefix_hits"]
        cold_ttft = run_pass(100, "cold")
        _, n_cold = fused_programs()
        check(engine.stats()["prefix_hits"] > hits0,
              "the shared-prefix request recorded a prefix hit")
        warm_ttft = run_pass(200, "warm")
        sites, n_warm = fused_programs()
        check(n_warm == n_cold,
              f"no step compiled after warm-up ({n_cold} fused programs "
              f"{[s.split('#')[0][13:] for s in sites]} served both passes)")
        log(f"  TTFT of the first streamed request {cold_ttft[0]:.2f} s "
            f"(compiles included), of its warm twin {warm_ttft[0]:.3f} s")

        # the burst: concurrent clients, so prefill chunks and decode
        # rows of different requests share one ragged launch
        burst = _seeded_prompts(cfg, burst_lens, 300)
        results = [None] * len(burst)

        def client(i):
            results[i] = _post_completion(srv.url, burst[i], new_tokens,
                                          stream=bool(i % 2))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(burst))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(all(r is not None and len(r[0]) == new_tokens
                  for r in results),
              f"a burst of {len(burst)} concurrent requests completed")
        served.extend(r[0] for r in results)
        want_prompts.extend(burst)
        sites, n_burst = fused_programs()
        log(f"  fused programs after the burst: {n_burst}")

        with urllib.request.urlopen(srv.url + "/metrics", timeout=60) as r:
            metrics = r.read().decode()
        check("serving_" in metrics,
              f"/metrics answered {len(metrics.splitlines())} lines with "
              f"serving_ series")
        st = engine.stats()
        log(f"  engine: {st['requests_retired']} requests retired, prefix "
            f"hits {st['prefix_hits']} / misses {st['prefix_misses']}, "
            f"{st['prefill_tokens_saved']} prefill tokens saved, "
            f"{st['prefill_chunks']} prefill chunks, nonfinite cycles "
            f"{st['nonfinite_cycles']}, preempts {st['preempts']}")
        check(st["nonfinite_cycles"] == 0, "no non-finite logits cycle")
        step_text_report(sites, kernels=("ragged_paged_attention",))
    finally:
        door.close()
        engine.close()

    top, margin = reference_margins(model, want_prompts, served, max_len)
    parity = check_greedy(served, top, margin,
                          f"{len(served)} served completions vs the plain "
                          f"reference")
    return {"parity": parity, "fused_programs": n_cold,
            "prefix_hits": st["prefix_hits"]}


# ---------------------------------------------------------------------------
# four chips: tensor-parallel serving, ZeRO data-parallel training
# ---------------------------------------------------------------------------

def phase_axk1_serve(model: dict, *, dtype: str, max_len: int,
                     block_size: int, num_slots: int, num_blocks: int,
                     prefill_budget: int, prompt_lens, new_tokens: int,
                     limits: dict, width: int, q_block: int) -> dict:
    """A.X-K1 (latent attention, routed experts; ``model`` is the
    ``model`` group of a benchmark configuration, cut to a toy DEPTH)
    through ``GenerationEngine``:
    chunked prefill and decode over the latent paged cache, then every
    served token's logit against the plain reference's best
    (``benchmark/lib/reference_axk1.py``), held to the configuration's
    own limits."""
    import numpy as np

    from benchmark.lib import correct as C
    from benchmark.lib import family_axk1 as F
    from benchmark.lib import reference_axk1 as R
    from paddle_tpu.serving import GenerationEngine

    seed = 2 ** 31 + 29
    net = F.build_lm(model, seed, dtype)
    rng = np.random.RandomState(29)
    prompts = [rng.randint(1, int(model["vocab_size"]), size=n).tolist()
               for n in prompt_lens]
    before = site_names()
    with GenerationEngine(net, block_size=block_size, max_len=max_len,
                          num_slots=num_slots, num_blocks=num_blocks,
                          prefill_budget=prefill_budget) as engine:
        handles = [engine.submit(p, new_tokens) for p in prompts]
        served = [[int(t) for t in h.stream()] for h in handles]
        stats = engine.stats()
        cycles = engine.flight_recorder.snapshot()["cycles"]
        text = step_text_report(sites_since(before, "serving/fused["),
                                ("mla_paged_attention",))
    log(f"axk1 steps: {text}")
    del net, engine
    gc.collect()
    check(stats["nonfinite_cycles"] == 0, "no non-finite cycle")
    check(any("moe_pairs" in c for c in cycles),
          "the routed layers' counters reached the cycle record")
    B, n = len(prompts), new_tokens
    ids = np.zeros((B, width), np.int32)
    pos = np.zeros((B, n), np.int32)
    for b, (p, o) in enumerate(zip(prompts, served)):
        check(len(o) == n, f"request {b} is whole ({len(o)} of {n} tokens)")
        ids[b, :len(p) + n] = p + o
        pos[b] = len(p) - 1 + np.arange(n)
    out = R.served_margins(F.Weights(seed, model, dtype), model, ids, pos,
                           np.asarray(served, np.int32), rows_per_call=B,
                           q_block=q_block)
    numbers = C.gap_summary((out["gap"] / out["std"]).reshape(-1))
    log(f"axk1 served tokens against the reference: {numbers}, limits "
        f"{limits}")
    for name, limit in limits.items():
        check(numbers[name] <= limit,
              f"axk1 {name} {numbers[name]:.5f} within {limit}")
    return numbers


def phase_sdar_serve(model: dict, *, dtype: str, max_len: int,
                     block_size: int, num_slots: int, num_blocks: int,
                     prefill_budget: int, prompt_lens, new_tokens: int,
                     limits: dict, width: int, states: int,
                     q_block: int) -> dict:
    """SDAR-MoE (grouped-query heads in the ragged kernel, softmax-routed
    experts all held, generation by diffusion over blocks; ``model`` is
    the ``model`` group of a benchmark configuration, cut to a toy DEPTH)
    through ``GenerationEngine``: chunked prefill, then denoising passes
    over the paged cache, a finished block's commit riding with the next
    block's first pass, then the plain reference
    TEACHER-FORCED on the states the program saw
    (``benchmark/lib/reference_sdar.py``): every served token's logit
    against the reference's best at the pass that fixed it, and every
    pass's position against the reference's most confident, held to the
    configuration's own limits."""
    import numpy as np

    from benchmark.drivers import serve_backlog_blocks as D
    from benchmark.lib import family_sdar as F
    from benchmark.lib import reference_sdar as R
    from paddle_tpu.serving import GenerationEngine

    seed = 2 ** 31 + 31
    net = F.build_lm(model, seed, dtype)
    rng = np.random.RandomState(31)
    prompts = [rng.randint(1, int(model["vocab_size"]), size=n).tolist()
               for n in prompt_lens]
    before = site_names()
    with GenerationEngine(net, block_size=block_size, max_len=max_len,
                          num_slots=num_slots, num_blocks=num_blocks,
                          prefill_budget=prefill_budget) as engine:
        handles = [engine.submit(p, new_tokens) for p in prompts]
        served = [[int(t) for t in h.stream()] for h in handles]
        stats = engine.stats()
        cycles = engine.flight_recorder.snapshot()["cycles"]
        text = step_text_report(sites_since(before, "serving/fused["),
                                ("ragged_paged_attention", "kv_append"))
    log(f"sdar steps: {text}")
    del net, engine
    gc.collect()
    check(stats["nonfinite_cycles"] == 0, "no non-finite cycle")
    check(any(c.get("denoise_slots") for c in cycles)
          and any(c.get("ride_slots") for c in cycles)
          and not any(c.get("commit_slots") for c in cycles),
          "denoising passes and commits that rode with one (none alone) "
          "reached the cycle record")
    check(any("moe_pairs" in c for c in cycles),
          "the routed layers' counters reached the cycle record")
    requests = []
    for b, (p, o, h) in enumerate(zip(prompts, served, handles)):
        check(len(o) == new_tokens and len(h.trace.token_passes) == len(o),
              f"request {b} is whole ({len(o)} of {new_tokens} tokens, "
              f"each with the pass that fixed it)")
        check(int(model["mask_token_id"]) not in o,
              f"request {b} never chose the mask id")
        requests.append((p, o, list(h.trace.token_passes)))
    out = R.served_margins(F.Weights(seed, model, dtype), model, requests,
                           width=width, states=states, q_block=q_block)
    numbers = D.summary(out["gap"] / out["std"], out["order_gap"])
    log(f"sdar served tokens against the reference: {numbers}, limits "
        f"{limits}")
    for name, limit in limits.items():
        check(numbers[name] <= limit,
              f"sdar {name} {numbers[name]:.5f} within {limit}")
    return numbers


def phase_mimo_serve(model: dict, *, dtype: str, max_len: int,
                     block_size: int, num_slots: int, num_blocks: int,
                     prefill_budget: int, prompt_lens, new_tokens: int,
                     limits: dict, width: int, q_block: int) -> dict:
    """MiMo-V2-Flash (window and global layers in two cache groups, a
    sliding window with sink logits and K 192 | V 128 lanes in the ragged
    kernel, a bias-corrected top-k; ``model`` is the ``model`` group of a
    benchmark configuration, cut to a toy DEPTH) through
    ``GenerationEngine``: chunked prefill and decode over contexts longer
    than two windows, so the window group frees blocks behind every slot
    and the windowed walk starts past them — a freed block read on the
    real kernel would show in the gaps — then every served token's logit
    against the plain reference's best
    (``benchmark/lib/reference_mimo.py``), held to the configuration's
    own limits."""
    import numpy as np

    from benchmark.lib import correct as C
    from benchmark.lib import family_mimo as F
    from benchmark.lib import reference_mimo as R
    from paddle_tpu.serving import GenerationEngine

    seed = 2 ** 31 + 35
    net = F.build_lm(model, seed, dtype)
    rng = np.random.RandomState(35)
    prompts = [rng.randint(1, int(model["vocab_size"]), size=n).tolist()
               for n in prompt_lens]
    before = site_names()
    with GenerationEngine(net, block_size=block_size, max_len=max_len,
                          num_slots=num_slots, num_blocks=num_blocks,
                          prefill_budget=prefill_budget) as engine:
        handles = [engine.submit(p, new_tokens) for p in prompts]
        served = [[int(t) for t in h.stream()] for h in handles]
        stats = engine.stats()
        cycles = engine.flight_recorder.snapshot()["cycles"]
        text = step_text_report(
            sites_since(before, "serving/fused["),
            ("ragged_paged_attention", "ragged_paged_attention_window",
             "kv_append"))
    log(f"mimo steps: {text}")
    del net, engine
    gc.collect()
    check(stats["nonfinite_cycles"] == 0, "no non-finite cycle")
    groups = stats["cache_groups"]
    log(f"mimo cache groups: {groups}; window blocks freed "
        f"{stats['window_blocks_freed']}")
    check(len(groups) == 2 and groups[0]["window"] == 0
          and groups[1]["window"] == int(model["sliding_window"]),
          "a global and a window cache group")
    window = int(model["sliding_window"])
    check(max(len(p) for p in prompts) + new_tokens > 2 * window
          and stats["window_blocks_freed"] > 0,
          f"a context longer than two windows freed "
          f"{stats['window_blocks_freed']} blocks behind the window")
    check(any("kv_tokens_window" in c and "kv_live_bytes" in c
              for c in cycles) and any("moe_pairs" in c for c in cycles),
          "the window's and the routed layers' counters reached the cycle "
          "record")
    B, n = len(prompts), new_tokens
    ids = np.zeros((B, width), np.int32)
    pos = np.zeros((B, n), np.int32)
    for b, (p, o) in enumerate(zip(prompts, served)):
        check(len(o) == n, f"request {b} is whole ({len(o)} of {n} tokens)")
        ids[b, :len(p) + n] = p + o
        pos[b] = len(p) - 1 + np.arange(n)
    out = R.served_margins(F.Weights(seed, model, dtype), model, ids, pos,
                           np.asarray(served, np.int32), rows_per_call=B,
                           q_block=q_block)
    numbers = C.gap_summary((out["gap"] / out["std"]).reshape(-1))
    log(f"mimo served tokens against the reference: {numbers}, limits "
        f"{limits}")
    for name, limit in limits.items():
        check(numbers[name] <= limit,
              f"mimo {name} {numbers[name]:.5f} within {limit}")
    return numbers


def _pool_array(cfg, block_size: int, num_blocks: int, sharded: bool):
    """The engine's block pool, found among jax's live arrays by its
    shape ``[L, NB + 1, H, block_size, 2 * Dh]`` (the engine does not
    hand it out)."""
    import jax
    shape = (cfg.num_hidden_layers, num_blocks + 1, cfg.num_attention_heads,
             block_size,
             2 * cfg.hidden_size // cfg.num_attention_heads)
    found = [a for a in jax.live_arrays() if a.shape == shape
             and a.is_fully_replicated != sharded]
    if len(found) != 1:
        raise AssertionError(f"{len(found)} live arrays of pool shape "
                             f"{shape}")
    return found[0]


def _engine_tokens(engine, prompts, new_tokens: int) -> list:
    out = []
    for p in prompts:
        handle = engine.submit(p, new_tokens)
        out.append([int(t) for t in handle.stream()])
    return out


def phase_tp_serve(cfg, devices, *, max_len: int, block_size: int,
                   num_slots: int, num_blocks: int, prompt_lens,
                   new_tokens: int) -> dict:
    """The fused paged engine head-partitioned over ``len(devices)``
    chips against the same engine on one chip: same model, same prompts,
    same pool geometry. Both engines' tokens are held to the plain
    reference, so where the two differ from each other it is at a
    verified near-tie."""
    import numpy as np
    import paddle_tpu as paddle
    from jax.sharding import Mesh
    from paddle_tpu.models.gpt import GPTForPretraining
    from paddle_tpu.serving import GenerationEngine

    mp = len(devices)
    paddle.seed(SEED + 3)
    model = GPTForPretraining(cfg)
    model.eval()
    prompts = _seeded_prompts(cfg, prompt_lens, 400)
    geometry = dict(block_size=block_size, max_len=max_len,
                    num_slots=num_slots, num_blocks=num_blocks)

    single = GenerationEngine(model, **geometry)
    try:
        want = _engine_tokens(single, prompts, new_tokens)
        st1 = single.stats()
    finally:
        single.close()
    del single
    gc.collect()

    used0 = [memory_stats(d).get("bytes_in_use") for d in devices]
    sharded = GenerationEngine(model, mesh=Mesh(np.array(devices), ("mp",)),
                               mp_axis="mp", **geometry)
    try:
        got = _engine_tokens(sharded, prompts, new_tokens)
        st = sharded.stats()
        pool = _pool_array(cfg, block_size, num_blocks, sharded=True)
        pool_devs = {s.device for s in pool.addressable_shards}
        shard_shape = pool.addressable_shards[0].data.shape
        log(f"tp: pool {pool.shape} {pool.dtype}, shard {shard_shape} on "
            f"{sorted(d.id for d in pool_devs)}")
        check(len(pool_devs) == mp and shard_shape[2] * mp == pool.shape[2],
              f"the pool is head-partitioned over {mp} distinct devices")
        spread = {n: {s.device for s in p._data.addressable_shards}
                  for n, p in model.named_parameters()}
        check(all(len(d) == mp for d in spread.values()),
              f"each of {len(spread)} parameters has addressable shards "
              f"on {mp} distinct devices")
        check(st["mp"] == mp and
              st["kv_bytes_per_device"] * mp == st1["kv_bytes"]["blocks"],
              f"per-device pool bytes {st['kv_bytes_per_device']:,} = 1/{mp} "
              f"of the one-chip pool's {st1['kv_bytes']['blocks']:,}")
        used = [memory_stats(d).get("bytes_in_use") for d in devices]
        if used[0] is not None:
            log(f"  bytes_in_use per device: {used} (before the sharded "
                f"engine: {used0})")
            # (device 0 GAVE bytes too: it held the whole model before
            # the engine laid the weights out Megatron-style)
            check(all(u >= st["kv_bytes_per_device"] for u in used),
                  "every device holds at least its pool shard")
    finally:
        sharded.close()
    # the engines sharded `model` in place: the reference runs on an
    # identical unsharded twin (same seed, same draws)
    paddle.seed(SEED + 3)
    twin = GPTForPretraining(cfg)
    twin.eval()
    check_greedy(want, *reference_margins(twin, prompts, want, max_len),
                 "mp=1 tokens vs the plain reference")
    parity = check_greedy(got, *reference_margins(twin, prompts, got,
                                                  max_len),
                          f"mp={mp} tokens vs the plain reference")
    same = sum(g == w for g, w in zip(got, want))
    log(f"  mp={mp} vs mp=1: {same}/{len(got)} sequences token-identical")
    return {"parity": parity, "identical_to_single": same}


def phase_zero_train(cfg, devices, *, batch: int, seq: int,
                     steps: int) -> dict:
    """``Model.fit(zero=1)`` over dp = ``len(devices)`` against the
    replicated step: same recipe, same data, same seed — both without
    fp32 master weights, which ``fit(zero=1)`` refuses (its flat update
    already runs in f32 over the cast-up parameters)."""
    import numpy as np
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.profiler import memory as _memory

    dp = len(devices)
    data = _repeated_batch(cfg, batch, seq, steps)

    def run(**fit_kwargs):
        keys0 = set(_memory.ledger())
        model = _lm_trainer(cfg, SEED, multi_precision=False)
        losses = _fit_losses(model, data, batch, **fit_kwargs)
        led = _memory.ledger()
        opt_bytes = [v for k, v in led.items()
                     if k.endswith("/opt_state") and k not in keys0]
        check(len(opt_bytes) == 1, "one optimizer-state ledger entry")
        return losses, opt_bytes[0], model

    import jax
    rep_losses, rep_bytes, rep_model = run()
    del rep_model
    gc.collect()
    born_before = {id(a) for a in jax.live_arrays()}
    denv.build_mesh({"dp": dp}, devices=devices)
    try:
        zero_losses, zero_bytes, zero_model = run(zero=1)
    finally:
        denv.set_mesh(None)
    striped = [a for a in jax.live_arrays()
               if id(a) not in born_before and not a.is_fully_replicated
               and len({s.device for s in a.addressable_shards}) == dp]
    check(len(striped) > 0,
          f"{len(striped)} live arrays are striped over {dp} distinct "
          f"devices (largest {max(a.nbytes for a in striped):,} bytes)")
    used = [memory_stats(d).get("bytes_in_use") for d in devices]
    if used[0] is not None:
        log(f"  bytes_in_use per device: {used}")
        check(min(used) > zero_bytes, "every device holds train state")
    log(f"zero: replicated losses {[round(v, 4) for v in rep_losses]}")
    log(f"      zero dp={dp}  losses {[round(v, 4) for v in zero_losses]}")
    check(bool(np.all(np.isfinite(zero_losses)))
          and zero_losses[-1] < zero_losses[0],
          "ZeRO losses are finite and fall")
    # bf16 tolerance: activations and the exchanged gradients carry 8
    # bits of mantissa, and the dp exchange sums in another order
    check(bool(np.allclose(zero_losses, rep_losses, rtol=2e-2, atol=2e-2)),
          "the ZeRO loss trajectory follows the replicated one within "
          "bf16 tolerance")
    log(f"  optimizer state per device: replicated {rep_bytes:,} bytes, "
        f"ZeRO {zero_bytes:,} bytes (ratio "
        f"{zero_bytes / rep_bytes:.3f}, 1/dp = {1 / dp:.3f})")
    check(zero_bytes <= rep_bytes / dp * 1.05,
          f"optimizer state bytes per device are ~1/{dp}")
    del zero_model
    return {"rep_losses": rep_losses, "zero_losses": zero_losses}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def gpt2_124m():
    """GPT-2 124M at published widths; dropout off as in bench_gpt2 (the
    smoke checks a falling loss and greedy parity, not regularisation)."""
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig.gpt2_small()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    return cfg


def blocks_for_hbm_share(cfg, block_size: int, share: float) -> int:
    """Pool blocks that take ``share`` of the device memory still free —
    from the limit the device itself reports, so the pool is as large as a
    deployment's and not the dozen blocks of a unit test."""
    from paddle_tpu.serving import PagedKVPool
    ms = memory_stats()
    free = ms["bytes_limit"] - ms["bytes_in_use"]
    return PagedKVPool.blocks_within_budget(
        int(free * share), num_layers=cfg.num_hidden_layers,
        num_heads=cfg.num_attention_heads, block_size=block_size,
        head_dim=cfg.hidden_size // cfg.num_attention_heads,
        dtype="float32")


def where_arrays_live() -> None:
    """``set_device`` / ``place=`` steer nothing today (nothing calls
    ``Place.jax_device()``): say where things really are."""
    import jax
    import paddle_tpu as paddle
    t = paddle.to_tensor([1.0, 2.0])
    lin = paddle.nn.Linear(4, 4)
    log(f"placement: default place {paddle.get_device()}; to_tensor on "
        f"{sorted(str(d) for d in t._data.devices())}; a fresh parameter "
        f"on {sorted(str(d) for d in lin.weight._data.devices())}; jax "
        f"default device {jax.devices()[0]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the multi-chip phases (needs four "
                         "chips in this one process)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax reports platform {dev.platform!r}, not a "
              f"TPU — nothing is run and no result is printed",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2

    jax.monitoring.register_event_listener(_count_cache_event)
    import paddle_tpu  # noqa: F401  (arms the compile cache at import)
    from paddle_tpu.framework import compile_cache
    cc = compile_cache.status()
    log(f"jax {jax.__version__}, device_kind {dev.device_kind!r}, "
        f"{len(jax.devices())} device(s), bytes_limit "
        f"{memory_stats().get('bytes_limit')}")
    log(f"compile cache: {cc} with {compile_cache.entries()} entries")
    if not cc["enabled"]:
        raise RuntimeError(f"the compile cache is off: {cc['reason']}")
    where_arrays_live()
    cfg = gpt2_124m()

    if args.chips == 4:
        devices = jax.devices()[:4]
        with PhaseMeter("tp_serve"):
            phase_tp_serve(cfg, devices, max_len=1024, block_size=16,
                           num_slots=8, num_blocks=2048,
                           prompt_lens=(40, 200), new_tokens=16)
        gc.collect()
        with PhaseMeter("zero_train"):
            phase_zero_train(cfg, devices, batch=4, seq=1024, steps=4)
    else:
        with PhaseMeter("train"):
            phase_train(cfg, batch=4, seq=1024, steps=8)
        gc.collect()
        with PhaseMeter("eager"):
            phase_eager(cfg, batch=1, seq=256)
        gc.collect()
        with PhaseMeter("serve"):
            phase_serve(cfg, max_len=1024, block_size=16, num_slots=8,
                        num_blocks=blocks_for_hbm_share(cfg, 16, 0.5),
                        prompt_lens=(32, 100, 512), prefix=256, tail=40,
                        burst_lens=(48, 200, 400), new_tokens=32)
        gc.collect()
        with PhaseMeter("axk1_serve"):
            # the benchmark's configuration at its published widths, cut
            # to a toy depth: the dense layer and ONE expert layer
            here = os.path.dirname(os.path.abspath(__file__))
            with open(os.path.join(here, "benchmark", "configs",
                                   "axk1-ep16.json")) as f:
                axk1 = json.load(f)
            phase_axk1_serve(
                dict(axk1["model"], num_hidden_layers=2),
                dtype=axk1["serving"]["dtype"], max_len=2048,
                block_size=int(axk1["serving"]["block_size"]), num_slots=8,
                num_blocks=2048, prefill_budget=512,
                prompt_lens=(40, 700, 1300), new_tokens=24,
                limits=axk1["serving"]["check"]["limits"], width=1536,
                q_block=512)
            with open(os.path.join(here, "benchmark", "configs",
                                   "sdar-30b-a3b-pp8.json")) as f:
                sdar = json.load(f)
            phase_sdar_serve(
                dict(sdar["model"], num_hidden_layers=2),
                dtype=sdar["serving"]["dtype"], max_len=2048,
                block_size=int(sdar["serving"]["block_size"]), num_slots=8,
                num_blocks=1024, prefill_budget=512,
                prompt_lens=(41, 702, 1303), new_tokens=30,
                limits=sdar["serving"]["check"]["limits"], width=1536,
                states=64, q_block=512)
            # the dense global layer and two window expert layers: both
            # cache groups, contexts of up to six windows
            with open(os.path.join(here, "benchmark", "configs",
                                   "mimo-v2-flash-ep16.json")) as f:
                mimo = json.load(f)
            phase_mimo_serve(
                dict(mimo["model"], num_hidden_layers=3),
                dtype=mimo["serving"]["dtype"], max_len=2048,
                block_size=int(mimo["serving"]["block_size"]), num_slots=8,
                num_blocks=1024, prefill_budget=512,
                prompt_lens=(40, 300, 700), new_tokens=24,
                limits=mimo["serving"]["check"]["limits"], width=1024,
                q_block=512)
    n, secs = compile_totals()
    hits, misses = CACHE_EVENTS.values()
    log(f"all phases passed: {n} compiles taking {secs:.1f} s in this "
        f"process; persistent cache {hits} hits / {misses} misses, "
        f"{compile_cache.entries()} entries now")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
