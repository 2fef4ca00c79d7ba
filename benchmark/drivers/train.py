"""Training job: the bench_gpt2 recipe through ``paddle.Model`` and its
donated step, on seeded token batches of one shape. Set-up builds ONE
trainer, drives it through its first steps (the readings ``correct``
rests on) and hands that same object to the window."""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from benchmark.lib import correct as C
from benchmark.lib import harness as H
from benchmark.lib import system as SUT
from benchmark.lib import trace_reduce as TR
from benchmark.lib import traffic as T
from benchmark.lib import train_check as TC
from benchmark.lib import weights as W


IN_FLIGHT = 8


def token_batches(seed: int, count: int, batch: int, seq: int, vocab: int):
    """``count`` (ids, labels) next-token batches whose rows all differ."""
    rng = T.seed_rng(seed, 6)
    tokens = rng.integers(0, vocab, size=(count, batch, seq + 1), dtype=np.int32)
    return [(t[:, :-1].copy(), t[:, 1:].copy()) for t in tokens]


def program_probe(trainer, opt, prefix, model, recipe, seed, batches) -> dict:
    """The trainer's first ``len(batches)`` steps through
    ``train_batch`` — the call the window uses — and the state read
    after them."""
    layers = int(model["num_hidden_layers"])
    start = W.program_state(W.make_weights(seed, model, recipe["dtype"]), prefix)
    losses, grad_norms = [], None
    for step, (ids, labels) in enumerate(batches, start=1):
        losses.append(float(trainer.train_batch([ids, labels],
                                                return_numpy=False)))
        if step == 1:
            grad_norms = TC.program_leaf_norms(
                SUT.optimizer_slots(trainer, opt, "moment1"), layers, prefix,
                scale=1.0 / (1.0 - float(recipe["beta1"])))
    masters = SUT.optimizer_slots(trainer, opt, "master_weight")
    change = TC.program_leaf_norms(masters, layers, prefix, minus=start)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def compare(program: dict, reference: dict) -> dict:
    numbers = {f"loss_gap_step{i + 1}": abs(p - r) for i, (p, r) in
               enumerate(zip(program["losses"], reference["losses"]))}
    numbers["loss_gap_max"] = max(numbers.values())
    numbers["grad_norm_gap"] = C.worst_leaf_gap(program["grad_norms"],
                                                reference["grad_norms"])
    numbers["update_norm_gap"] = C.worst_leaf_gap(
        program["change_norms"], reference["change_norms"],
        C.live_leaves(reference["grad_norms"]))
    return numbers


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    devs = jax.devices()[:1]
    model, recipe = config["model"], config["training"]
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    vocab = int(model["vocab_size"])
    counter = SUT.CompileCounter()
    H.log(f"compile cache: {SUT.compile_cache_status()}")

    trainer, opt, prefix = SUT.build_trainer(model, recipe, seed)
    batches = token_batches(seed, int(traffic["distinct_batches"]), batch,
                            seq, vocab)
    n_probe = int(recipe["check"]["steps"])
    probe = program_probe(trainer, opt, prefix, model, recipe, seed,
                          batches[:n_probe])
    H.log(f"probe losses {[round(v, 5) for v in probe['losses']]}")
    step_i = n_probe

    def step():
        nonlocal step_i
        ids, labels = batches[step_i % len(batches)]
        step_i += 1
        return trainer.train_batch([ids, labels], return_numpy=False)

    last = None
    for _ in range(max(0, int(traffic["warmup_steps"]) - n_probe)):
        last = step()
    if last is not None:
        last.block_until_ready()

    # -- the window: steps until the time is used, at most IN_FLIGHT queued
    # on the device (1.7 s of work at 16 x 1,024: the host may stall that
    # long before the device runs dry), ending in block_until_ready on
    # the last loss
    at_start = counter.snapshot()
    t0 = time.monotonic()
    setup_s = t0 - H.PROCESS_START
    H.log(f"window open (set-up {setup_s:.1f} s)")
    inflight, steps, slice_info = [], 0, None
    trace_at = float(traffic["trace_at_s"]) if trace else None
    while time.monotonic() - t0 < seconds:
        if trace_at is not None and time.monotonic() - t0 >= trace_at:
            trace_at = None
            for x in inflight:
                x.block_until_ready()
            inflight.clear()
            with H.profiler_slice("train") as slice_info:
                traced = [step() for _ in range(int(traffic["trace_steps"]))]
                traced[-1].block_until_ready()
            steps += len(traced)
            continue
        inflight.append(step())
        steps += 1
        if len(inflight) > IN_FLIGHT:
            inflight.pop(0).block_until_ready()
    last = inflight[-1] if inflight else traced[-1]
    last.block_until_ready()
    elapsed = time.monotonic() - t0
    final_loss = float(last)
    at_end = counter.snapshot()
    compiles = SUT.compiles_between(at_start, at_end)
    H.log(f"window closed: {steps} steps in {elapsed:.3f} s, last loss "
          f"{final_loss:.4f}; compiles inside the window: {compiles}")
    device = H.device_report(devs)
    text = SUT.train_step_compiled_text()
    kernels = {k: k in text for k in ("flash_attention_fwd", "flash_attention_dq",
                                      "flash_attention_dkv")}
    H.log(f"kernels in the compiled step: {kernels}; its memory by XLA: "
          f"{SUT.train_step_memory()}")
    del trainer, opt, inflight, last
    gc.collect()

    readings = {"steps": steps, "elapsed_s": elapsed, "batch": batch,
                "seq": seq, "compiles": compiles, "device": device,
                "model": model, "traffic": traffic,
                "device_kind": devs[0].device_kind, "final_loss": final_loss}
    extra_device, breakdown = {}, None
    if trace:
        reduced = TR.reduce_trace(TR.latest_xplane(slice_info["dir"]),
                                  slice_info["window_s"])
        readings["trace"] = reduced
        readings["trace_steps"] = int(traffic["trace_steps"])
        extra_device = {"busy_s": reduced["busy_s"],
                        "window_s": reduced["window_s"]}
        breakdown = {"device_ops": TR.top_ops(reduced),
                     "idle_gaps": TR.label_gaps(
                         TR.gaps(reduced), [], other="between device ops "
                         "(host dispatch of the next step)")}
        H.log(f"trace: {reduced['busy_s']:.3f} s busy of "
              f"{reduced['window_s']:.3f} s over {traffic['trace_steps']} steps")

    # -- the reference, once the program's state is freed ------------------
    t_ref = time.monotonic()
    reference = TC.reference_steps(seed, model, recipe, batches[:n_probe],
                                   int(recipe["check"]["rows_per_block"]))
    numbers = compare(probe, reference)
    H.log(f"reference losses {[round(v, 5) for v in reference['losses']]} "
          f"({time.monotonic() - t_ref:.1f} s)")
    ok, lines = C.verdict(numbers, recipe["check"]["limits"])
    for line in lines:
        H.log(line)
    finite = bool(np.isfinite(final_loss))
    H.log(f"check final loss finite: {final_loss:.4f} {'ok' if finite else 'FAILED'}")
    readings["check"] = numbers
    return {"correct": bool(ok and finite), "setup_s": setup_s,
            "attempted": steps, "failed": 0,
            "end_to_end": {"train_tok_s": steps * batch * seq / elapsed},
            "readings": readings, "device": {**device, **extra_device},
            "breakdown": breakdown}
