"""Benchmark harness — prints ONE JSON line for the driver.

Configs measured:
  gpt2     — GPT-2 124M causal-LM train step, tokens/sec + MFU
  resnet50 — ResNet50 synthetic ImageNet train step, imgs/sec + MFU
  bert     — BERT-base QA fine-tune step, AMP O2 bf16, steps/sec
  lenet    — LeNet/MNIST Model.fit train_batch, imgs/sec

Measurement discipline:
  * data is device-resident — transferred once, reused every step;
  * steps run through the ASYNC engine path (device-scalar loss) and the
    timed region ends in ``block_until_ready`` on the last loss, so jax
    pipelines the chip and the clock still covers all queued work;
  * the Pallas smoke gate runs before each model bench; a kernel that
    cannot lower on this chip is an error naming the kernel, never a
    silent switch to the lax compositions;
  * gpt2/bert additionally record an explicit with/without-Pallas delta;
  * vs_baseline is null — the reference publishes no benchmark numbers,
    so there is no honest ratio to compute.

Process contract: a chip belongs to one process at a time, so the parent
process NEVER imports jax — each benchmark runs in a child with a
timeout, and a crash or hang costs one bench, not the run. A child that
finds no TPU fails: there is no CPU fallback and no result that hides
the device. Benches run cheapest-first and the aggregate JSON line is
re-printed after EVERY completed bench (the driver reads the last
line), so a kill preserves all finished results. The default budget
(840s) and per-child cap (300s) read env overrides
(PADDLE_BENCH_BUDGET_SEC, PADDLE_BENCH_CHILD_TIMEOUT_SEC).
``--dry-run`` is the CPU canary of the instrumentation and reports no
device number.

Reference analog: tools/ci_op_benchmark.sh, tools/check_op_benchmark_result.py
(perf as a CI gate).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

def _peak_flops(device_kind: str):
    """bf16 peak FLOP/s of one chip from the one peaks table
    (framework/program_registry.py PEAK_FLOPS_TABLE). A device that is
    not in the table is an error on this measured path, not a default
    and not an environment override."""
    from paddle_tpu.framework.program_registry import PEAK_FLOPS_TABLE
    dk = device_kind.lower()
    for sub, peak in PEAK_FLOPS_TABLE:
        if sub in dk:
            return peak
    raise KeyError(f"no published peak FLOP/s for device kind "
                   f"{device_kind!r}")


def _device_kind():
    import jax
    return jax.devices()[0].device_kind


def _smoke():
    return os.environ.get("PADDLE_BENCH_SMOKE") == "1"


def _no_pallas():
    return os.environ.get("PADDLE_BENCH_NO_PALLAS") == "1"


def _setup_pallas():
    """Switch the tier off when this child is the explicit no-Pallas leg
    of a with/without delta; otherwise run the TPU smoke gate, which
    raises naming any kernel that cannot run on this chip. Returns the
    state dict recorded in every result."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops import pallas_smoke

    if _no_pallas():
        set_flags({"FLAGS_use_pallas": False})
        return {"pallas": False, "reason": "disabled by request"}
    return {"pallas": pallas_smoke.ensure()}


def _tune_attention(state, batch, seq, heads, head_dim, dtype="bfloat16",
                    is_causal=True):
    """Measure the pallas-vs-lax crossover for this bench's attention
    shape class on the real chip and record it in the persistent autotune
    cache (ops/autotune_cache.py) so dispatch uses the measured winner,
    not the heuristic. Records the outcome into the bench JSON."""
    if not state.get("pallas"):
        return
    import numpy as np
    from paddle_tpu import incubate
    rng = np.random.RandomState(0)
    q = rng.randn(batch, seq, heads, head_dim).astype("float32")
    if dtype == "bfloat16":
        import jax.numpy as jnp
        q = jnp.asarray(q, jnp.bfloat16)
    # skip_if_cached: the per-device autotune cache persists beside the
    # compile cache, so only a cold machine pays the block-config search
    state["attn_tuned"] = incubate.autotune.tune_attention(
        q, q, q, is_causal=is_causal, skip_if_cached=True)


def _timeit_async(step_fn, n_warmup, n_steps):
    """Time n_steps of an async step fn (returns a device scalar),
    blocking only on the last value. Returns (dt, last_loss_float).

    The barrier is ``block_until_ready`` on the last loss: loss N needs
    the params of step N-1, so waiting for it bounds all queued work
    (on a directly attached chip it waits for the whole chain —
    chip_smoke.py's train phase prints the check)."""
    last = None
    for _ in range(n_warmup):
        last = step_fn()
    # the method, not jax.block_until_ready(): that one skips a leaf
    # that is no jax array, and the clock would then time the enqueue
    last.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        last = step_fn()
    last.block_until_ready()
    dt = time.perf_counter() - t0
    return dt, float(last)


# ---------------------------------------------------------------------------
# individual benchmarks (run inside the child process)
# ---------------------------------------------------------------------------

def bench_gpt2(amp_o2=True):
    """GPT-2 124M train step. bf16 AMP O2 is the PRIMARY config (r4
    verdict item 3: fp32 params capped MFU at 0.26 on a bf16-first
    chip); the fp32 variant stays as a secondary parity point."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.distributed.spmd import ParallelEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.optimizer import AdamW

    pallas_state = _setup_pallas()
    if _smoke():
        cfg, batch, seq = GPTConfig.tiny(), 2, 32
    else:
        # bf16 halves activation memory: batch 8 keeps the MXU fed
        cfg, batch, seq = GPTConfig.gpt2_small(), (8 if amp_o2 else 4), 1024
        cfg.hidden_dropout_prob = 0.0
        cfg.attention_dropout_prob = 0.0
        _tune_attention(pallas_state, batch, seq,
                        cfg.num_attention_heads,
                        cfg.hidden_size // cfg.num_attention_heads,
                        dtype="bfloat16" if amp_o2 else "float32")
    paddle.framework.random.seed(0)
    # chunked tied-head CE: never materializes the [B, S, 50304] logits
    # (1.6 GB fp32 at this config) — parity-tested vs the dense path in
    # tests/test_chunked_lm_loss.py
    model = GPTForPretraining(cfg, lm_loss_chunks=8)
    if amp_o2:
        amp.decorate(model, level="O2", dtype="bfloat16")
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                parameters=model.parameters(), multi_precision=amp_o2)
    denv.build_mesh({"data": 1})
    eng = ParallelEngine(model, opt, loss_fn=None, mesh=denv.get_mesh())
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size,
                         (batch, seq + 1)).astype(np.int32)
    # next-token objective (position t predicts t+1) at IDENTICAL
    # shapes/FLOPs: feeding ids as their own labels would train a
    # degenerate copy task (r5 review finding)
    ids, labels = tokens[:, :-1], tokens[:, 1:]
    (dev_ids,), (dev_lbl,) = eng.device_put_batch(
        [ids], [np.ascontiguousarray(labels)])

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    n_warm, n_steps = (1, 2) if _smoke() else (5, 20)
    dt, last_loss = _timeit_async(
        lambda: eng.train_step_async([dev_ids], [dev_lbl]),
        n_warm, n_steps)
    assert np.isfinite(last_loss), f"non-finite loss {last_loss}"
    tokens_per_sec = batch * seq * n_steps / dt
    # config 5 proper is dp×mp over v5e-8; this hardware exposes ONE chip,
    # so the measured mesh is dp=1 — the mp dimension is validated by the
    # driver's CPU dryrun only. Say so in the JSON (r2 verdict weak #10).
    metric = "gpt2_124m_train_tokens_per_sec_1chip_dp1" + (
        "_bf16" if amp_o2 else "_fp32")
    out = {"metric": metric,
           "value": round(tokens_per_sec, 1), "unit": "tokens/sec",
           "n_params": n_params, "batch": batch, "seq": seq,
           "loss": round(last_loss, 4),
           "dtype": "bf16_amp_o2" if amp_o2 else "fp32",
           "mesh": "data=1 (single chip; dpxmp dryrun-validated only)",
           "device_kind": _device_kind(), **pallas_state}
    peak = _peak_flops(out["device_kind"])
    if peak:
        out["mfu"] = round(6.0 * n_params * tokens_per_sec / peak, 4)
    return out


def bench_resnet50():
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import amp
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.distributed.spmd import ParallelEngine
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.models import resnet50

    pallas_state = _setup_pallas()
    batch, hw = (4, 32) if _smoke() else (128, 224)
    # channels-last end to end: the TPU-preferred conv layout (r3 verdict
    # item 3) — no layout-change ops anywhere in the network. Override
    # with PADDLE_BENCH_NCHW=1 to measure the layout delta.
    layout = "NCHW" if os.environ.get("PADDLE_BENCH_NCHW") == "1" \
        else "NHWC"
    paddle.framework.random.seed(0)
    model = resnet50(num_classes=1000, data_format=layout)
    # bf16 AMP O2 on a bf16-first chip (r2 verdict item 3); master weights
    # stay fp32 in the optimizer
    amp.decorate(model, level="O2", dtype="bfloat16")
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters(), multi_precision=True)
    denv.build_mesh({"data": 1})
    eng = ParallelEngine(model, opt, loss_fn=nn.CrossEntropyLoss(),
                         mesh=denv.get_mesh())
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 3, hw, hw).astype(np.float32)
    if layout == "NHWC":
        x = np.ascontiguousarray(x.transpose(0, 2, 3, 1))
    y = rng.randint(0, 1000, (batch, 1)).astype(np.int64)
    (dev_x,), (dev_y,) = eng.device_put_batch([x], [y])

    n_warm, n_steps = (1, 2) if _smoke() else (5, 30)
    dt, last_loss = _timeit_async(
        lambda: eng.train_step_async([dev_x], [dev_y]), n_warm, n_steps)
    assert np.isfinite(last_loss), f"non-finite loss {last_loss}"
    imgs_per_sec = batch * n_steps / dt
    out = {"metric": "resnet50_train_imgs_per_sec",
           "value": round(imgs_per_sec, 1), "unit": "imgs/sec",
           "batch": batch, "dtype": "bf16_amp_o2", "layout": layout,
           "loss": round(last_loss, 4),
           "device_kind": _device_kind(), **pallas_state}
    peak = _peak_flops(out["device_kind"])
    if peak and hw == 224:
        # ~4.09 GFLOPs/img fwd at 224px; train ~= 3x fwd
        out["mfu"] = round(3 * 4.09e9 * imgs_per_sec / peak, 4)
    return out


def bench_bert():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.distributed.spmd import ParallelEngine
    from paddle_tpu.models.bert import BertConfig, BertForQuestionAnswering
    from paddle_tpu.optimizer import AdamW

    pallas_state = _setup_pallas()
    if _smoke():
        cfg = BertConfig(vocab_size=256, hidden_size=64,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=128, max_position_embeddings=64)
        batch, seq = 2, 16
    else:
        cfg = BertConfig()  # base
        cfg.hidden_dropout_prob = 0.0
        cfg.attention_dropout_prob = 0.0
        batch, seq = 32, 128
        # BERT's attention is bidirectional: tune the non-causal class
        _tune_attention(pallas_state, batch, seq,
                        cfg.num_attention_heads,
                        cfg.hidden_size // cfg.num_attention_heads,
                        is_causal=False)
    paddle.framework.random.seed(0)
    import paddle_tpu.nn as nn

    class _QATrain(nn.Layer):
        # positional (ids, start, end) signature for the engine
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, ids, start, end):
            return self.inner(ids, start_positions=start,
                              end_positions=end)

    model = _QATrain(BertForQuestionAnswering(cfg))
    # AMP O2: bf16 parameters + fp32 master weights in the optimizer
    amp.decorate(model, level="O2", dtype="bfloat16")
    opt = AdamW(learning_rate=3e-5, weight_decay=0.01,
                parameters=model.parameters(), multi_precision=True)
    denv.build_mesh({"data": 1})
    eng = ParallelEngine(model, opt, loss_fn=None, mesh=denv.get_mesh())
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    start = rng.randint(0, seq, (batch,)).astype(np.int64)
    end = rng.randint(0, seq, (batch,)).astype(np.int64)
    (dev_ids,), (dev_s, dev_e) = eng.device_put_batch([ids], [start, end])

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    n_warm, n_steps = (1, 2) if _smoke() else (5, 30)
    dt, last_loss = _timeit_async(
        lambda: eng.train_step_async([dev_ids], [dev_s, dev_e]),
        n_warm, n_steps)
    assert np.isfinite(last_loss), f"non-finite loss {last_loss}"
    steps_per_sec = n_steps / dt
    out = {"metric": "bert_base_amp_o2_steps_per_sec",
           "value": round(steps_per_sec, 3), "unit": "steps/sec",
           "batch": batch, "seq": seq, "loss": round(last_loss, 4),
           "device_kind": _device_kind(), **pallas_state}
    peak = _peak_flops(out["device_kind"])
    if peak:
        out["mfu"] = round(
            6.0 * n_params * batch * seq * steps_per_sec / peak, 4)
    return out


def bench_resnet50_pipeline():
    """ResNet50 with the REAL input path — DataLoader batches +
    io.device_prefetch overlapping H2D with compute (r3 verdict item 3's
    input-pipeline-overlap leg). Data loading time is INCLUDED in the
    measurement, unlike the device-resident primary bench."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import amp, io
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.distributed.spmd import ParallelEngine
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.models import resnet50

    pallas_state = _setup_pallas()
    batch, hw = (4, 32) if _smoke() else (128, 224)
    n_warm, n_steps = (1, 2) if _smoke() else (3, 15)
    paddle.framework.random.seed(0)
    model = resnet50(num_classes=1000, data_format="NHWC")
    amp.decorate(model, level="O2", dtype="bfloat16")
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters(), multi_precision=True)
    denv.build_mesh({"data": 1})
    eng = ParallelEngine(model, opt, loss_fn=nn.CrossEntropyLoss(),
                         mesh=denv.get_mesh())

    rng = np.random.RandomState(0)
    n_samples = batch * (n_warm + n_steps)
    imgs = rng.randn(n_samples, hw, hw, 3).astype(np.float32)
    labels = rng.randint(0, 1000, (n_samples, 1)).astype(np.int64)

    class _DS(io.Dataset):
        def __len__(self):
            return n_samples

        def __getitem__(self, i):
            return imgs[i], labels[i]

    loader = io.DataLoader(_DS(), batch_size=batch, shuffle=False,
                           num_workers=0, drop_last=True)
    prefetched = io.device_prefetch(loader, buffer_size=2)

    it = iter(prefetched)
    last = None
    for _ in range(n_warm):
        bx, by = next(it)
        last = eng.train_step_async([bx], [by])
    float(last)
    t0 = time.perf_counter()
    steps = 0
    for bx, by in it:
        last = eng.train_step_async([bx], [by])
        steps += 1
    last_loss = float(last)
    dt = time.perf_counter() - t0
    assert np.isfinite(last_loss), f"non-finite loss {last_loss}"
    return {"metric": "resnet50_pipeline_imgs_per_sec",
            "value": round(batch * steps / dt, 1), "unit": "imgs/sec",
            "batch": batch, "dtype": "bf16_amp_o2", "layout": "NHWC",
            "includes_input_pipeline": True, "loss": round(last_loss, 4),
            "device_kind": _device_kind(), **pallas_state}


def bench_lenet():
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import LeNet

    pallas_state = _setup_pallas()
    batch = 256
    model = paddle.Model(LeNet())
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.network.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    import jax
    x = jax.device_put(rng.randn(batch, 1, 28, 28).astype(np.float32))
    y = jax.device_put(rng.randint(0, 10, (batch, 1)).astype(np.int64))

    n_warm, n_steps = (1, 3) if _smoke() else (6, 50)
    dt, last_loss = _timeit_async(
        lambda: model.train_batch([x], [y], return_numpy=False),
        n_warm, n_steps)
    assert np.isfinite(last_loss), f"non-finite loss {last_loss}"
    return {"metric": "lenet_mnist_train_imgs_per_sec",
            "value": round(batch * n_steps / dt, 1), "unit": "imgs/sec",
            "loss": round(last_loss, 4),
            "device_kind": _device_kind(), **pallas_state}


def bench_eager():
    """Eager-dispatch overhead microbenchmark (r3 verdict weak #4): ops/s
    for a chain of small adds — the 'dygraph feel' cost of python
    dispatch + cache-key hashing + jax.vjp per op, which jitted train
    steps never pay."""
    import numpy as np
    import paddle_tpu as paddle

    pallas_state = _setup_pallas()
    x = paddle.to_tensor(np.ones(16, "float32"))
    for _ in range(50):
        y = x + 1.0  # warm dispatch caches
    n = 1000 if _smoke() else 5000

    def chain(requires_grad):
        t = paddle.to_tensor(np.ones(16, "float32"),
                             stop_gradient=not requires_grad)
        t0 = time.perf_counter()
        y = t
        for _ in range(n):
            y = y + 1.0
        float(y.numpy()[0])
        return n / (time.perf_counter() - t0)

    no_grad_ops = chain(False)
    with_grad_ops = chain(True)
    return {"metric": "eager_small_op_dispatch_per_sec",
            "value": round(no_grad_ops, 1), "unit": "ops/sec",
            "with_grad_tape": round(with_grad_ops, 1),
            "device_kind": _device_kind(), **pallas_state}


def bench_serve():
    """Batched-serve latency/throughput over the Predictor (r4 verdict
    weak #6 'no batching serve story'): jit.save a LeNet, serve it via
    inference.create_predictor + BatchingEngine, report single-request
    p50/p95 latency and 8-client batched throughput."""
    import tempfile
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference, jit
    from paddle_tpu.static import InputSpec
    from paddle_tpu.vision.models import LeNet

    pallas_state = _setup_pallas()
    paddle.framework.random.seed(0)
    net = LeNet()
    net.eval()
    d = tempfile.mkdtemp()
    path = d + "/lenet"
    jit.save(net, path,
             input_spec=[InputSpec([None, 1, 28, 28], "float32")])
    pred = inference.create_predictor(inference.Config(path + ".pdmodel"))
    rng = np.random.RandomState(0)
    one = rng.randn(1, 1, 28, 28).astype(np.float32)

    # single-request latency (latency mode: no gather delay)
    eng = inference.BatchingEngine(pred, max_batch_size=32, max_delay_ms=0)
    n = 5 if _smoke() else 50
    for _ in range(3):
        eng.infer(one)                    # warm the size-1 bucket
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        eng.infer(one)
        lat.append((time.perf_counter() - t0) * 1000)
    import math
    lat.sort()
    p50 = lat[len(lat) // 2]
    p95 = lat[max(0, math.ceil(0.95 * len(lat)) - 1)]

    # batched throughput: 8 concurrent clients, gather window on
    eng2 = inference.BatchingEngine(pred, max_batch_size=64,
                                    max_delay_ms=3.0)
    per_client = 4 if _smoke() else 40
    for _ in range(3):
        eng2.infer(one)

    def client():
        for _ in range(per_client):
            eng2.infer(one)

    threads = [threading.Thread(target=client) for _ in range(8)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    eng.close(), eng2.close()
    total = 8 * per_client
    return {"metric": "serve_lenet_latency_p50_ms", "value": round(p50, 2),
            "unit": "ms", "p95_ms": round(p95, 2),
            "batched_requests_per_sec": round(total / dt, 1),
            "clients": 8, "device_kind": _device_kind(), **pallas_state}


def bench_gpt2_decode():
    """GPT-2 124M autoregressive decode (serving): tokens/sec through the
    compiled static-KV-cache generate loop (models/generation.py — prefill
    + lax.while_loop in ONE XLA program, bf16 params). Greedy with no EOS
    so every run does the full token budget: deterministic work, honest
    tokens/s. Reference analog: fused_multi_transformer decode serving
    (paddle/fluid/operators/fused/fused_multi_transformer_op.cu:1)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    pallas_state = _setup_pallas()
    if _smoke():
        cfg, batch, prompt, new = GPTConfig.tiny(), 2, 8, 8
    else:
        cfg, batch, prompt, new = GPTConfig.gpt2_small(), 8, 128, 128
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    paddle.framework.random.seed(0)
    model = GPTForPretraining(cfg)
    amp.decorate(model, level="O2", dtype="bfloat16")  # bf16 weights+cache
    model.eval()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, prompt)).astype(np.int32)

    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new)
    out.numpy()  # value barrier: compile + first run
    t_compile = time.perf_counter() - t0
    reps = 1 if _smoke() else 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out = model.generate(ids, max_new_tokens=new)
    last = out.numpy()  # the final tokens bound the whole queued chain
    dt = time.perf_counter() - t0
    assert last.shape == (batch, prompt + new)
    tokens_per_sec = batch * new * reps / dt
    return {"metric": "gpt2_124m_decode_tokens_per_sec_1chip",
            "value": round(tokens_per_sec, 1), "unit": "tokens/sec",
            "batch": batch, "prompt_len": prompt, "new_tokens": new,
            "dtype": "bf16", "compile_sec": round(t_compile, 1),
            "ms_per_token_per_seq": round(1000.0 * dt / (reps * new), 2),
            "device_kind": _device_kind(), **pallas_state}


def bench_zero():
    """Replicated vs ZeRO-sharded donated train step (``--bench-zero``):
    the same Adam fit through ``fit(zero=0)`` and ``fit(zero=1)`` (plus
    ``grad_comm='int8'``) on a dp=4 mesh, reporting per-step wall ms
    and — from the PR-7 HBM ledger — per-replica train-state bytes.
    The memory claim IS the gate: the sharded run must report
    opt-state bytes at ~1/dp of the replicated run (stripe padding
    allowed), and the trained params must stay allclose-identical, or
    this bench raises instead of publishing a number. Runs at
    ``--xla_force_host_platform_device_count=4`` on CPU (the child env
    forces it) so the mechanism is measurable every round; on real
    multi-chip backends the same code paths ride ICI."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.profiler import memory as _memory

    pallas_state = _setup_pallas()
    if len(jax.devices()) < 4:
        raise RuntimeError(
            f"bench_zero needs >= 4 devices (have {len(jax.devices())}); "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count=4")
    dp = 4
    denv.build_mesh({"dp": dp})
    batch, d, hidden, classes = 256, 256, 512, 16
    rng = np.random.RandomState(0)
    xs = rng.randn(batch, d).astype(np.float32)
    ys = rng.randint(0, classes, (batch, 1)).astype(np.int64)
    data = TensorDataset([xs, ys])

    def make():
        paddle.framework.random.seed(0)
        net = nn.Sequential(nn.Linear(d, hidden), nn.ReLU(),
                            nn.Linear(hidden, hidden), nn.ReLU(),
                            nn.Linear(hidden, classes))
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=net.parameters()),
                  nn.CrossEntropyLoss())
        return m

    n_warm, n_steps = (1, 3) if _smoke() else (4, 30)

    def run(zero, grad_comm="fp32"):
        m = make()
        # one short fit arms the mode (shards the opt state, compiles
        # the donated step); the timed region then measures warm steps
        m.fit(data, batch_size=batch, epochs=1, log_freq=1,
              shuffle=False, verbose=0, zero=zero, grad_comm=grad_comm)
        dt, last = _timeit_async(
            lambda: m.train_batch([xs], [ys], return_numpy=False),
            n_warm, n_steps)
        m._update_memory_ledger()
        led = _memory.ledger()
        base = m._ledger_base
        return m, {"step_ms": round(dt / n_steps * 1e3, 3),
                   "opt_state_bytes_per_replica":
                       led.get(f"{base}/opt_state"),
                   "params_bytes": led.get(f"{base}/params"),
                   "loss": round(last, 4)}

    m_rep, rep = run(0)
    m_zero, z = run(1)
    m_int8, z8 = run(1, "int8")
    # tolerance sized to Adam's eps-sensitivity: near-zero gradients
    # amplify the exchange's summation-order noise (~1e-7 relative on
    # the grad) into ~1e-5 absolute on the first update — bounded
    # noise, not divergence; real layout corruption is orders beyond
    parity = all(np.allclose(np.asarray(m_rep._params[k]),
                             np.asarray(m_zero._params[k]),
                             rtol=1e-3, atol=1e-4)
                 for k in m_rep._params)
    shrink = rep["opt_state_bytes_per_replica"] / max(
        1, z["opt_state_bytes_per_replica"])
    # the int8 leg is gated too: quantized but still the same training
    # run — finite loss and bounded drift vs the replicated params (a
    # broken scale alignment must not publish a plausible step_ms)
    int8_drift = max(
        float(np.max(np.abs(np.asarray(m_rep._params[k])
                            - np.asarray(m_int8._params[k]))))
        for k in m_rep._params)
    z8["drift_vs_replicated"] = round(int8_drift, 5)
    int8_ok = np.isfinite(z8["loss"]) and int8_drift < 0.05
    # ISSUE-13 collective device timing: the zero fits above ran the
    # sampled same-shape probe (first step always), so the per-kind
    # timing histograms and the exposed-vs-overlapped report must be
    # live — this is the instrument the ZeRO overlap follow-on will be
    # judged by, so its absence is a failed bench, not a missing row
    from paddle_tpu.distributed import collective as _coll
    comm = _coll.communication_report()
    coll_ms = {
        kind: round(row["time_ms"]["p50"], 4)
        for kind, row in comm["per_kind"].items()
        if row["time_ms"] and kind in ("reduce_scatter", "all_gather",
                                       "all_to_all")}
    timing_ok = "reduce_scatter" in coll_ms and "all_gather" in coll_ms \
        and "all_to_all" in coll_ms \
        and comm["exposed_ms_per_step"] is not None
    # the win must be real: ~1/dp per-replica opt state (half counts as
    # failed — padding can only cost one stripe) and identical training
    if not parity or shrink < dp / 2 or not int8_ok or not timing_ok:
        raise RuntimeError(
            f"zero bench invalid: parity={parity} "
            f"opt_state_shrink={shrink:.2f} (expected ~{dp}x) "
            f"int8_drift={int8_drift:.4f} int8_loss={z8['loss']} "
            f"collective_timing={coll_ms}")
    return {"metric": "zero_sharded_step_ms", "value": z["step_ms"],
            "unit": "ms", "dp": dp, "parity": parity,
            "replicated": rep, "zero": z, "zero_int8": z8,
            "opt_state_shrink": round(shrink, 2),
            "step_ms_vs_replicated": round(
                z["step_ms"] / max(1e-9, rep["step_ms"]), 3),
            "collective_time_ms": coll_ms,
            "comm_exposed_ms_per_step": round(
                comm["exposed_ms_per_step"], 4),
            "comm_overlap_headroom_pct":
                None if comm["overlap_headroom_pct"] is None
                else round(comm["overlap_headroom_pct"], 2),
            "device_kind": _device_kind(), **pallas_state}


def bench_spec():
    """Speculative-vs-plain fused decode + int8-vs-fp32 paged pool
    (``--bench-spec``): the two ISSUE-12 multipliers, measured.

    Leg 1 — spec: the same greedy workload through the fused engine
    WITH and WITHOUT a draft (draft = the target itself, the agreeing
    ceiling; ``accept_rate`` and ``tokens_per_step`` are the published
    evidence). Token parity between the two engines is a HARD FAIL —
    a speculative path that changes greedy output is a bug, not a
    number. Leg 2 — int8 blocks: a same-byte-budget capacity ratio
    (``blocks_within_budget``) plus an int8-vs-fp32 token-agreement
    drift check through the engine. Lands in the BENCH artifact
    so ``--history`` gates accept rate, tokens/step and capacity from
    round 1."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import GenerationEngine, PagedKVPool

    pallas_state = _setup_pallas()
    if _smoke() or jax_backend_is_cpu():
        cfg, slots, prompt, new, reqs, spec_k = \
            GPTConfig.tiny(), 4, 12, 16, 8, 4
    else:
        cfg = GPTConfig.gpt2_small()
        cfg.hidden_dropout_prob = 0.0
        cfg.attention_dropout_prob = 0.0
        slots, prompt, new, reqs, spec_k = 8, 64, 64, 16, 4
    paddle.framework.random.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, prompt).astype(np.int32)
               for _ in range(reqs)]
    max_len = prompt + new + 8

    def run(spec_draft, kv_dtype=None, block_size=16):
        eng = GenerationEngine(
            model, num_slots=slots, max_len=max_len, block_size=block_size, kv_dtype=kv_dtype, spec_draft=spec_draft, spec_k=spec_k)
        warm = [eng.submit(p, max_new_tokens=new) for p in prompts]
        [h.result(timeout=600) for h in warm]
        warm_snap = eng._sched.recorder.snapshot()
        warm_last = warm_snap["cycles"][-1]["cycle"] \
            if warm_snap["cycles"] else 0
        t0 = time.perf_counter()
        hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        wall = time.perf_counter() - t0
        snap = eng._sched.recorder.snapshot()
        timed = [c for c in snap["cycles"]
                 if c["cycle"] > warm_last
                 and c.get("decode_dispatch_ms", 0) > 0]
        decode_ms = [c["decode_dispatch_ms"] + c["fetch_ms"]
                     for c in timed]
        decode_cycles = [c for c in timed if not c.get("chunk_tokens")]
        stats = eng.stats()
        eng.close()
        r = {
            "outs": outs,
            "decode_step_ms": (round(float(np.median(decode_ms)), 3)
                               if decode_ms else None),
            "tokens_per_sec": round(reqs * new / wall, 1),
            "wall_ms": round(wall * 1e3, 1),
        }
        if decode_cycles:
            r["tokens_per_step"] = round(
                sum(c.get("emitted", 0) for c in decode_cycles)
                / max(1, sum(c.get("spec_slots") or c.get("active", 0)
                             for c in decode_cycles)), 3)
        if spec_draft is not None:
            r["accept_rate"] = round(stats.get("spec_accept_rate", 0), 4)
            r["spec_tokens_per_cycle"] = round(
                stats.get("spec_tokens_per_cycle", 0), 3)
        return r

    plain = run(None)
    spec = run(model)                    # agreeing draft: the ceiling
    spec_parity = all(np.array_equal(a, b) for a, b in
                      zip(plain.pop("outs"), spec.pop("outs")))
    if not spec_parity:
        raise RuntimeError(
            "speculative decoding bench invalid: greedy spec output "
            "diverged from the plain fused engine")
    if not spec.get("spec_tokens_per_cycle", 0) > 1.0:
        raise RuntimeError(
            f"speculative decoding bench invalid: agreeing draft netted "
            f"{spec.get('spec_tokens_per_cycle')} tokens/cycle (<= 1)")

    # --- int8 leg: capacity ratio + token-agreement drift ------------
    fp_pool_kw = dict(num_layers=cfg.num_hidden_layers,
                      num_heads=cfg.num_attention_heads, block_size=16,
                      head_dim=cfg.hidden_size // cfg.num_attention_heads)
    fp_blocks = slots * (-(-max_len // 16))
    # pure arithmetic — allocating a real fp32 pool just to read its
    # capacity_bytes would zero-fill ~100 MB of device memory for a
    # shape*itemsize multiply
    fp_block_bytes = (cfg.num_hidden_layers * 2
                      * cfg.num_attention_heads * 16
                      * (cfg.hidden_size // cfg.num_attention_heads) * 4)
    budget = (fp_blocks + 1) * fp_block_bytes     # +1: scratch block
    q_blocks = PagedKVPool.blocks_within_budget(budget, dtype="int8",
                                                **fp_pool_kw)
    capacity_ratio = round(q_blocks / fp_blocks, 3)

    def run_pool(kv_dtype):
        # 32-token blocks: what an int8 tile needs
        eng = GenerationEngine(
            model, num_slots=slots, max_len=max_len, block_size=32,
            kv_dtype=kv_dtype)
        hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        eng.close()
        return outs

    fp_outs = run_pool(None)
    q_outs = run_pool("int8")
    gen = np.concatenate([o[prompt:] for o in fp_outs])
    qgen = np.concatenate([o[prompt:] for o in q_outs])
    token_agreement = round(float((gen == qgen).mean()), 4)
    if token_agreement < 0.5:
        raise RuntimeError(
            f"int8 KV bench invalid: only {token_agreement:.0%} of "
            f"greedy tokens agree with fp32 — drift is not 'bounded'")

    out = {"metric": "spec_tokens_per_cycle",
           "value": spec.get("spec_tokens_per_cycle"),
           "unit": "tokens/cycle",
           "spec": spec, "plain": plain, "spec_parity": spec_parity,
           "spec_k": spec_k,
           "int8": {"capacity_ratio_vs_fp32": capacity_ratio,
                    "blocks_fp32": fp_blocks, "blocks_int8": q_blocks,
                    "budget_bytes": budget,
                    "token_agreement_vs_fp32": token_agreement},
           "batch_requests": reqs, "prompt_len": prompt,
           "new_tokens": new, "device_kind": _device_kind(),
           **pallas_state}
    if plain["decode_step_ms"] and spec["decode_step_ms"]:
        # wall multiplier per decode step: how much one verify launch
        # costs vs a plain decode launch (the accept rate buys it back)
        out["spec_step_cost_ratio"] = round(
            spec["decode_step_ms"] / plain["decode_step_ms"], 3)
    if capacity_ratio < 2.0:
        raise RuntimeError(
            f"int8 KV bench invalid: same-budget capacity ratio "
            f"{capacity_ratio} < 2.0")
    return out


def bench_mp():
    """Single-device vs mp=2 tensor-parallel paged serving
    (``--bench-mp``): the ISSUE-15 scale-out, measured.

    The same greedy workload runs through the fused paged engine twice
    — once single-device, once with ``GenerationEngine(mesh=)`` over a
    2-way model-parallel mesh (head-sharded block pool, shard_map'd
    ragged decode, one psum per step). Token parity between the two
    engines is a HARD FAIL — a sharded path that changes greedy output
    is a bug, not a number — and so is a per-device KV ledger that
    isn't exactly 1/mp of the single-device bytes. Reports
    decode-step wall-ms for both legs plus the per-device block bytes;
    lands in the BENCH artifact so ``--history`` gates the shard
    figures from round 1. Needs >= 2 devices — on CPU run under
    XLA_FLAGS=--xla_force_host_platform_device_count=2."""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import GenerationEngine

    pallas_state = _setup_pallas()
    mp = 2
    if len(jax.devices()) < mp:
        raise RuntimeError(
            f"bench_mp needs >= {mp} devices (have {len(jax.devices())});"
            f" on CPU set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={mp}")
    if _smoke() or jax_backend_is_cpu():
        cfg, slots, prompt, new, reqs = GPTConfig.tiny(), 4, 12, 16, 8
    else:
        cfg = GPTConfig.gpt2_small()
        cfg.hidden_dropout_prob = 0.0
        cfg.attention_dropout_prob = 0.0
        slots, prompt, new, reqs = 8, 64, 64, 16
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, prompt).astype(np.int32)
               for _ in range(reqs)]
    max_len = prompt + new + 8

    def run(mesh):
        # fresh model per leg: sharding device_puts the params in place,
        # and both legs must start from the same seeded weights
        paddle.framework.random.seed(0)
        model = GPTForPretraining(cfg)
        model.eval()
        eng = GenerationEngine(
            model, num_slots=slots, max_len=max_len, block_size=16, mesh=mesh)
        warm = [eng.submit(p, max_new_tokens=new) for p in prompts]
        [h.result(timeout=600) for h in warm]
        warm_snap = eng._sched.recorder.snapshot()
        warm_last = warm_snap["cycles"][-1]["cycle"] \
            if warm_snap["cycles"] else 0
        t0 = time.perf_counter()
        hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        outs = [h.result(timeout=600) for h in hs]
        wall = time.perf_counter() - t0
        snap = eng._sched.recorder.snapshot()
        decode_ms = [c["decode_dispatch_ms"] + c["fetch_ms"]
                     for c in snap["cycles"]
                     if c["cycle"] > warm_last
                     and c.get("decode_dispatch_ms", 0) > 0]
        stats = eng.stats()
        eng.close()
        return {
            "outs": outs,
            "decode_step_ms": (round(float(np.median(decode_ms)), 3)
                               if decode_ms else None),
            "tokens_per_sec": round(reqs * new / wall, 1),
            "wall_ms": round(wall * 1e3, 1),
            "kv_block_bytes_per_device": stats["kv_bytes"]["blocks"],
        }

    single = run(None)
    mesh = Mesh(np.array(jax.devices()[:mp]).reshape(mp), ("mp",))
    sharded = run(mesh)
    parity = all(np.array_equal(a, b) for a, b in
                 zip(single.pop("outs"), sharded.pop("outs")))
    if not parity:
        raise RuntimeError(
            "tensor-parallel bench invalid: greedy sharded output "
            "diverged from the single-device engine")
    if sharded["kv_block_bytes_per_device"] * mp \
            != single["kv_block_bytes_per_device"]:
        raise RuntimeError(
            f"tensor-parallel bench invalid: per-device KV block bytes "
            f"{sharded['kv_block_bytes_per_device']} * mp={mp} != "
            f"single-device {single['kv_block_bytes_per_device']}")

    out = {"metric": "mp_decode_step_ms",
           "value": sharded["decode_step_ms"], "unit": "ms",
           "mp": mp, "mp_parity": parity,
           "single": single, "sharded": sharded,
           "kv_bytes_per_device_ratio": round(
               sharded["kv_block_bytes_per_device"]
               / single["kv_block_bytes_per_device"], 3),
           "batch_requests": reqs, "prompt_len": prompt,
           "new_tokens": new, "device_kind": _device_kind(),
           **pallas_state}
    if single["decode_step_ms"] and sharded["decode_step_ms"]:
        # wall multiplier per decode step: on a host-platform CPU mesh
        # the psum costs more than the halved heads save, so this is a
        # plumbing figure, not a speedup claim — the speedup story
        # needs real interconnect
        out["mp_step_cost_ratio"] = round(
            sharded["decode_step_ms"] / single["decode_step_ms"], 3)
    return out


def jax_backend_is_cpu():
    import jax
    return jax.default_backend() == "cpu"


BENCHES = {"gpt2": bench_gpt2, "resnet50": bench_resnet50,
           "bert": bench_bert, "lenet": bench_lenet,
           "gpt2_fp32": lambda: bench_gpt2(amp_o2=False),
           "resnet50_pipeline": bench_resnet50_pipeline,
           "eager": bench_eager, "serve": bench_serve,
           "gpt2_decode": bench_gpt2_decode,
           "zero": bench_zero, "spec": bench_spec, "mp": bench_mp}


# ---------------------------------------------------------------------------
# regression gate (--compare / --history)
# ---------------------------------------------------------------------------
# The bench trajectory only matters if something reads it: --compare
# diffs the key metrics of two bench artifacts with per-metric
# tolerances and exits nonzero on regression; --history appends an
# artifact's flattened metrics to BENCH_history.jsonl, gating against
# the previous entry — so a series of bench artifacts accumulates into
# a guarded trend instead of a pile of unread files. Reference analog:
# tools/check_op_benchmark_result.py (perf diff as a CI gate).

DEFAULT_TOLERANCE = 0.05          # 5% relative, either direction

# wider tolerances where run-to-run noise is structural: eager dispatch
# is host-scheduler bound, serve latency percentiles on shared CI boxes
# jitter, compile seconds depend on a warm or cold cache
PER_METRIC_TOLERANCE = {
    "eager": 0.25,
    "serve": 0.25,
    "serve.p95_ms": 0.30,
}


def _tolerance_for(name, tolerances, default):
    """Exact name first, then the structural-noise classes: latency
    PERCENTILES jitter on shared
    boxes far beyond the throughput default."""
    if name in tolerances:
        return tolerances[name]
    if name.endswith(".p95") or name.endswith(".p95_ms"):
        return max(default, 0.30)
    return default


def _load_bench_doc(path):
    """Load a bench artifact: the aggregate JSON line (--dry-run /
    _emit output saved to a file) or
    a driver wrapper ({"tail": "<stdout>"} — the artifact is the last
    parseable JSON line of the tail)."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
        for line in reversed(text.strip().splitlines()):
            try:
                doc = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
        if doc is None:
            raise ValueError(f"{path}: no parseable JSON document")
    if isinstance(doc, dict) and "tail" in doc and "extras" not in doc:
        for line in reversed(str(doc["tail"]).strip().splitlines()):
            try:
                cand = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(cand, dict) and "metric" in cand:
                return cand
    if not isinstance(doc, dict):
        raise ValueError(
            f"{path}: artifact is not a JSON object (got "
            f"{type(doc).__name__})")
    return doc


def _flatten_bench_doc(doc):
    """{name: {"value", "unit", "metric"}} for every gateable number in
    an artifact."""
    out = {}

    def add(name, rec):
        if not isinstance(rec, dict) or "error" in rec:
            return
        v = rec.get("value")
        if not isinstance(v, (int, float)):
            return
        out[name] = {"value": float(v), "unit": str(rec.get("unit", "")),
                     "metric": str(rec.get("metric", name))}
        if isinstance(rec.get("mfu"), (int, float)):
            out[f"{name}.mfu"] = {"value": float(rec["mfu"]),
                                  "unit": "mfu", "metric": f"{name}.mfu"}
        if isinstance(rec.get("p95_ms"), (int, float)):
            out[f"{name}.p95_ms"] = {"value": float(rec["p95_ms"]),
                                     "unit": "ms",
                                     "metric": f"{name}.p95_ms"}

    extras = doc.get("extras")
    if isinstance(extras, dict):
        for name, rec in sorted(extras.items()):
            add(name, rec)
        return out
    add(doc.get("metric", "value"), doc)
    return out


def _lower_is_better(entry) -> bool:
    m = entry["metric"]
    return entry["unit"] == "ms" or m.endswith("_ms") or \
        m.endswith(".p95") or "latency" in m


def compare_flat(old_m, new_m, tolerance=DEFAULT_TOLERANCE,
                 tolerances=None):
    """Diff two flattened metric maps. Returns (rows, regressions,
    missing): rows are (name, old, new, rel_delta, unit, verdict);
    a metric beyond its tolerance in the WORSE direction regresses.
    Metrics present only on one side are reported, never gated — bench
    rounds legitimately differ in which children survived the budget."""
    tolerances = {**PER_METRIC_TOLERANCE, **(tolerances or {})}
    rows, regressions = [], []
    for name in sorted(set(old_m) & set(new_m)):
        o, n = old_m[name], new_m[name]
        tol = _tolerance_for(name, tolerances, tolerance)
        if o["value"]:
            delta = (n["value"] - o["value"]) / abs(o["value"])
        else:
            delta = 0.0 if n["value"] == o["value"] else \
                (1.0 if n["value"] > o["value"] else -1.0)
        worse = delta > tol if _lower_is_better(o) else delta < -tol
        better = delta < -tol if _lower_is_better(o) else delta > tol
        verdict = "REGRESSED" if worse else \
            ("improved" if better else "ok")
        rows.append((name, o["value"], n["value"], delta, o["unit"],
                     verdict))
        if worse:
            regressions.append(name)
    # BOTH one-sided sets are reported (never gated): an operator must
    # be able to tell a metric RENAME (old-only + new-only pair) from a
    # dropped benchmark (old-only alone)
    missing = {"old_only": sorted(set(old_m) - set(new_m)),
               "new_only": sorted(set(new_m) - set(old_m))}
    return rows, regressions, missing


def _print_compare(rows, regressions, missing, label_a, label_b):
    w = max([len(r[0]) for r in rows] + [10])
    print(f"{'metric':<{w}}  {'old':>14}  {'new':>14}  {'delta':>8}  "
          f"verdict   ({label_a} -> {label_b})")
    for name, old, new, delta, unit, verdict in rows:
        print(f"{name:<{w}}  {old:>14,.3f}  {new:>14,.3f}  "
              f"{delta:>+7.1%}  {verdict}  [{unit}]")
    for name in missing["old_only"]:
        print(f"{name:<{w}}  (present in {label_a} only — not gated)")
    for name in missing["new_only"]:
        print(f"{name:<{w}}  (present in {label_b} only — not gated)")
    if regressions:
        print(f"REGRESSION: {', '.join(regressions)}")
    elif rows:
        print("no regressions")
    else:
        print("WARNING: no common metrics to compare")


def run_compare(argv):
    """``bench.py --compare A.json B.json [--tolerance 0.05]``: exit 1
    when B regresses any shared metric beyond tolerance vs A."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"))
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE)
    args = ap.parse_args(argv)
    old_path, new_path = args.compare
    rows, regressions, missing = compare_flat(
        _flatten_bench_doc(_load_bench_doc(old_path)),
        _flatten_bench_doc(_load_bench_doc(new_path)),
        tolerance=args.tolerance)
    _print_compare(rows, regressions, missing,
                   os.path.basename(old_path), os.path.basename(new_path))
    sys.exit(1 if regressions or not rows else 0)


def run_history(argv):
    """``bench.py --history ARTIFACT.json [--history-file F.jsonl]``:
    gate the artifact against the history's last entry (exit 1 on
    regression), then append it — the trajectory accumulates either
    way, so one regressed round is visible in the trend, not lost."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--history", metavar="ARTIFACT")
    ap.add_argument("--history-file",
                    default=os.path.join(HERE, "BENCH_history.jsonl"))
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE)
    args = ap.parse_args(argv)
    flat = _flatten_bench_doc(_load_bench_doc(args.history))
    if not flat:
        # same contract as --compare's empty-intersection case: a
        # metric-less artifact means the bench output format broke —
        # appending it would make the NEXT round's compare vacuously
        # green too, greenlighting two broken rounds in a row
        print(f"ERROR: {args.history} yields no gateable metrics; "
              f"not appended")
        sys.exit(1)
    prev = None
    if os.path.exists(args.history_file):
        with open(args.history_file) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        prev = json.loads(line)
                    except json.JSONDecodeError:
                        continue
    rc = 0
    if prev and isinstance(prev.get("metrics"), dict):
        rows, regressions, missing = compare_flat(
            prev["metrics"], flat, tolerance=args.tolerance)
        _print_compare(rows, regressions, missing,
                       f"history[{prev.get('n', '?')}]",
                       os.path.basename(args.history))
        # same contract as run_compare: ZERO shared metrics means the
        # gate compared nothing (a metric rename, a format break) — that
        # must fail loudly, not greenlight this round and the next
        rc = 1 if regressions or not rows else 0
    n = (prev.get("n", 0) + 1) if prev else 1
    with open(args.history_file, "a") as f:
        f.write(json.dumps({"n": n, "ts": time.time(),
                            "source": os.path.abspath(args.history),
                            "metrics": flat}) + "\n")
    print(f"appended entry {n} to {args.history_file}")
    sys.exit(rc)


# ---------------------------------------------------------------------------
# parent orchestration
# ---------------------------------------------------------------------------

def _run_child(name: str, timeout: float, no_pallas: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    if no_pallas:
        env["PADDLE_BENCH_NO_PALLAS"] = "1"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", name],
            env=env, cwd=HERE, timeout=timeout,
            capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout:.0f}s"}
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("RESULT "):
            try:
                return json.loads(line[len("RESULT "):])
            except json.JSONDecodeError:
                break
    return {"error": f"rc={proc.returncode}: "
                     f"{(proc.stderr or proc.stdout)[-800:]}"}


def _child(name: str):
    """Entry of a bench child — the one process that touches jax. A
    measurement needs the chip: without one this fails, it does not
    fall back. The persistent compile cache is on (directory rule:
    framework/compile_cache.py)."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench child {name!r}: no TPU (jax reports {platform!r}); "
            f"bench.py measures on the chip only — the CPU canary is "
            f"--dry-run")
    from paddle_tpu.framework import compile_cache
    compile_cache.enable()
    print("RESULT " + json.dumps(BENCHES[name]()))


# benches the headline should prefer, most-informative first; the RUN
# order is cheapest-first so a driver timeout still leaves results behind
_HEADLINE_PREF = ["gpt2", "resnet50", "bert", "lenet"]


def _emit(results):
    """Print the aggregate JSON line for whatever has completed SO FAR.

    Called after every finished bench: the driver reads the LAST line of
    stdout, so each re-emission supersedes the previous one and a
    driver-side kill preserves every bench that already ran."""
    headline = None
    for name in _HEADLINE_PREF:
        r = results.get(name)
        if r and "error" not in r:
            headline = r
            break
    if headline is None:
        headline = {"metric": "bench_failed", "value": 0.0, "unit": "none"}
    # vs_baseline: the reference publishes NO benchmark numbers, so there
    # is no real ratio to compute; null is the honest value.
    out = {"metric": headline["metric"], "value": headline["value"],
           "unit": headline["unit"], "vs_baseline": None,
           "extras": results}
    if "mfu" in headline:
        out["mfu"] = headline["mfu"]
    print(json.dumps(out), flush=True)


def main():
    budget = float(os.environ.get("PADDLE_BENCH_BUDGET_SEC", "840"))
    child_cap = float(os.environ.get("PADDLE_BENCH_CHILD_TIMEOUT_SEC",
                                     "300"))
    t_start = time.perf_counter()
    results = {}

    def remaining():
        return budget - (time.perf_counter() - t_start)

    def child_timeout():
        return min(child_cap, remaining())

    # --- primary pass, cheapest-first so a timeout preserves the most
    # finished results. gpt2 precedes resnet50: it carries the MFU
    # target; the heavy benches get a raised cap for a cold compile when
    # the budget allows. A child that fails (no TPU, a kernel refused, a
    # crash) is recorded as an error — nothing is retried on another
    # path or another device.
    order = ["lenet", "bert", "gpt2", "resnet50"]
    heavy = {"gpt2", "resnet50"}
    for name in order:
        if remaining() < 90:
            results[name] = {"error": "skipped: bench time budget exhausted"}
            continue
        cap = child_timeout()
        if name in heavy and remaining() > 300:
            # up to 450s for a cold compile, always keeping 60s to emit;
            # never BELOW the default cap (raise-only)
            cap = max(cap, min(450.0, remaining() - 60.0))
        results[name] = _run_child(name, timeout=cap)
        _emit(results)

    # --- second pass, strictly best-effort: fp32 GPT-2 parity point
    # (the primary gpt2 bench is bf16 AMP O2) and the explicit
    # with/without-Pallas delta for the attention-heavy configs
    if not _smoke() and remaining() > 90 and \
            "error" not in results.get("gpt2", {}):
        extra = _run_child("gpt2_fp32", timeout=child_timeout())
        if "error" not in extra:
            results["gpt2_fp32"] = extra
            _emit(results)
    if not _smoke() and remaining() > 90 and \
            "error" not in results.get("resnet50", {}):
        # real-input-path variant: DataLoader + device_prefetch overlap
        extra = _run_child("resnet50_pipeline", timeout=child_timeout())
        if "error" not in extra:
            results["resnet50_pipeline"] = extra
            _emit(results)
    for name, floor, cap in (
            # eager-dispatch overhead microbenchmark
            ("eager", 60, 120.0),
            # batched-serve latency/throughput
            ("serve", 60, 180.0),
            # compiled static-cache decode throughput
            ("gpt2_decode", 90, child_cap),
            # replicated-vs-ZeRO donated train step + per-replica
            # train-state bytes (needs four chips)
            ("zero", 90, child_cap),
            # speculative-vs-plain fused decode + int8-vs-fp32 pool
            # capacity/drift (greedy parity HARD-FAILs inside)
            ("spec", 90, child_cap),
            # single-vs-mp=2 tensor-parallel paged serving (token parity
            # and the 1/mp per-device KV ledger HARD-FAIL inside; needs
            # two chips)
            ("mp", 90, child_cap)):
        if remaining() > floor:
            extra = _run_child(name, timeout=min(cap, child_timeout()))
            if "error" not in extra:
                results[name] = extra
                _emit(results)
    if not _smoke():
        for name in ("gpt2", "bert"):
            if remaining() < 90 or not results.get(name, {}).get("pallas"):
                continue
            off = _run_child(name, timeout=child_timeout(),
                             no_pallas=True)
            if "error" not in off:
                results[f"{name}_nopallas"] = off
                if off["value"]:
                    results[name]["pallas_speedup"] = round(
                        results[name]["value"] / off["value"], 3)
                _emit(results)

    _emit(results)


def dry_run():
    """Offline observability+perf smoke (tier-1 gate:
    tests/test_bench_dryrun.py).

    Runs one tiny train step PLUS a short async fit() on the CPU backend
    under an armed profiler.profile() session and asserts the whole
    metrics surface works end to end: monitor counters non-empty, a
    chrome trace with nested span categories, a Prometheus exposition,
    the async-fast-path counters (``hapi/host_sync`` bounded at
    O(steps/log_freq), prefetch put/wait histograms), and the persistent
    XLA compile cache populating entries. PR-3 additions: the fit runs
    with ``analyze='warn'`` (jaxpr linter pre-flight), a GPT-2-class and
    a ResNet-class donated train step are ``analyze()``d and must report
    ZERO error-severity findings, the repo self-lint (AST rules over
    paddle_tpu/) must be clean, and the ``analysis/*`` +
    ``dispatch/retrace_cause`` counters must be populated. The serving
    canary: a short continuous-batching serve over the tiny GPT
    (paddle_tpu/serving/) must complete every request token-identical
    to ``models.generate`` with live
    ``serving/ttft_ms``/``serving/tokens_per_sec``/``serving/tpot_ms``
    metrics, a repeated system prompt scoring ``serving/prefix_hit``
    with prefill tokens saved, a long prompt fed in chunks, a
    zero-error ``analyze()`` bill on the fused step, exactly one
    trace per (q, table) bucket, request traces complete in lifecycle
    order with derived TTFT/TPOT, per-engine stats() latency present
    and the always-on flight recorder non-empty. ISSUE-10
    addition: the training numerics canary — a clean
    ``fit(numerics='record')`` leaves ``hapi/grad_norm``/
    ``hapi/grad_clip_ratio`` live with ZERO extra compiled programs on
    a warm re-fit (the audit is fused into the donated step), and an
    injected-inf fit in ``warn`` mode trips the NaN/Inf sentinel at the
    exact step within one flush window, dumps a round-tripping anomaly
    postmortem JSON, and keeps ``hapi/host_sync`` at the PR-2 windowed
    budget. PR-19 addition: the HTTP front door on an ephemeral port —
    non-streamed /v1/completions byte-identical to an in-process greedy
    submit, exact SSE framing, a 429 off the per-tenant token bucket
    with Retry-After, and a malformed body answered 400 without
    killing the server thread. Prints the stats summary to stderr and ONE JSON line to
    stdout; exits nonzero when any assertion fails, so CI catches an
    instrumentation or fast-path regression before it costs a real
    benchmark round."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # ISSUE-7: pin a fake per-device peak so the MFU math (hapi/mfu,
    # serving_mfu) is exercised end to end on the CPU backend — without
    # the override CPU honestly reports FLOP/s only, never an MFU
    os.environ.setdefault("PADDLE_TPU_PEAK_FLOPS", "1e12")
    import tempfile

    # a throwaway cache directory, placed the one way the cache can be
    # placed (the variable, before jax is imported): this CPU canary
    # asserts that entries APPEAR, which a warm fixed directory could
    # not show. No measured path does this.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          tempfile.mkdtemp(prefix="paddle_dryrun_xla_"))

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import profiler
    from paddle_tpu.framework import compile_cache, monitor
    from paddle_tpu.framework import program_registry
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.profiler import memory as _memory

    # floor at 0 so the tiny CPU compiles of this canary produce entries
    # (production keeps jax's >1s floor)
    cache_on = compile_cache.enable(min_compile_time_secs=0)
    cache_dir = compile_cache.status()["dir"]

    net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 4))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 4, (8, 1)).astype(np.int64)
    n_batches, log_freq = 8, 4
    xs = rng.randn(8 * n_batches, 16).astype(np.float32)
    ys = rng.randint(0, 4, (8 * n_batches, 1)).astype(np.int64)

    monitor.stat_reset()
    with profiler.profile() as sess:
        loss = model.train_batch([x], [y])
        # async fast path: donated step + device_prefetch input +
        # windowed host syncs, all counter-asserted below; analyze='warn'
        # additionally runs the jaxpr linter over the built train step
        # on the first batch (tracing only, nothing executes twice)
        import warnings as _warnings
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore", UserWarning)
            model.fit(TensorDataset([xs, ys]), batch_size=8, epochs=1,
                      log_freq=log_freq, shuffle=False, verbose=0,
                      analyze="warn")

        # analyze() pre-flight of the two zoo train steps (tiny smoke
        # configs, same model classes as the north-star benches): the
        # donated GPT-2 and ResNet steps must carry ZERO error-severity
        # findings — this is the standing guard for the PR-2 donation/
        # frozen-grad bug classes. Tracing the full networks also
        # populates dispatch/retrace_cause organically (shared op sites
        # re-trace at each new per-layer shape class).
        from paddle_tpu import analysis

        def _zoo_reports():
            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.vision.models import resnet18
            import paddle_tpu.nn.functional as F

            paddle.framework.random.seed(0)
            cfg = GPTConfig.tiny()
            gpt = GPTForPretraining(cfg)
            gm = paddle.Model(gpt)
            gm.prepare(
                paddle.optimizer.AdamW(learning_rate=1e-4,
                                       parameters=gpt.parameters()),
                lambda logits, lbl: F.cross_entropy(
                    logits.reshape([-1, cfg.vocab_size]),
                    lbl.reshape([-1])))
            ids = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
            g_rep = analysis.analyze_model(gm, [ids], [ids.astype(np.int64)],
                                           name="gpt2_tiny.train_step")

            res = resnet18(num_classes=10)
            rm = paddle.Model(res)
            rm.prepare(
                paddle.optimizer.Momentum(learning_rate=0.1,
                                          parameters=res.parameters()),
                nn.CrossEntropyLoss())
            img = rng.randn(2, 3, 32, 32).astype(np.float32)
            lbl = rng.randint(0, 10, (2, 1)).astype(np.int64)
            r_rep = analysis.analyze_model(rm, [img], [lbl],
                                           name="resnet18.train_step")
            return g_rep, r_rep

        gpt_report, resnet_report = _zoo_reports()
        lint_findings = analysis.lint_repo()

        # serving canary: mixed-length requests through GenerationEngine
        # — a shared two-block system prompt (prefix hits) and a
        # 40-token prompt fed in chunks under an 8-token budget. Every
        # request completes token-identical to per-request
        # models.generate; the serving/* metrics, the request traces and
        # the flight recorder are live; the fused step analyzes clean
        # (donation-safe, host-sync-free) and every (q, table) bucket
        # traced exactly once. The SLO tracker and the zero-dependency
        # ops HTTP server (PR 16) ride the same engine. Counts and
        # parity only: what a cycle costs is benchmark/run.py's, on the
        # chip.
        def _serving_canary():
            import urllib.error
            import urllib.request

            from paddle_tpu.framework import trace_probe
            from paddle_tpu.framework.metrics import parse_prometheus
            from paddle_tpu.models import generate
            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.serving import (GenerationEngine, OpsServer,
                                            SLOTracker)

            paddle.framework.random.seed(0)
            model = GPTForPretraining(GPTConfig.tiny())
            model.eval()
            eng = GenerationEngine(model, num_slots=4, max_len=64,
                                   block_size=8, prefill_budget=8)
            slo = SLOTracker(name="dryrun_slo")
            # CPU-scale SLO: the canary asserts the measurement works,
            # not that an untuned CPU backend meets a production SLO
            slo.add_objective("ttft_canary", metric="ttft_ms",
                              target_ms=60_000.0, goal=0.95)
            slo.attach_engine(eng)
            srv = OpsServer(target=eng, slo=slo).start()
            system = np.arange(2, 18, dtype=np.int32)     # two full blocks
            prompts = [np.concatenate([system, [30]])] \
                + [np.concatenate([system, np.arange(40, 40 + n,
                                                     dtype=np.int32)])
                   for n in (1, 5, 9, 2)] \
                + [np.arange(1, 1 + n, dtype=np.int32) for n in (3, 7)] \
                + [np.arange(2, 42, dtype=np.int32)]   # chunks at budget 8
            # the system prompt's blocks are computed once, then served
            # from the prefix cache under mixed lengths
            handles = [eng.submit(prompts[0], max_new_tokens=5)]
            handles[0].result(timeout=300)
            handles += [eng.submit(p, max_new_tokens=5)
                        for p in prompts[1:]]
            outs = [h.result(timeout=300) for h in handles]
            n = len(prompts)
            prom_samples = parse_prometheus(urllib.request.urlopen(
                srv.url + "/metrics", timeout=30).read().decode())["samples"]
            slo_live = any(name == "slo_attainment"
                           for name, _labels in prom_samples)
            healthz_live = urllib.request.urlopen(
                srv.url + "/healthz", timeout=30).status == 200
            tracez = json.loads(urllib.request.urlopen(
                srv.url + "/tracez", timeout=30).read().decode())
            tail = next(iter(tracez["engines"].values()))
            tracez_ok = (len(tail["recent"]) == n
                         and tracez["slo"]["objectives"]
                         ["ttft_canary"]["total"] == n)
            report = eng.analyze()
            recorder = eng.dump_flight_recorder()
            stats = eng.stats()
            eng.close()
            # a closed engine flips /healthz to 503 while the server
            # itself (and /statusz) stays up
            try:
                urllib.request.urlopen(srv.url + "/healthz", timeout=30)
                healthz_flips = False
            except urllib.error.HTTPError as e:
                healthz_flips = e.code == 503
            srv.close()
            slo.close()
            sites = {k: v for k, v in trace_probe.snapshot().items()
                     if k.startswith("serving/")
                     and k.endswith(f"#{eng._eid}")}
            return {
                "requests": n,
                "completed": monitor.stat_get("serving/completed"),
                "submitted": monitor.stat_get("serving/requests"),
                "parity": all(
                    np.array_equal(o, generate(
                        model, p[None, :], max_new_tokens=5).numpy()[0])
                    for p, o in zip(prompts, outs)),
                "report": report,
                # the fused (q, table) programs are the ONLY serving
                # programs, each traced once
                "one_trace": bool(sites) and all(
                    k.startswith("serving/fused[")
                    and s["traces"] == 1 and not s["causes"]
                    for k, s in sites.items()),
                "stats": stats,
                "traces_complete": all(
                    h.trace.completed
                    and h.trace.t("submit") <= h.trace.t("admitted")
                    <= h.trace.t("first_token") <= h.trace.finished_at
                    and h.trace.ttft_ms is not None
                    for h in handles),
                "engine_latency_present":
                    stats["ttft_ms"] is not None
                    and stats["tpot_ms"] is not None
                    and stats["ttft_ms"]["count"] == n,
                "flight_recorder_nonempty":
                    len(recorder["cycles"]) > 0
                    and len(recorder["events"]) > 0,
                # PR-16 ops surface: live scrape over HTTP carried the
                # SLO series, health answered 200 then flipped 503 on
                # close, tracez served the tail-sampled traces
                "ops_scrape": len(prom_samples) > 0 and slo_live,
                "ops_healthz": healthz_live and healthz_flips,
                "ops_tracez": tracez_ok,
                "ops_goodput": (stats.get("goodput_rps") or 0) > 0,
            }

        serving_canary = _serving_canary()

        # ISSUE-12 speculative-decoding canary: the same greedy
        # workload through the plain fused engine and a speculating one
        # (agreeing draft) must be token-identical, the accept
        # telemetry must be live, and every spec (q, table) bucket must
        # trace exactly ONCE — verify rows must not cause a retrace
        # storm. An int8-block engine rides the same prompts to prove
        # the quantized path end to end.
        def _spec_canary():
            from paddle_tpu.framework import trace_probe
            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.serving import GenerationEngine

            paddle.framework.random.seed(0)
            model = GPTForPretraining(GPTConfig.tiny())
            model.eval()
            prompts = [np.arange(1, 1 + n, dtype=np.int32)
                       for n in (3, 9, 17, 5)]
            outs = {}
            accept_before = monitor.stat_get("serving/spec_accept")
            for kind in ("plain", "spec"):
                eng = GenerationEngine(
                    model, num_slots=4, max_len=64, block_size=8, prefill_budget=16,
                    spec_draft=model if kind == "spec" else None,
                    spec_k=3)
                handles = [eng.submit(p, max_new_tokens=6)
                           for p in prompts]
                outs[kind] = [h.result(timeout=300) for h in handles]
                if kind == "spec":
                    # warm second wave: zero retraces on warm buckets
                    # (a bucket first-compiling in wave 2 would show
                    # traces == 1 too; traces > 1 or a recorded cause
                    # is the storm signal)
                    handles = [eng.submit(p, max_new_tokens=6)
                               for p in prompts]
                    outs["spec_warm"] = [h.result(timeout=300)
                                         for h in handles]
                    sites = {k: v
                             for k, v in trace_probe.snapshot().items()
                             if k.endswith(f"#{eng._eid}")}
                    stats = eng.stats()
                    spec_sites = {
                        k: v for k, v in sites.items()
                        if k.startswith("serving/spec[")}
                eng.close()
            # int8 blocks over the same prompts (at the block size
            # their tile needs), vs the plain outputs
            eng = GenerationEngine(model, num_slots=4, max_len=64,
                                   kv_dtype="int8")
            handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
            int8_outs = [h.result(timeout=300) for h in handles]
            int8_stats = eng.stats()
            eng.close()
            gen = np.concatenate([o[len(p):]
                                  for o, p in zip(outs["plain"], prompts)])
            qgen = np.concatenate([o[len(p):]
                                   for o, p in zip(int8_outs, prompts)])
            return {
                "parity": all(np.array_equal(a, b) for a, b in
                              zip(outs["plain"], outs["spec"])),
                "warm_parity": all(np.array_equal(a, b) for a, b in
                                   zip(outs["plain"], outs["spec_warm"])),
                "accept_live":
                    monitor.stat_get("serving/spec_accept")
                    - accept_before > 0
                    and stats["spec_proposed"] > 0,
                "accept_rate": stats["spec_accept_rate"],
                "tokens_per_cycle": stats.get("spec_tokens_per_cycle"),
                "one_trace": bool(spec_sites) and all(
                    s["traces"] == 1 and not s["causes"]
                    for s in spec_sites.values()),
                "zero_warm_retraces": all(
                    s["traces"] == 1 and not s["causes"]
                    for s in sites.values()),
                "int8_dtype": int8_stats["kv_dtype"],
                "int8_token_agreement":
                    float((gen == qgen).mean()),
            }

        spec_canary = _spec_canary()

        # front-door canary (PR 19): the OpenAI-style /v1/completions
        # surface on an ephemeral port — one non-streamed request whose
        # wire tokens match an in-process submit exactly (greedy
        # parity), one SSE stream with correct framing (per-token data:
        # chunks, a finish_reason chunk, the [DONE] sentinel), one
        # rate-limited tenant drawing a 429 with Retry-After, and a
        # malformed body answered 400 with the server thread surviving
        # to serve the next request.
        def _frontdoor_canary():
            import urllib.error
            import urllib.request

            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.serving import FrontDoor, GenerationEngine

            paddle.framework.random.seed(0)
            m = GPTForPretraining(GPTConfig.tiny())
            m.eval()
            eng = GenerationEngine(m, num_slots=2, max_len=32,
                                   min_bucket=8)
            door = FrontDoor(eng, tenant_limits={"starved": (5.0, 12.0)})
            srv = door.start()

            def post(doc, tenant="canary", raw=None):
                req = urllib.request.Request(
                    srv.url + "/v1/completions",
                    data=raw if raw is not None
                    else json.dumps(doc).encode(),
                    headers={"Content-Type": "application/json",
                             "X-Tenant": tenant})
                try:
                    with urllib.request.urlopen(req, timeout=120) as r:
                        return r.status, json.loads(r.read())
                except urllib.error.HTTPError as e:
                    return e.code, json.loads(e.read())

            prompt = [3, 1, 4, 1, 5]
            st, doc = post({"prompt": prompt, "max_tokens": 6})
            inproc = [int(t) for t in
                      eng.submit(prompt, max_new_tokens=6).stream()]
            roundtrip = (st == 200
                         and doc["choices"][0]["token_ids"] == inproc
                         and doc["usage"]["completion_tokens"] == 6)

            req = urllib.request.Request(
                srv.url + "/v1/completions",
                data=json.dumps({"prompt": prompt, "max_tokens": 4,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Tenant": "canary"})
            with urllib.request.urlopen(req, timeout=120) as r:
                ctype = r.headers["Content-Type"]
                frames = [f[len("data: "):] for f in
                          r.read().decode().strip().split("\n\n")]
            toks = [json.loads(f)["choices"][0]["token_id"]
                    for f in frames[:-2]]
            final = json.loads(frames[-2])["choices"][0]
            sse_ok = (ctype == "text/event-stream"
                      and frames[-1] == "[DONE]"
                      and toks == inproc[:4]
                      and final["finish_reason"] == "length")

            st1, _ = post({"prompt": [7] * 6, "max_tokens": 6},
                          tenant="starved")   # drains the 12-token burst
            st2, doc2 = post({"prompt": [7] * 6, "max_tokens": 6},
                             tenant="starved")
            shed_ok = (st1 == 200 and st2 == 429
                       and doc2["error"]["type"] == "rate_limit_exceeded"
                       and doc2["error"]["retry_after_s"] > 0)

            st3, doc3 = post(None, raw=b"{not json")
            st4, _doc4 = post({"prompt": prompt, "max_tokens": 2})
            survives = (st3 == 400
                        and doc3["error"]["type"]
                        == "invalid_request_error"
                        and st4 == 200)
            door_stats = door.stats()
            door.close()
            eng.close()
            return {"roundtrip": roundtrip, "sse": sse_ok,
                    "shed_429": shed_ok, "survives_malformed": survives,
                    "stats": door_stats}

        frontdoor_canary = _frontdoor_canary()

        # tiered canary (PR 20): the hierarchical KV cache end to end —
        # a repeated system prompt's blocks are evicted out of a TINY
        # 8-block device pool by churn, demoted to the host-DRAM tier
        # on the spiller thread, and the re-submitted system prompt is
        # served back THROUGH an async promotion: host-tier hits > 0,
        # the promotion-latency histogram live, and greedy output
        # token-identical to an untiered engine over the same prompts.
        def _tiered_canary():
            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.serving import GenerationEngine

            def run(tier_bytes):
                paddle.framework.random.seed(0)
                m = GPTForPretraining(GPTConfig.tiny())
                m.eval()
                eng = GenerationEngine(
                    m, num_slots=2, max_len=48, min_bucket=8,
                    block_size=8, num_blocks=8,
                    host_tier_bytes=tier_bytes)
                system = np.arange(2, 18, dtype=np.int32)  # 2 blocks
                outs = [eng.submit(np.concatenate([system, [40]]),
                                   max_new_tokens=4).result(timeout=300)]
                for j in range(3):          # churn the 8-block pool
                    outs.append(eng.submit(
                        np.arange(60 + 20 * j, 76 + 20 * j,
                                  dtype=np.int32),
                        max_new_tokens=4).result(timeout=300))
                tier = eng._pool.host_tier
                if tier is not None:
                    eng._pool.tier_tick()
                    tier.drain()            # demotions landed host-side
                outs.append(eng.submit(np.concatenate([system, [40]]),
                                       max_new_tokens=4)
                            .result(timeout=300))
                stats = eng.stats()
                eng.close()
                return outs, stats

            tiered_outs, tiered_stats = run(4 << 20)
            plain_outs, _ = run(None)
            parity = all(np.array_equal(a, b)
                         for a, b in zip(tiered_outs, plain_outs))
            ht = tiered_stats["host_tier"]
            return {"host_hits": tiered_stats["tier_hits"]["host"],
                    "demoted": ht["demoted_blocks"],
                    "promoted": ht["promoted_blocks"],
                    "promotion_ms": ht["promotion_ms"],
                    "hit_split": {k: round(tiered_stats[k], 3) for k in
                                  ("prefix_hit_hbm", "prefix_hit_host",
                                   "prefix_miss")},
                    "parity": parity}

        tiered_canary = _tiered_canary()

        # numerics canary (ISSUE 10): the training numerics health layer
        # end to end — a clean fit with numerics='record' leaves
        # hapi/grad_norm + hapi/grad_clip_ratio live and a warm re-fit
        # compiles ZERO additional programs (the audit is fused into the
        # existing donated step); an injected-inf fit in 'warn' mode
        # trips the sentinel within one flush window at the exact step,
        # dumps an anomaly postmortem JSON that round-trips, and leaves
        # hapi/host_sync at the PR-2 windowed budget.
        def _numerics_canary():
            net2 = nn.Sequential(nn.Linear(16, 8), nn.ReLU(),
                                 nn.Linear(8, 4))
            m2 = paddle.Model(net2)
            m2.prepare(
                paddle.optimizer.Adam(
                    learning_rate=1e-3, parameters=net2.parameters(),
                    grad_clip=nn.ClipGradByGlobalNorm(1.0)),
                nn.CrossEntropyLoss())
            data = TensorDataset([xs, ys])
            budget = n_batches / log_freq + 2
            s0 = monitor.stat_get("hapi/host_sync")
            m2.fit(data, batch_size=8, epochs=1, log_freq=log_freq,
                   shuffle=False, verbose=0, numerics="record")
            clean_syncs = monitor.stat_get("hapi/host_sync") - s0
            c0 = monitor.stat_get("compile/count")
            # warm re-fit, same signatures: the audit must not have
            # grown a second program per signature
            m2.fit(data, batch_size=8, epochs=1, log_freq=log_freq,
                   shuffle=False, verbose=0, numerics="record")
            extra_programs = monitor.stat_get("compile/count") - c0
            inject_at = m2._step_counter + 3
            m2._numerics_inject_inf_at = inject_at
            s1 = monitor.stat_get("hapi/host_sync")
            import warnings as _w
            with _w.catch_warnings():
                _w.simplefilter("ignore")
                m2.fit(data, batch_size=8, epochs=1, log_freq=log_freq,
                       shuffle=False, verbose=0, numerics="warn")
            m2._numerics_inject_inf_at = None
            warn_syncs = monitor.stat_get("hapi/host_sync") - s1
            rec = m2._numerics_recorder
            nonfin = [a for a in rec.anomaly_list()
                      if a["kind"] == "nonfinite"]
            pm_ok = False
            pm_path = rec.last_dump_path
            if pm_path and os.path.exists(pm_path):
                with open(pm_path) as f:
                    pm = json.load(f)
                pm_ok = (bool(pm.get("ring"))
                         and pm.get("anomaly", {}).get("kind")
                         == "nonfinite"
                         and "blamed_groups" in pm
                         and "memory_postmortem" in pm
                         and "monitor" in pm)
            return {
                "sentinel_tripped":
                    bool(nonfin) and nonfin[0]["step"] == inject_at
                    and bool(nonfin[0]["blamed_groups"]),
                "postmortem_ok": pm_ok,
                "postmortem": pm_path,
                "sync_budget_kept":
                    0 < clean_syncs <= budget
                    and 0 < warn_syncs <= budget,
                "zero_extra_programs": extra_programs == 0,
                "grad_norm_live":
                    monitor.stat_histogram("hapi/grad_norm") is not None
                    and monitor.stat_histogram("hapi/grad_clip_ratio")
                    is not None,
                "inject_step": inject_at,
                "anomaly_step": nonfin[0]["step"] if nonfin else None,
                "host_syncs": {"clean": clean_syncs, "warn": warn_syncs},
            }

        # snapshot the host-sync counter BEFORE the numerics canary's
        # own fits add their windowed flushes: host_sync_windowed below
        # asserts the budget of the FIRST fit alone
        host_syncs = monitor.stat_get("hapi/host_sync")
        numerics_canary = _numerics_canary()

        # ZeRO canary (ISSUE-11): on a dp=4 mesh, fit(zero=1) must
        # train allclose-identical params to the replicated donated
        # step AND the PR-7 ledger must bill per-replica opt-state
        # bytes at ~1/dp (one stripe of padding allowed). Skipped —
        # reported, not failed — when fewer than 4 devices are visible
        # (the tier-1 conftest forces 8 host devices, so CI always
        # exercises it).
        def _zero_canary():
            import jax
            if len(jax.devices()) < 4:
                return {"skipped": True, "parity": True,
                        "ledger_ok": True, "opt_bytes": None,
                        "replicated_opt_bytes": None}
            from paddle_tpu.distributed import env as denv
            from paddle_tpu.hapi import zero as zmod
            mesh_before = denv.get_mesh()
            denv.build_mesh({"dp": 4})
            try:
                def mk():
                    paddle.framework.random.seed(0)
                    netz = nn.Sequential(nn.Linear(16, 64), nn.ReLU(),
                                         nn.Linear(64, 4))
                    mm = paddle.Model(netz)
                    mm.prepare(
                        paddle.optimizer.Adam(
                            learning_rate=1e-3,
                            parameters=netz.parameters()),
                        nn.CrossEntropyLoss())
                    return mm
                dset = TensorDataset([xs, ys])
                m_rep = mk()
                m_rep.fit(dset, batch_size=8, epochs=1,
                          log_freq=log_freq, shuffle=False, verbose=0)
                m_z = mk()
                m_z.fit(dset, batch_size=8, epochs=1,
                        log_freq=log_freq, shuffle=False, verbose=0,
                        zero=1)
                parity = all(
                    np.allclose(np.asarray(m_rep._params[k]),
                                np.asarray(m_z._params[k]),
                                rtol=1e-5, atol=1e-6)
                    for k in m_rep._params)
                led = _memory.ledger()
                rep_b = led.get(f"{m_rep._ledger_base}/opt_state", 0)
                z_b = led.get(f"{m_z._ledger_base}/opt_state", 0)
                n_slots = len(m_z._optimizer._slot_names)
                bound = rep_b // 4 + n_slots * zmod.QUANT_CHUNK * 4 + 64
                return {"skipped": False, "parity": parity,
                        "ledger_ok": 0 < z_b <= bound,
                        "opt_bytes": z_b,
                        "replicated_opt_bytes": rep_b}
            finally:
                denv.set_mesh(mesh_before)

        zero_canary = _zero_canary()

        # Tensor-parallel serving canary (ISSUE-15): on an mp=2 mesh
        # the sharded paged engine (head-partitioned block pool +
        # shard_map'd fused step) must generate greedy output
        # token-identical to the single-device engine AND bill the
        # per-device KV block bytes at exactly 1/mp. Skipped —
        # reported, not failed — when fewer than 2 devices are visible
        # (the tier-1 conftest forces 8 host devices, so CI always
        # exercises it).
        def _mp_canary():
            import jax
            from jax.sharding import Mesh
            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.serving import GenerationEngine
            if len(jax.devices()) < 2:
                return {"skipped": True, "parity": True,
                        "kv_bytes_per_device_ok": True,
                        "kv_bytes_per_device": None,
                        "single_device_kv_bytes": None}
            mp = 2
            rng = np.random.RandomState(7)
            cfg = GPTConfig.tiny()
            prompts = [rng.randint(1, cfg.vocab_size, 6 + 3 * i)
                       .astype(np.int32) for i in range(4)]

            def run_leg(mesh):
                # fresh model per leg: sharding device_puts the params
                # in place, and both legs must start from the same
                # seeded weights
                paddle.framework.random.seed(0)
                m = GPTForPretraining(cfg)
                m.eval()
                eng = GenerationEngine(m, num_slots=2, max_len=48,
                                       block_size=8,
                                       mesh=mesh)
                hs = [eng.submit(p, max_new_tokens=8) for p in prompts]
                outs = [h.result(timeout=600) for h in hs]
                blocks = eng.stats()["kv_bytes"]["blocks"]
                eng.close()
                return outs, blocks

            s_outs, s_blocks = run_leg(None)
            mesh = Mesh(np.array(jax.devices()[:mp]).reshape(mp),
                        ("mp",))
            m_outs, m_blocks = run_leg(mesh)
            parity = all(np.array_equal(a, b)
                         for a, b in zip(s_outs, m_outs))
            return {"skipped": False, "parity": parity,
                    "kv_bytes_per_device_ok": m_blocks * mp == s_blocks,
                    "kv_bytes_per_device": m_blocks,
                    "single_device_kv_bytes": s_blocks}

        mp_canary = _mp_canary()

        # ISSUE-13 telemetry spine: the labeled metrics registry is the
        # surface every scale-out PR reports through, so the dry run
        # proves it end to end — (1) an explicit dp=2 CPU-mesh probe of
        # the ZeRO exchange populates collective_time_ms/{reduce_
        # scatter,all_gather} and the exposed-vs-overlapped report;
        # (2) statusz() renders with NO live engine (every canary
        # engine above is closed) and WITH a live 2-replica EngineFleet
        # whose aggregated stats sum the replicas' work with pooled
        # latency percentiles; (3) the registry's Prometheus exposition
        # is non-empty and round-trips through parse_prometheus with
        # the collective-timing family on board; (4) one sampler-ring
        # entry records the live gauges.
        def _telemetry_canary():
            import jax
            from jax.sharding import Mesh

            from paddle_tpu.distributed import collective as _coll
            from paddle_tpu.framework import metrics as _reg
            from paddle_tpu.hapi import zero as zmod
            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.serving import EngineFleet, GenerationEngine

            timing_skipped = len(jax.devices()) < 2
            probed = []
            if not timing_skipped:
                mesh = Mesh(np.array(jax.devices()[:2]), (zmod.AXIS,))
                layout = zmod.FlatLayout.build(
                    {"w": np.zeros((4096,), np.float32)}, dp=2)
                probed = sorted(
                    zmod.time_step_collectives(mesh, layout, "int8"))
            comm = _coll.communication_report()
            timing_live = timing_skipped or (
                monitor.stat_histogram(
                    "collective_time_ms/reduce_scatter") is not None
                and monitor.stat_histogram(
                    "collective_time_ms/all_gather") is not None
                and comm["exposed_ms_per_step"] is not None)

            console_idle = _reg.statusz()
            idle_ok = ("(no live engines)" in console_idle
                       and "--- collectives ---" in console_idle
                       and "--- memory ---" in console_idle
                       and "--- training ---" in console_idle
                       and "(section error" not in console_idle)

            def mk():
                paddle.framework.random.seed(0)
                m = GPTForPretraining(GPTConfig.tiny())
                m.eval()
                return GenerationEngine(m, num_slots=2, max_len=32,
                                        min_bucket=8)
            fleet = EngineFleet([mk(), mk()], name="dryrun")
            handles = [fleet.submit(np.arange(1, 1 + n, dtype=np.int32),
                                    max_new_tokens=3)
                       for n in (3, 5, 4, 6)]
            for h in handles:
                h.result(timeout=300)
            fstats = fleet.stats()
            fleet_ok = (fstats["replicas_healthy"] == 2
                        and fstats["requests_retired"] == 4
                        and fstats["ttft_ms"] is not None
                        and fstats["ttft_ms"]["count"] == 4
                        and len(fstats["replicas"]) == 2)
            console_live = _reg.statusz()
            live_ok = ("engine #" in console_live
                       and "fleet dryrun: 2/2 healthy" in console_live
                       and "(section error" not in console_live)
            prom_text = _reg.to_prometheus()
            parsed = _reg.parse_prometheus(prom_text)
            prom_ok = (
                len(parsed["samples"]) > 0
                and parsed["types"].get("collective_time_ms") == "summary"
                and any(n == "serving_requests_retired"
                        for n, _ in parsed["samples"]))
            ring_entry = _reg.registry().sample_now(label="dryrun")
            ring_ok = (len(ring_entry["values"]) > 0
                       and len(_reg.registry().timeseries()) > 0)
            fleet.close()
            return {"timing_skipped": timing_skipped,
                    "probed_kinds": probed,
                    "timing_live": timing_live,
                    "exposed_ms_per_step": comm["exposed_ms_per_step"],
                    "statusz_idle_ok": idle_ok,
                    "statusz_live_ok": live_ok,
                    "fleet_ok": fleet_ok,
                    "fleet_requests_retired":
                        fstats.get("requests_retired"),
                    "fleet_ttft_p50": (fstats["ttft_ms"] or {}).get("p50"),
                    "prometheus_ok": prom_ok,
                    "prometheus_samples": len(parsed["samples"]),
                    "ring_ok": ring_ok}

        telemetry_canary = _telemetry_canary()

        # ISSUE-18 static planner canary: (1) the donation-aware
        # liveness estimate must BRACKET XLA's own memory_analysis
        # (within liveness.CROSSCHECK_RTOL) on every program this dry
        # run actually compiled and both figures exist for — the tiny-
        # GPT train step is compiled here explicitly so the check
        # covers a real fused train step, and the serving canaries
        # above already compiled every decode/fused/spec bucket; (2) a
        # doctored too-small HBM budget must make engine construction
        # raise PlanError naming the fattest program point with
        # compile/count UNCHANGED (fit-before-compile: the plan is a
        # make_jaxpr trace, never an XLA compile); (3) a generous
        # budget constructs fine with a fitting plan attached.
        def _planner_canary():
            import paddle_tpu.nn.functional as F
            from paddle_tpu.analysis import liveness
            from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
            from paddle_tpu.serving import GenerationEngine, PlanError

            paddle.framework.random.seed(0)
            cfg = GPTConfig.tiny()
            gpt = GPTForPretraining(cfg)
            gm = paddle.Model(gpt)
            gm.prepare(
                paddle.optimizer.AdamW(learning_rate=1e-4,
                                       parameters=gpt.parameters()),
                lambda logits, lbl: F.cross_entropy(
                    logits.reshape([-1, cfg.vocab_size]),
                    lbl.reshape([-1])))
            ids = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
            gm.train_batch([ids], [ids.astype(np.int64)])

            crosschecks = {}
            for site, rec in program_registry.snapshot().items():
                cc = liveness.crosscheck(
                    rec.get("static_peak_bytes"), rec.get("argument_bytes"),
                    rec.get("output_bytes"), rec.get("temp_bytes"))
                if cc is not None:
                    crosschecks[site] = cc
            train_sites = [s for s in crosschecks
                           if "train_step" in s]
            serving_sites = [s for s in crosschecks
                             if s.startswith("serving/")]

            c0 = monitor.stat_get("compile/count")
            m2 = GPTForPretraining(cfg)
            m2.eval()
            gate = {"raised": False, "peak_point": None, "plan": None}
            try:
                GenerationEngine(m2, num_slots=4, max_len=48,
                                 min_bucket=8, block_size=8,
                                 hbm_budget_bytes=64 * 1024)
            except PlanError as e:
                gate = {"raised": True,
                        "peak_point": (e.plan.get("peak_point") or {})
                        .get("primitive"),
                        "plan": {k: e.plan[k] for k in
                                 ("static_peak_bytes", "pool_bytes",
                                  "budget_bytes", "fits")}}
            gate_extra_compiles = monitor.stat_get("compile/count") - c0

            eng = GenerationEngine(m2, num_slots=4, max_len=48,
                                   min_bucket=8, block_size=8,
                                   hbm_budget_bytes=1 << 33)
            generous_plan = eng._plan
            eng.close()
            return {
                "crosschecks": crosschecks,
                "crosscheck_ok": bool(crosschecks) and all(
                    c["ok"] for c in crosschecks.values()),
                "train_step_checked": bool(train_sites),
                "serving_checked": bool(serving_sites),
                "gate": gate,
                "gate_extra_compiles": gate_extra_compiles,
                "generous_fits": (generous_plan or {}).get("fits") is True,
            }

        planner_canary = _planner_canary()

    # ISSUE-7: the bench regression gate, exercised the way the driver
    # would use it — a seeded artifact vs a doctored copy with a 20%
    # throughput loss and a 40% latency blowup must exit nonzero
    # through the real --compare CLI, and a self-compare must exit 0.
    # bench.py's parent entry imports no jax, so these children are
    # milliseconds, not interpreter+backend startups.
    import copy
    import subprocess
    seeded = {"metric": "gpt2_tps", "value": 100.0, "unit": "tokens/sec",
              "extras": {
                  "gpt2": {"metric": "gpt2_tps", "value": 100.0,
                           "unit": "tokens/sec", "mfu": 0.40},
                  "serve": {"metric": "serve_lenet_latency_p50_ms",
                            "value": 10.0, "unit": "ms"}}}
    doctored = copy.deepcopy(seeded)
    doctored["extras"]["gpt2"]["value"] = 80.0       # -20% throughput
    doctored["extras"]["serve"]["value"] = 14.0      # +40% latency
    cmp_dir = tempfile.mkdtemp(prefix="paddle_dryrun_cmp_")
    a_path = os.path.join(cmp_dir, "a.json")
    b_path = os.path.join(cmp_dir, "b.json")
    with open(a_path, "w") as f:
        json.dump(seeded, f)
    with open(b_path, "w") as f:
        json.dump(doctored, f)
    me = os.path.abspath(__file__)
    rc_self = subprocess.run(
        [sys.executable, me, "--compare", a_path, a_path],
        capture_output=True).returncode
    rc_regress = subprocess.run(
        [sys.executable, me, "--compare", a_path, b_path],
        capture_output=True).returncode
    # the pure diff logic agrees with the CLI verdicts
    _, regs, _ = compare_flat(_flatten_bench_doc(seeded),
                              _flatten_bench_doc(doctored))

    counters = monitor.all_stats()
    mem_ledger = _memory.ledger()
    mem_timeline_labels = {e.get("label") for e in _memory.timeline()}
    trace_path = os.path.join(tempfile.mkdtemp(prefix="paddle_dryrun_"),
                              "trace.json")
    sess.export_chrome_trace(trace_path)
    with open(trace_path) as f:
        doc = json.load(f)
    cats = sorted({e["cat"] for e in doc["traceEvents"]
                   if e.get("ph") == "X"})
    prom = sess.export_prometheus()
    cache_entries = compile_cache.entries(cache_dir) if cache_on else 0

    checks = {
        "counters_nonempty": len(counters) > 0,
        "op_counts_present": any(k.startswith("op_count/")
                                 for k in counters),
        "cache_counters_present": ("op_cache_miss" in counters
                                   or "op_cache_hit" in counters),
        "step_histogram_present":
            monitor.stat_histogram("hapi/step_time_ms") is not None,
        "trace_categories": len(cats) >= 3,
        "prometheus_nonempty": "paddle_tpu_counter{name=" in prom,
        "loss_finite": bool(np.isfinite(loss)),
        # the async-fit sync budget: flushes at step%log_freq==0 plus
        # the epoch tail, never one stall per batch
        "host_sync_windowed":
            0 < host_syncs <= n_batches / log_freq + 2,
        "prefetch_histograms_present":
            monitor.stat_histogram("prefetch_put_ms") is not None
            and monitor.stat_histogram("prefetch_wait_ms") is not None,
        "prefetch_fed_fit":
            monitor.stat_get("prefetch_batches") >= n_batches,
        "compile_cache_populated": (not cache_on) or cache_entries > 0,
        # PR-3 static-analysis surface: the linter ran (fit pre-flight +
        # two zoo steps), the zoo steps carry no error findings, the
        # retrace-cause classifier recorded trace churn, and the repo
        # self-lint is clean
        "analysis_ran": monitor.stat_get("analysis/runs") >= 3,
        "analysis_findings_counted": "analysis/findings" in counters,
        "zoo_steps_clean": gpt_report.ok() and resnet_report.ok(),
        "retrace_cause_recorded":
            monitor.stat_get("dispatch/retrace_cause") > 0,
        "selflint_clean": not lint_findings,
        # serving surface: every canary request completed token-identical
        # to models.generate, the serving/* metrics are live, the
        # repeated system prompt hit the prefix cache (whole blocks of
        # tokens saved), the 40-token prompt chunked under the 8-token
        # budget (>= 5 launches), the fused step analyzes clean and every
        # (q, table) bucket traced once
        "serving_completed":
            serving_canary["completed"] == serving_canary["requests"],
        "serving_parity": serving_canary["parity"],
        "serving_counters_live":
            monitor.stat_histogram("serving/ttft_ms") is not None
            and monitor.stat_histogram("serving/tokens_per_sec")
            is not None
            and serving_canary["submitted"] == serving_canary["requests"],
        "serving_prefix_hit":
            serving_canary["stats"]["prefix_hits"] >= 4
            and serving_canary["stats"]["prefill_tokens_saved"] >= 4 * 16,
        "serving_chunked_prefill":
            serving_canary["stats"]["prefill_chunks"] >= 5
            and serving_canary["stats"]["chunked_prefill_tokens"] >= 40,
        "serving_step_clean": serving_canary["report"].ok(),
        "serving_one_trace_per_bucket": serving_canary["one_trace"],
        # ISSUE-12 speculative decoding + int8 KV blocks: greedy spec
        # output token-identical to the plain fused engine (cold AND
        # warm waves), serving/spec_accept live with tokens/cycle > 1
        # on the agreeing draft, one trace per spec (q, table) bucket
        # with zero retraces on the warm wave (no retrace storm from
        # verify rows), and the int8-block engine's greedy tokens agree
        # with fp32 on this workload
        "spec_parity": spec_canary["parity"]
        and spec_canary["warm_parity"],
        "spec_accept_live": spec_canary["accept_live"]
        and (spec_canary["tokens_per_cycle"] or 0) > 1.0,
        "spec_one_trace_per_bucket": spec_canary["one_trace"]
        and spec_canary["zero_warm_retraces"],
        # the canary model is UNTRAINED (near-tie argmaxes), so int8
        # noise may flip a couple of tokens — bounded drift here means
        # "mostly agrees"; exact trained-margin parity is asserted by
        # tests/test_serving_paging.py::TestQuantizedBlocks
        "spec_int8_agrees": spec_canary["int8_dtype"] == "int8"
        and spec_canary["int8_token_agreement"] >= 0.75,
        # ISSUE-6 serving observability: the canary's request traces all
        # completed in lifecycle order, the per-token decode cadence
        # histogram is live, per-engine stats() latency derives from the
        # engine's own traces, and the always-on flight recorder
        # captured cycles + events without the profiler
        "serving_traces_complete": serving_canary["traces_complete"],
        "serving_tpot_live":
            monitor.stat_histogram("serving/tpot_ms") is not None
            and serving_canary["engine_latency_present"],
        "serving_flight_recorder":
            serving_canary["flight_recorder_nonempty"],
        # PR-16 SLO plane: the ops HTTP server booted on an ephemeral
        # port and served a live Prometheus scrape carrying the SLO
        # series, /healthz answered 200 live and flipped 503 once the
        # engine closed, /tracez served the tail-sampled traces + SLO
        # report, and the engine published SLO-gated goodput
        "ops_server_scrape": serving_canary["ops_scrape"],
        "ops_server_healthz": serving_canary["ops_healthz"],
        "ops_server_tracez": serving_canary["ops_tracez"],
        "ops_server_goodput": serving_canary["ops_goodput"],
        # PR-19 HTTP front door: the non-streamed wire answer is
        # byte-identical to the in-process greedy submit, the SSE frame
        # sequence is well-formed and token-exact, the rate-limited
        # tenant draws a 429 with an honest Retry-After, and a
        # malformed body gets a 400 while the server thread survives to
        # answer the next request
        "frontdoor_roundtrip": frontdoor_canary["roundtrip"],
        "frontdoor_sse_stream": frontdoor_canary["sse"],
        "frontdoor_429_shed": frontdoor_canary["shed_429"],
        "frontdoor_survives_malformed":
            frontdoor_canary["survives_malformed"],
        # ISSUE-7 compute/memory observability: every owned jit site
        # registered its compile (compile/ms histogram + compile/count
        # counter live), the train step's cost analysis produced
        # hapi/flops_per_sec + hapi/mfu (pinned fake peak), the serving
        # engines derived model-FLOPs-per-token from the decode step's
        # registry record, the HBM ledger holds the train state + the
        # timeline carries serving-cycle/pool watermarks, and the
        # --compare regression gate flags the doctored artifact while
        # self-compare stays green
        "registry_compiles_recorded":
            monitor.stat_get("compile/count") > 0
            and monitor.stat_histogram("compile/ms") is not None,
        "hapi_mfu_present":
            monitor.stat_histogram("hapi/flops_per_sec") is not None
            and monitor.stat_histogram("hapi/mfu") is not None,
        "serving_flops_per_token":
            serving_canary["stats"].get("model_flops_per_token", 0) > 0,
        "memory_ledger_live":
            sum(mem_ledger.values()) > 0
            and any(k.startswith("hapi/state") and k.endswith("/params")
                    and v > 0 for k, v in mem_ledger.items())
            and "serving/cycle" in mem_timeline_labels
            and "kv/alloc" in mem_timeline_labels,
        "bench_compare_gate":
            rc_self == 0 and rc_regress != 0 and bool(regs),
        # ISSUE-10 training numerics health: a clean numerics='record'
        # fit leaves the gradient telemetry live at zero extra programs
        # and the windowed sync budget, and the injected-inf warn run
        # trips the sentinel at the exact step with a round-tripping
        # anomaly postmortem
        "numerics_sentinel": numerics_canary["sentinel_tripped"],
        "numerics_postmortem": numerics_canary["postmortem_ok"],
        "numerics_sync_budget": numerics_canary["sync_budget_kept"],
        "numerics_zero_extra_programs":
            numerics_canary["zero_extra_programs"],
        "numerics_grad_norm_live": numerics_canary["grad_norm_live"],
        # fit(zero=1): dp=4 parity with the replicated step + the
        # ledger's ~1/dp per-replica opt-state bytes
        "zero_parity": zero_canary["parity"],
        "zero_opt_state_sharded": zero_canary["ledger_ok"],
        # GenerationEngine(mesh=): mp=2 greedy token parity with the
        # single-device engine + the exact-1/mp per-device KV ledger
        "mp_parity": mp_canary["parity"],
        "mp_kv_bytes_per_device": mp_canary["kv_bytes_per_device_ok"],
        # ISSUE-13 telemetry spine: dp=2 collective timing + the
        # exposed-vs-overlapped report live, statusz renders with and
        # without a live engine, the fleet aggregation sums replicas'
        # work with pooled percentiles, the Prometheus exposition
        # round-trips non-empty, the sampler ring records
        "telemetry_collective_timing": telemetry_canary["timing_live"],
        "telemetry_statusz_idle": telemetry_canary["statusz_idle_ok"],
        "telemetry_statusz_live": telemetry_canary["statusz_live_ok"],
        "telemetry_fleet_agg": telemetry_canary["fleet_ok"],
        "telemetry_prometheus_roundtrip":
            telemetry_canary["prometheus_ok"],
        "telemetry_sampler_ring": telemetry_canary["ring_ok"],
        # ISSUE-18 static memory planner: the liveness estimate
        # brackets XLA's memory_analysis on EVERY compiled program
        # where both figures exist (incl. a real train step and the
        # serving buckets), the doctored 64 KiB budget fails engine
        # construction with a PlanError naming the fattest program
        # point and ZERO new compiles, and a generous budget attaches
        # a fitting plan
        "planner_crosscheck": planner_canary["crosscheck_ok"]
        and planner_canary["train_step_checked"]
        and planner_canary["serving_checked"],
        "planner_gate_raises": planner_canary["gate"]["raised"]
        and planner_canary["gate"]["peak_point"] is not None,
        "planner_gate_zero_compiles":
            planner_canary["gate_extra_compiles"] == 0,
        "planner_generous_fits": planner_canary["generous_fits"],
        # PR-20 tiered surface: churn-evicted system blocks came BACK
        # through the host tier (demote + async promote), the
        # promotion-latency histogram is live, and tiered greedy output
        # is token-identical to the untiered engine
        "tiered_host_hit": tiered_canary["host_hits"] > 0
        and tiered_canary["demoted"] > 0
        and tiered_canary["promoted"] > 0,
        "tiered_promotion_live":
            tiered_canary["promotion_ms"]["count"] > 0
            and monitor.stat_histogram("serving/promotion_ms")
            is not None,
        "tiered_parity": tiered_canary["parity"],
    }
    print(monitor.stats_summary(), file=sys.stderr)
    for f in lint_findings:
        print(f"SELFLINT {f}", file=sys.stderr)
    if not gpt_report.ok() or not resnet_report.ok():
        print(gpt_report.table(), file=sys.stderr)
        print(resnet_report.table(), file=sys.stderr)
    if not serving_canary["report"].ok():
        print(serving_canary["report"].table(), file=sys.stderr)
    if not planner_canary["crosscheck_ok"]:
        for site, cc in planner_canary["crosschecks"].items():
            print(f"PLANNER {'ok ' if cc['ok'] else 'FAIL'} {site}: "
                  f"static {cc['static_peak_bytes']:,} B vs XLA "
                  f"{cc['xla_bytes']:,} B (ratio {cc['ratio']:.2f}, "
                  f"rtol {cc['rtol']})", file=sys.stderr)
    ok = all(checks.values())
    print(json.dumps({"metric": "dry_run", "ok": ok,
                      "counters": len(counters),
                      "span_categories": cats, "trace": trace_path,
                      "host_syncs": host_syncs,
                      "compile_cache_enabled": bool(cache_on),
                      "compile_cache_entries": cache_entries,
                      "analysis_runs": monitor.stat_get("analysis/runs"),
                      "analysis_findings":
                          monitor.stat_get("analysis/findings"),
                      "retrace_causes": {
                          k.rsplit("/", 1)[-1]: v
                          for k, v in counters.items()
                          if k.startswith("dispatch/retrace_cause/")},
                      "selflint_findings": len(lint_findings),
                      "serving": {
                          "requests": serving_canary["requests"],
                          **{k: serving_canary["stats"][k] for k in
                             ("prefix_hits", "prefill_tokens_saved",
                              "prefill_chunks", "chunked_prefill_tokens",
                              "model_flops_per_token")}},
                      "spec": {k: spec_canary[k] for k in
                               ("accept_rate", "tokens_per_cycle",
                                "int8_token_agreement")},
                      "frontdoor": frontdoor_canary["stats"],
                      "tiered": {k: tiered_canary[k] for k in
                                 ("host_hits", "demoted", "promoted",
                                  "hit_split")},
                      "numerics": {
                          "inject_step": numerics_canary["inject_step"],
                          "anomaly_step":
                              numerics_canary["anomaly_step"],
                          "postmortem": numerics_canary["postmortem"],
                          "host_syncs": numerics_canary["host_syncs"],
                          "nonfinite_steps":
                              monitor.stat_get("hapi/nonfinite_steps"),
                      },
                      "zero": zero_canary,
                      "mp": mp_canary,
                      "planner": {
                          "n_crosschecked":
                              len(planner_canary["crosschecks"]),
                          "ratios": {
                              s: round(c["ratio"], 3) for s, c in
                              planner_canary["crosschecks"].items()},
                          "gate": planner_canary["gate"],
                          "gate_extra_compiles":
                              planner_canary["gate_extra_compiles"],
                      },
                      "telemetry": {k: telemetry_canary[k] for k in
                                    ("probed_kinds",
                                     "exposed_ms_per_step",
                                     "fleet_requests_retired",
                                     "fleet_ttft_p50",
                                     "prometheus_samples")},
                      "compile_count":
                          int(monitor.stat_get("compile/count")),
                      "hapi_mfu": (monitor.stat_histogram("hapi/mfu")
                                   or {}).get("p50"),
                      "memory_ledger_bytes": sum(mem_ledger.values()),
                      "compare_gate_rc": {"self": rc_self,
                                          "regression": rc_regress},
                      "loss": round(float(loss), 4), "checks": checks}),
          flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child(sys.argv[2])
    elif "--compare" in sys.argv[1:]:
        run_compare(sys.argv[1:])
    elif "--history" in sys.argv[1:]:
        run_history(sys.argv[1:])
    elif "--bench-zero" in sys.argv[1:]:
        # standalone replicated-vs-ZeRO microbench (same child schema);
        # needs >= 4 devices — on CPU run under
        # XLA_FLAGS=--xla_force_host_platform_device_count=4
        print("RESULT " + json.dumps(bench_zero()))
    elif "--bench-spec" in sys.argv[1:]:
        # standalone speculative-decoding + int8-KV microbench (same
        # child schema): spec-vs-plain decode ms, accept rate,
        # tokens/step, int8 capacity + drift; parity hard-fails
        print("RESULT " + json.dumps(bench_spec()))
    elif "--bench-mp" in sys.argv[1:]:
        # standalone single-vs-mp=2 tensor-parallel serving microbench
        # (same child schema): decode-step ms both legs + per-device KV
        # bytes; token parity and the 1/mp ledger hard-fail. Needs
        # >= 2 devices — on CPU run under
        # XLA_FLAGS=--xla_force_host_platform_device_count=2
        print("RESULT " + json.dumps(bench_mp()))
    elif "--dry-run" in sys.argv[1:]:
        dry_run()
    else:
        main()
