"""Continuous-batching LLM serving: many concurrent clients, one engine.

Trains a small character-level GPT-2 for a few steps (so the decodes are
legible), then starts a ``serving.GenerationEngine`` and hammers it with
N concurrent clients submitting prompts of MIXED lengths and output
budgets. With ``--mp N`` the whole engine serves TENSOR-PARALLEL
(``GenerationEngine(mesh=)``): Megatron weight layout, the paged KV
pool head-partitioned over an N-way mesh, every step a shard_map — so
each device holds 1/N of the KV bytes (the per-device pool stats line
at the end shows it). Each client streams its tokens as they are produced; the demo
prints per-client time-to-first-token and the engine-wide throughput —
the two serving numbers that matter, straight from the monitor
histograms the engine maintains (``serving/ttft_ms``,
``serving/tokens_per_sec``).

Why this beats gather-and-run batching for generation: requests join
and leave the in-flight batch EVERY cycle (continuous batching over a
paged KV pool, one fused ragged launch a cycle), so a client asking for
4 tokens is never held hostage by one asking for 48.

Every client shares the same block-aligned system preamble, so after
the first request has fed it, the others are PREFIX-CACHE HITS that
adopt its blocks and feed only their own tail, in chunks mixed into the
decode launches — watch ``prefix_hit_ratio``, ``prefill_tokens_saved``
and the chunk counters in the end-of-run ``engine.stats()`` report.

With ``--spec`` a 2-layer draft sharing the
target's embeddings proposes ``--spec-k`` tokens per slot per cycle and
the target verifies them all in ONE fused ragged launch — watch the
``spec accept rate`` and ``tokens/cycle`` lines: an agreeing draft
multiplies decode throughput without changing a single output token
(greedy speculative output is token-identical by construction). With
``--kv-dtype int8`` the pool stores quantized blocks with
per-block max-abs scales, so the same device byte budget admits ~4x
the blocks — the ``block capacity`` line shows the same-budget
comparison against fp32.

``--statusz`` prints the one-call ops console
(``framework.metrics.statusz()``) while the engine is live, and
``--prom FILE`` writes the Prometheus exposition of the whole metrics
surface — the operational view every flag above feeds.

Usage:
    python examples/serve_gpt2.py [--clients 12] [--slots 8] [--mp 2]
                                  [--spec] [--kv-dtype int8]
                                  [--statusz] [--prom metrics.prom]
"""
import argparse
import threading
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.framework import monitor
from paddle_tpu.models import GPTConfig, GPTForPretraining
from paddle_tpu.serving import GenerationEngine

CORPUS = (
    "the quick brown fox jumps over the lazy dog. "
    "pack my box with five dozen liquor jugs. "
    "how vexingly quick daft zebras jump. "
) * 8

PROMPTS = [b"the quick", b"pack my box with five dozen", b"how",
           b"jumps over", b"the lazy dog", b"liquor jugs",
           b"daft zebras", b"five dozen liquor"]


def build_model(train_steps=40):
    cfg = GPTConfig(vocab_size=128, hidden_size=128, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=256,
                    max_position_embeddings=128, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.Adam(learning_rate=3e-3,
                                parameters=model.parameters())
    data = np.frombuffer(CORPUS.encode(), np.uint8).astype(np.int32)
    rng = np.random.RandomState(0)
    seq, batch = 64, 8
    print(f"training a 2-layer char GPT for {train_steps} steps...")
    for step in range(train_steps):
        starts = rng.randint(0, len(data) - seq - 1, batch)
        chunk = np.stack([data[s:s + seq + 1] for s in starts])
        loss, _ = model(paddle.to_tensor(chunk[:, :-1]),
                        paddle.to_tensor(chunk[:, 1:].astype(np.int64)))
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 20 == 0:
            print(f"  step {step:3d} loss {float(loss):.3f}")
    model.eval()
    return model


def make_mesh(mp):
    """1-D ``mp``-way device mesh for the TENSOR-PARALLEL engine
    (``GenerationEngine(mesh=)``): the engine lays the weights out
    Megatron-style, head-partitions the paged block pool, and runs
    every serving step as a shard_map over the mesh — each device
    holds 1/mp of the KV bytes (the scale-up half; EngineFleet is the
    scale-out half)."""
    if mp <= 1:
        return None
    import jax
    from jax.sharding import Mesh
    if mp > len(jax.devices()):
        raise SystemExit(
            f"--mp {mp} needs {mp} devices, found {len(jax.devices())} "
            f"(on CPU: XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={mp})")
    mesh = Mesh(np.array(jax.devices()[:mp]).reshape(mp), ("mp",))
    print(f"serving tensor-parallel over {mp} device(s)")
    return mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--mp", type=int, default=1,
                    help="tensor-parallel ways (<= visible devices)")
    ap.add_argument("--train-steps", type=int, default=40)
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding: a 2-layer draft sharing "
                         "the target's embeddings proposes --spec-k "
                         "tokens per cycle, verified in one fused "
                         "ragged launch")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--kv-dtype", default=None,
                    choices=["float32", "int8"],
                    help="KV block storage dtype; int8 stores "
                         "quantized blocks with per-block max-abs "
                         "scales (~4x blocks per byte budget)")
    ap.add_argument("--statusz", action="store_true",
                    help="print the one-call ops console "
                         "(framework.metrics.statusz()) while the "
                         "engine is still live: pool occupancy, prefix "
                         "cache, latency, HBM headroom in one report")
    ap.add_argument("--prom", default=None, metavar="FILE",
                    help="write the Prometheus text exposition of the "
                         "whole metrics surface (registry + monitor "
                         "bridge) to FILE after the run")
    args = ap.parse_args()
    if args.mp > 1:
        # the spec/int8 compositions are not sharded yet
        if args.spec:
            ap.error("--mp does not compose with --spec yet (no "
                     "sharded draft/verify builders)")
        if args.kv_dtype == "int8":
            ap.error("--mp does not compose with --kv-dtype int8 yet "
                     "(block scales have no head-sharded layout)")
    paddle.seed(0)
    model = build_model(args.train_steps)
    mesh = make_mesh(args.mp)

    # 8-token blocks where the kernel takes them; an int8 tile needs 32
    engine = GenerationEngine(
        model, num_slots=args.slots, max_len=128,
        block_size=32 if args.kv_dtype == "int8" else 8,
        kv_dtype=args.kv_dtype,
        spec_draft="auto" if args.spec else None,
        spec_k=args.spec_k, mesh=mesh)
    # a shared system preamble every client prepends — exactly three
    # full 8-token blocks, so it is computed once and then served whole
    # from the prefix cache
    system = np.frombuffer(b"the quick brown fox jump", np.uint8) \
        .astype(np.int32)
    print(f"\nserving with {args.slots} slots, "
          f"{args.clients} concurrent clients (mixed lengths):")

    lines, lock = [], threading.Lock()

    def client(i):
        rng = np.random.RandomState(i)
        text = PROMPTS[i % len(PROMPTS)]
        ids = np.frombuffer(text, np.uint8).astype(np.int32)
        ids = np.concatenate([system, ids])
        max_new = int(rng.randint(4, 25))
        t0 = time.perf_counter()
        ttft, toks = None, []
        for tok in engine.stream(ids, max_new_tokens=max_new):
            if ttft is None:
                ttft = (time.perf_counter() - t0) * 1e3
            toks.append(tok)
        dt = time.perf_counter() - t0
        out = bytes(c for c in toks if 0 < c < 128).decode(errors="replace")
        with lock:
            lines.append(f"  client {i:2d} {text.decode()!r:>30} "
                         f"+{len(toks):2d} tok  ttft {ttft:6.1f} ms  "
                         f"{len(toks) / dt:6.1f} tok/s  -> {out!r}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(args.clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = engine.stats()      # snapshot BEFORE close drains the pool
    if args.statusz:
        # the ops console, rendered while the engine is still LIVE so
        # its serving section shows this engine's row
        from paddle_tpu.framework import metrics
        print("\n" + metrics.statusz())
    if args.prom:
        from paddle_tpu.framework import metrics
        metrics.to_prometheus(args.prom)
        print(f"prometheus exposition -> {args.prom}")
    engine.close()

    for ln in sorted(lines):
        print(ln)
    # per-ENGINE latency percentiles, derived from this engine's own
    # request traces (stats()["ttft_ms"/"tpot_ms"]) — unlike the
    # process-global monitor histograms, these cannot be contaminated
    # by another engine in the same process
    ttft = stats["ttft_ms"] or {}
    tpot = stats["tpot_ms"] or {}
    total_tokens = monitor.stat_get("serving/tokens")
    print(f"\nserved {args.clients} requests in {wall:.2f}s: "
          f"{total_tokens:.0f} tokens, "
          f"aggregate {total_tokens / wall:.1f} tokens/s, "
          f"ttft p50 {ttft.get('p50', 0):.1f} ms "
          f"p95 {ttft.get('p95', 0):.1f} ms, "
          f"tpot p50 {tpot.get('p50', 0):.2f} ms "
          f"p95 {tpot.get('p95', 0):.2f} ms")
    # the operator snapshot: one call instead of scraping serving/*
    # monitor counters by prefix
    print(f"engine.stats(): queue={stats['queue_depth']} "
          f"active={stats['active_requests']} "
          f"slots={stats['slots_in_use']}/{stats['num_slots']} "
          f"preempts={stats['preempts']}")
    print(f"  paged: blocks {stats['kv_blocks_in_use']}"
          f"/{stats['num_blocks']} x{stats['block_size']}, "
          f"cached {stats['cached_blocks']}, "
          f"prefix hit ratio {stats['prefix_hit_ratio']:.2f} "
          f"({stats['prefix_hits']} hit / "
          f"{stats['prefix_misses']} miss), "
          f"prefill tokens saved {stats['prefill_tokens_saved']}")
    if stats.get("mp"):
        print(f"  tensor-parallel: mp={stats['mp']} "
              f"('{stats['mp_axis']}' axis), per-device KV pool "
              f"{stats['kv_bytes_per_device'] // 1024} KiB "
              f"(1/{stats['mp']} of the single-device bytes)")
    print(f"  fused: prefill chunks {stats['prefill_chunks']} "
          f"({stats['chunked_prefill_tokens']} tokens chunked)")
    if args.spec:
        print(f"  spec: accept rate {stats['spec_accept_rate']:.2f} "
              f"({stats['spec_accepted']}/{stats['spec_proposed']} "
              f"draft tokens), "
              f"tokens/cycle {stats.get('spec_tokens_per_cycle', 1.0):.2f} "
              f"(k={stats['spec_k']}, draft {stats['draft_layers']}L)")
    # same-byte-budget capacity: how many blocks THIS pool's budget
    # would buy at fp32 vs its actual dtype — the quantized-KV
    # "more requests per pool" line
    from paddle_tpu.serving import PagedKVPool
    budget = stats["kv_pool_capacity_bytes"]
    pool = engine._pool
    fp32_blocks = PagedKVPool.blocks_within_budget(
        budget, num_layers=pool.num_layers,
        num_heads=pool.num_heads, block_size=pool.block_size,
        head_dim=pool.head_dim, dtype="float32")
    print(f"  block capacity: {stats['num_blocks']} x "
          f"{stats['block_size']}-token {stats['kv_dtype']} blocks "
          f"in {budget // 1024} KiB "
          f"(same budget at fp32: {fp32_blocks} blocks, "
          f"{stats['num_blocks'] / max(1, fp32_blocks):.1f}x)")


if __name__ == "__main__":
    main()
