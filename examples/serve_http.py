"""The HTTP inference front door: OpenAI-style /v1/completions demo.

Boots a ``serving.GenerationEngine`` on a tiny untrained GPT, puts a
:class:`~paddle_tpu.serving.FrontDoor` in front of it (mounted on the
same stdlib ops server that serves ``/metrics`` — one process, one
port) and then plays three tenants against it over REAL sockets:

* ``alice`` — interactive-lane clients streaming completions over SSE,
  wire-side TTFT stamped at the first ``data:`` chunk;
* ``bulk-corp`` — batch-lane clients hammering non-streamed requests
  concurrently (the scheduler's weighted deficit-round-robin keeps
  them from starving alice);
* ``starved`` — a tenant with a deliberately tiny token bucket whose
  over-budget requests draw 429 + Retry-After instead of queueing.

The end-of-run report prints the per-tenant wire TTFT, the engine's
own per-tenant goodput accounting (``engine.stats()["tenants"]``) and
the front door's shed counts — the operator view of one noisy
neighbor being priced instead of everyone being slow.

The engine lands on the default place (the TPU when jax finds one,
else the CPU); the TTFT this demo prints is a host-clock illustration,
not a measurement — on the measured paths (``chip_smoke.py``,
``bench.py``) a missing chip is an error.

Usage:
    python examples/serve_http.py [--interactive 6] [--batch 6]
"""
import argparse
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForPretraining
from paddle_tpu.serving import FrontDoor, GenerationEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interactive", type=int, default=6)
    ap.add_argument("--batch", type=int, default=6)
    args = ap.parse_args()

    paddle.framework.random.seed(0)
    cfg = GPTConfig.tiny()
    model = GPTForPretraining(cfg)
    model.eval()
    eng = GenerationEngine(model, num_slots=4, max_len=64)

    # 15 tokens of burst cover ONE of the starved tenant's requests; at
    # half a token a second the bucket does not refill another within
    # the run, however slow the (interpreted, on a CPU) kernels are
    door = FrontDoor(eng, tenant_limits={"starved": (0.5, 15.0)})
    srv = door.start()
    print(f"front door live at {srv.url}  "
          f"(POST /v1/completions beside GET /metrics)")

    rng = np.random.RandomState(3)
    ttfts = {"alice": [], "bulk-corp": []}
    lock = threading.Lock()

    def interactive_client(prompt, max_new):
        """SSE stream; TTFT = first data: chunk hitting the socket."""
        req = urllib.request.Request(
            srv.url + "/v1/completions",
            data=json.dumps({"prompt": prompt, "max_tokens": max_new,
                             "lane": "interactive",
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Tenant": "alice"})
        t0 = time.perf_counter()
        toks = []
        with urllib.request.urlopen(req, timeout=300) as r:
            t_first = None
            for line in r:
                if not line.startswith(b"data: "):
                    continue
                payload = line[len(b"data: "):].strip()
                if payload == b"[DONE]":
                    break
                if t_first is None:
                    t_first = time.perf_counter()
                tok = json.loads(payload)["choices"][0]["token_id"]
                if tok is not None:
                    toks.append(tok)
        with lock:
            ttfts["alice"].append((t_first - t0) * 1e3)
        return toks

    def batch_client(prompt, max_new):
        req = urllib.request.Request(
            srv.url + "/v1/completions",
            data=json.dumps({"prompt": prompt, "max_tokens": max_new,
                             "lane": "batch"}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Tenant": "bulk-corp"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=300) as r:
            doc = json.loads(r.read())
        with lock:
            ttfts["bulk-corp"].append((time.perf_counter() - t0) * 1e3)
        return doc["choices"][0]["token_ids"]

    threads = []
    for _ in range(args.interactive):
        p = [int(t) for t in rng.randint(2, cfg.vocab_size,
                                         rng.randint(4, 16))]
        threads.append(threading.Thread(
            target=interactive_client, args=(p, 8), daemon=True))
    for _ in range(args.batch):
        p = [int(t) for t in rng.randint(2, cfg.vocab_size,
                                         rng.randint(4, 16))]
        threads.append(threading.Thread(
            target=batch_client, args=(p, 8), daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    print(f"served {args.interactive} interactive (SSE) + "
          f"{args.batch} batch requests over HTTP")

    # the over-budget tenant: burst 15 covers ONE of these, then 429
    shed = 0
    for _ in range(4):
        req = urllib.request.Request(
            srv.url + "/v1/completions",
            data=json.dumps({"prompt": [7] * 5,
                             "max_tokens": 10}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Tenant": "starved"})
        try:
            urllib.request.urlopen(req, timeout=300).read()
        except urllib.error.HTTPError as e:
            body = json.loads(e.read())
            assert e.code == 429, e.code
            shed += 1
            retry = body["error"]["retry_after_s"]
    print(f"tenant 'starved': {shed} requests shed with 429 "
          f"(last Retry-After {retry:.2f}s)")

    for tenant, vals in sorted(ttfts.items()):
        if vals:
            vals = sorted(vals)
            print(f"  wire ttft[{tenant}]: "
                  f"p50 {vals[len(vals) // 2]:.1f} ms over "
                  f"{len(vals)} requests")
    tenants = eng.stats().get("tenants") or {}
    for tenant, s in sorted(tenants.items()):
        p95 = s["ttft_p95_ms"]
        print(f"  engine tenants[{tenant}]: {s['retired']} retired, "
              f"goodput {s['goodput_rps']:.1f} req/s, ttft p95 "
              + (f"{p95:.1f} ms" if p95 is not None else "n/a"))
    print(f"front door: {door.stats()['served']} served, "
          f"shed per tenant {door.stats()['shed']}")

    door.close()
    eng.close()


if __name__ == "__main__":
    main()
