"""The serving SLO plane + the zero-dependency ops HTTP surface.

Boots a ``serving.GenerationEngine`` on a tiny untrained GPT, attaches
an :class:`~paddle_tpu.serving.SLOTracker` (two objectives: TTFT and
TPOT latency targets with attainment goals) and an
:class:`~paddle_tpu.serving.OpsServer` on an ephemeral localhost port,
serves a small burst of requests, then plays Prometheus: every number
printed below comes back over REAL HTTP from the stdlib-only server —
``/metrics`` (text exposition), ``/healthz`` (flips 503 the moment the
engine closes), ``/tracez`` (tail-sampled slowest/violating request
traces + the SLO report with multi-window burn rates and per-replica
goodput).

This is the scrape surface a production deployment points Prometheus
at::

    scrape_configs:
      - job_name: paddle-serving
        scrape_interval: 5s
        static_configs: [{targets: ["localhost:<srv.port>"]}]

Usage:
    python examples/ops_surface.py [--requests 6]
"""
import argparse
import json
import urllib.error
import urllib.request

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.framework.metrics import parse_prometheus
from paddle_tpu.models import GPTConfig, GPTForPretraining
from paddle_tpu.serving import GenerationEngine, OpsServer, SLOTracker


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    paddle.framework.random.seed(0)
    cfg = GPTConfig.tiny()
    model = GPTForPretraining(cfg)
    model.eval()
    eng = GenerationEngine(model, num_slots=4, max_len=64)

    # the SLO plane: objectives are latency targets + attainment goals;
    # CPU-demo targets are generous — the point is the measurement
    slo = SLOTracker(name="demo")
    slo.add_objective("ttft", metric="ttft_ms", target_ms=60_000.0,
                      goal=0.95)
    slo.add_objective("tpot", metric="tpot_ms", target_ms=60_000.0,
                      goal=0.90)
    replica = slo.attach_engine(eng)
    srv = OpsServer(target=eng, slo=slo).start()
    print(f"ops server live at {srv.url}")

    rng = np.random.RandomState(3)
    handles = [eng.submit(rng.randint(2, cfg.vocab_size,
                                      size=rng.randint(4, 20)
                                      ).astype(np.int32),
                          max_new_tokens=8)
               for _ in range(args.requests)]
    done = sum(1 for h in handles if len(list(h.stream())) > 0)
    print(f"served {done} requests")

    # -- everything below travels over real HTTP ------------------------
    text = urllib.request.urlopen(srv.url + "/metrics",
                                  timeout=30).read().decode()
    samples = parse_prometheus(text)["samples"]
    print(f"scraped {len(samples)} samples from /metrics")
    for family in ("slo_attainment", "slo_burn_rate", "goodput_rps",
                   "slo_latency_ms_bucket"):
        live = any(n == family for n, _ in samples)
        print(f"  {family}: {'live' if live else 'MISSING'}")

    code = urllib.request.urlopen(srv.url + "/healthz",
                                  timeout=30).status
    print(f"healthz: {code} ok")

    tracez = json.loads(urllib.request.urlopen(
        srv.url + "/tracez", timeout=30).read().decode())
    tail = next(iter(tracez["engines"].values()))
    print(f"tracez: {len(tail['recent'])} recent traces, "
          f"slowest-N tail of {len(tail['slowest'])}")
    for name, obj in sorted(tracez["slo"]["objectives"].items()):
        burns = " ".join(f"burn[{w}]={b:.2f}"
                         for w, b in sorted(obj["burn_rate"].items()))
        print(f"  slo {name}: {obj['metric']} <= {obj['target_ms']:g}ms "
              f"attainment {obj['attainment']:.2%} {burns}")
    print(f"  goodput[{replica}] = "
          f"{tracez['slo']['goodput_rps'][replica]:.1f} req/s")

    eng.close()
    try:
        urllib.request.urlopen(srv.url + "/healthz", timeout=30)
        print("healthz after close: still 200 (BUG)")
    except urllib.error.HTTPError as e:
        print(f"healthz after close: {e.code}")
    srv.close()
    slo.close()


if __name__ == "__main__":
    main()
