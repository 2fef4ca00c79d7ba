#!/usr/bin/env python3
"""The one-off sweep that fixes an open-loop cell's rate: the same mix
at several arrival rates through ONE stood-up engine. The knee is the
highest rate at which the backlog does not grow over the window.

    python3 benchmark/tools/knee_sweep.py --workload gpt2-124m.chat \\
        --seed 5 --rates 4,6,8,10,12 --seconds 20

Prints one JSON line per rate. Never part of a benchmark run.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import harness as H  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    _, config, traffic = H.load_cell(args.workload)
    H.require_tpu(1)
    from benchmark.lib import serve
    from benchmark.lib import stats as S
    rig = serve.Rig(config, traffic, args.seed)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(traffic, rate_per_s=rate)
            r = rig.window(mix, args.seed + k, args.seconds, False, "open")
            t0, t1 = r["t0"], r["t1"]
            due = [x for x in r["records"] if t0 <= x["due"] < t1]
            done = [x for x in due if x["done"] is not None]
            ttft = [(x["t"][0] - x["due"]) * 1e3 for x in due if x["t"]]
            half = t0 + (t1 - t0) / 2
            first = [(x["t"][0] - x["due"]) * 1e3 for x in due
                     if x["t"] and x["due"] < half]
            second = [(x["t"][0] - x["due"]) * 1e3 for x in due
                      if x["t"] and x["due"] >= half]
            open_at_end = sum(1 for x in due if x["done"] is None
                              or x["done"] > t1)
            gaps = [(b - a) * 1e3 for x in done for a, b in zip(x["t"], x["t"][1:])]
            print(json.dumps({
                "sweep": args.workload, "rate_per_s": rate, "due": len(due),
                "finished": len(done), "open_at_window_end": open_at_end,
                "shed": sum(x["status"] in (429, 503) for x in due),
                "ttft_p50_ms": S.percentile(ttft, 50), "ttft_p95_ms": S.percentile(ttft, 95),
                "ttft_p50_first_half_ms": S.percentile(first, 50),
                "ttft_p50_second_half_ms": S.percentile(second, 50),
                "itl_p50_ms": S.percentile(gaps, 50), "itl_p95_ms": S.percentile(gaps, 95),
                "tokens_per_s": sum(len(x["tokens"]) for x in done) / (t1 - t0),
                "compiles": r["compiles"]["registry"]}), flush=True)
    finally:
        rig.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
