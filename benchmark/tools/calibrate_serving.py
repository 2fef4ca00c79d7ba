#!/usr/bin/env python3
"""Read the numbers a serving cell's limits are set from: the program's
gaps and the control's (the reference in the next lower precision), over
several traffic seeds through ONE stood-up engine.

    python3 benchmark/tools/calibrate_serving.py --workload gpt2-124m.chat \\
        --seed 11 --traffic-seeds 1,2,3,4 --seconds 15 --quant int8

Prints one JSON line per window. Never part of a benchmark run.
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
    ap.add_argument("--traffic-seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--quant", default="int8")
    args = ap.parse_args()
    _, config, traffic = H.load_cell(args.workload)
    H.require_tpu(1)
    from benchmark.lib import serve
    mode = {"serve_backlog": "backlog", "serve_open": "open"}[traffic["driver"]]
    rig = serve.Rig(config, traffic, args.seed)
    windows = []
    try:
        for ts in (int(s) for s in args.traffic_seeds.split(",")):
            windows.append(rig.window(traffic, ts, args.seconds, False, mode))
    finally:
        rig.close()
    for r in windows:
        ok, numbers = serve.check_window(config, r, args.seed, quant=args.quant)
        n_tok, gaps = serve.window_token_times(r["records"], r["t0"], r["t1"])
        print(json.dumps({"calibrate": args.workload, "weight_seed": args.seed,
                          "traffic_seed": r["seed"], "correct": ok,
                          "window_tokens": n_tok,
                          "finished": len(serve.finished_in_window(r)),
                          "compiles": r["compiles"]["registry"], **numbers}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
