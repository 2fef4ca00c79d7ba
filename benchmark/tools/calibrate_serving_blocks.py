#!/usr/bin/env python3
"""``calibrate_serving_family.py`` for a cell whose driver is
``serve_backlog_blocks``: the program's gaps and order gaps and the
control's (the family's reference in the next lower precision, run on the
same states) over several traffic seeds through ONE stood-up engine.

    python3 benchmark/tools/calibrate_serving_blocks.py \\
        --workload sdar-30b-a3b-pp8.decode --seed 11 --traffic-seeds 1,2 \\
        --seconds 20 --quant int8 --control-windows 1

Prints one JSON line per window; the control (a second pass of the
reference) is run on the first ``--control-windows`` windows only. Never
part of a benchmark run.
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
    ap.add_argument("--control-windows", type=int, default=1)
    args = ap.parse_args()
    _, config, traffic = H.load_cell(args.workload)
    H.require_tpu(1)
    from benchmark.drivers import serve_backlog_blocks as D
    from benchmark.lib import serve, serve_family
    rig = serve_family.Rig(config, traffic, args.seed)
    windows = []
    try:
        seen = D.collect_passes(rig.served.engine)
        for ts in (int(s) for s in args.traffic_seeds.split(",")):
            r = rig.window(traffic, ts, args.seconds, False, "backlog")
            D.join_passes(r["records"], seen)
            windows.append(r)
    finally:
        rig.close()
    for i, r in enumerate(windows):
        quant = args.quant if i < args.control_windows else None
        ok, numbers = D.check_window(config, r, args.seed, quant=quant)
        n_tok, _ = serve.window_token_times(r["records"], r["t0"], r["t1"])
        tokens, secs = serve.whole_cycle_rate(r["records"], r["t0"], r["t1"])
        print(json.dumps({"calibrate": args.workload, "weight_seed": args.seed,
                          "traffic_seed": r["seed"], "correct": ok,
                          "window_tokens": n_tok,
                          "serve_tok_s": tokens / secs,
                          "finished": len(serve.finished_in_window(r)),
                          "compiles": r["compiles"]["registry"],
                          "memory_peak_bytes":
                              r["device"]["memory_peak_bytes"], **numbers}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
