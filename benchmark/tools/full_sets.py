#!/usr/bin/env python3
"""Run a cell's full sets the way the driver's check does: ``--sets`` sets
of runs with the same seeds in each, every run a new process; then the
spread of every metric (distance between the first and third quartile,
``statistics.quantiles(n=4)``, over the median) per set, which is what a
bound is set from. The parent never touches jax.

    python3 benchmark/tools/full_sets.py --workload gpt2-124m.train \\
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 40 --out chiprun_out/train
"""
import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import stats as S  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seed", type=int, default=None,
                    help="also one --trace 1 run on this seed, last")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = [(k, s, 0) for k in range(args.sets) for s in seeds]
    if args.trace_seed is not None:
        plan.append((args.sets, args.trace_seed, 1))
    results = []
    for k, seed, trace in plan:
        log = os.path.join(args.out, f"set{k}_seed{seed}_t{trace}.log")
        with open(log, "w") as f:
            rc = subprocess.run(
                [sys.executable, "benchmark/run.py", "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(args.seconds),
                 "--trace", str(trace)],
                cwd=root, stdout=f, stderr=subprocess.STDOUT).returncode
        last = open(log).read().strip().splitlines()[-1]
        try:
            line = json.loads(last)
        except ValueError:
            line = {"error": last[:300]}
        line.update(set=k, seed=seed, trace=trace, rc=rc)
        results.append(line)
        print(json.dumps(line)[:1500], flush=True)
    with open(os.path.join(args.out, "results.jsonl"), "w") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")
    for k in range(args.sets):
        runs = [r for r in results if r["set"] == k and r.get("metrics")]
        for name in sorted({m for r in runs for m in r["metrics"]}):
            vals = [r["metrics"][name]["value"] for r in runs]
            if len(vals) >= 2:
                print(f"set {k} {name}: median {S.median(vals):.6g}, spread "
                      f"{100 * S.iqr_share(vals):.3f}% of it, values "
                      f"{[round(v, 4) for v in vals]}", flush=True)
    bad = [r for r in results if r.get("correct") is not True]
    print(f"{len(results)} runs, {len(bad)} not correct or failed: "
          f"{[(r['set'], r['seed'], r['rc']) for r in bad]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
