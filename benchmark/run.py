#!/usr/bin/env python3
"""One cell, once: ``python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Everything that belongs to one configuration, one traffic mix, one driver
or one per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``; this file knows none of them. The last line of
standard output is the result object.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.lib import harness as H  # noqa: E402  (starts the set-up clock)


def load_module(kind: str, name: str):
    path = os.path.join(H.ROOT, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = H.load_json("BENCHMARK.json")
    try:
        cell, config, traffic = H.load_cell(args.workload)
    except KeyError:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(w['name'] for w in bench['workloads'])})",
              file=sys.stderr)
        return 2

    H.require_tpu(int(cell["chips"]))
    H.log(f"cell {cell['name']}: config {cell['config']}, traffic "
          f"{cell['traffic']} (driver {traffic['driver']}), seed {args.seed}, "
          f"{args.seconds:g} s, trace {args.trace}")
    driver = load_module("drivers", traffic["driver"])
    res = driver.run(config, traffic, args.seed, args.seconds, bool(args.trace))

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not listed(m, cell["name"]):
                continue
            value = load_module("layer_metrics", m["name"]).read(res["readings"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(res["end_to_end"], setup_s=res["setup_s"])
        for m in bench["end_to_end"]:
            if listed(m, cell["name"]) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    line = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics,
            "device": res["device"]}
    if args.trace and res.get("breakdown"):
        line["breakdown"] = res["breakdown"]
    H.log(f"setup_s {res['setup_s']:.3f}; end to end {res['end_to_end']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
