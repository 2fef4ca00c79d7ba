#!/usr/bin/env python3
"""Read the numbers the training cell's limits are set from: for each
seed the program's first steps, the plain reference's, and the control's
(the reference with every linear layer in the next lower precision).

    python3 benchmark/tools/calibrate_training.py --workload gpt2-124m.train \\
        --seeds 1,2,3 --quant int8

Prints one JSON line per seed. Never part of a benchmark run.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import harness as H  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--quant", default="fp8")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the control on the first N seeds only")
    args = ap.parse_args()
    _, config, traffic = H.load_cell(args.workload)
    H.require_tpu(1)
    from benchmark import run as R
    from benchmark.lib import system as SUT
    from benchmark.lib import train_check as TC
    drv = R.load_module("drivers", traffic["driver"])
    model, recipe = config["model"], config["training"]
    n = int(recipe["check"]["steps"])
    rows = int(recipe["check"]["rows_per_block"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        batches = drv.token_batches(seed, n, int(traffic["batch"]),
                                    int(traffic["seq"]), int(model["vocab_size"]))
        trainer, opt, prefix = SUT.build_trainer(model, recipe, seed)
        probe = drv.program_probe(trainer, opt, prefix, model, recipe, seed,
                                  batches)
        del trainer, opt
        gc.collect()
        ref = TC.reference_steps(seed, model, recipe, batches, rows)
        out = {"calibrate": args.workload, "seed": seed,
               "program": drv.compare(probe, ref),
               "program_losses": probe["losses"], "reference_losses": ref["losses"]}
        if i < args.control_seeds:
            control = TC.reference_steps(seed, model, recipe, batches, rows,
                                         quant=args.quant)
            out["control"] = drv.compare(control, ref)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
