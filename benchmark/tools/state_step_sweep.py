"""What one layer's decode update of the Mamba-2 state costs on the chip:
``paddle_tpu/ops/ssm.py:state_step`` (the Pallas kernel: a slot's state
read and written once, in place) against the form it replaced (``ssm_step``
under ``jit`` with the in-place update: XLA's two fusions that pass over the
state three times), ONE layer's call at the two cells' shapes and slot
counts, the kernel under every head block ``step_head_block`` could pick
and last under its own.

A line a variant: device ms a call (the union of the device's busy
intervals over the calls of one trace), GB/s over the two passes the
algorithm needs (read the slots' states, write them), the share of the
HBM's 819 GB/s that is, the largest difference of the new state and of
``y`` from the first variant's (the float32 reference: ``ssm_step``),
whether the slot that is kept and the row no slot owns came back bit for
bit, and the seconds of the first call (trace, lower, compile). Written to
``chiprun_out/state_step_sweep.jsonl`` too.

    python3 benchmark/tools/state_step_sweep.py [--shapes falcon-h1,nemotron3]

(PERF.md §6, PR 51, holds the table this made.)"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

# name: layers with state L, slots S, heads H, head width P, state N, groups G
SHAPES = {
    "falcon-h1": dict(L=6, S=64, H=32, P=128, N=256, G=2),
    "nemotron3": dict(L=5, S=128, H=128, P=64, N=128, G=8),
    "toy": dict(L=2, S=4, H=32, P=8, N=32, G=2),
}
CALLS = 8
HBM_GBS = 819.0
KEPT, FRESH = 1, 2          # the slots of the launch that are not a plain step


def parent_step(state, layer, how, x, dt, a, b, c, d):
    """The decode update as ``ssm_scan`` made it before PR 51: the
    yardstick, and the float32 reference."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm as SSM
    S = x.shape[0]
    old = state[layer, :S]
    y, stepped = SSM.ssm_step(
        jnp.where((how == 2)[:, None, None, None], 0.0, old),
        x, dt, a, b, c, d)
    return y, state.at[layer, :S].set(
        jnp.where((how != 0)[:, None, None, None], stepped, old))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes",
                    default=",".join(k for k in SHAPES if k != "toy"))
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--only", default="",
                    help="substring of the labels to run")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark.lib import trace_reduce as TR
    from paddle_tpu.ops import ssm as SSM

    own = SSM.step_head_block
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/state_step_sweep.jsonl", "a")
    for name in args.shapes.split(","):
        L, S, H, P, N, G = (SHAPES[name][v] for v in "LSHPNG")
        layer = L - 2
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        f = lambda k, *s: jax.random.normal(k, s, jnp.float32)
        x, b, c = f(keys[0], S, H, P), f(keys[1], S, G, N), f(keys[2], S, G, N)
        dt = jax.nn.softplus(f(keys[3], S, H) - 2.0)
        a = -jnp.exp(jnp.linspace(0.0, 2.0, H, dtype=jnp.float32))
        d = jnp.ones(H, jnp.float32)
        how = jnp.ones(S, jnp.int32).at[KEPT].set(0).at[FRESH].set(2)
        fresh_state = lambda: f(keys[4], L, S + 1, H, P, N)
        start = fresh_state()
        first = np.asarray(start[layer])
        two_passes = 2 * S * H * P * N * 4
        print(f"== {name}: state [{L}, {S + 1}, {H}, {P}, {N}] float32, "
              f"{H * P * N * 4 / 1e6:.2f} MB a slot a layer, "
              f"{two_passes / 1e9:.3f} GB in two passes", flush=True)
        legal = sorted({own(H, P, N, G)} | {
            hb for hb in SSM.step_head_blocks(H, G)
            if 4 * hb * P * N * 4 <= 12 << 20})      # 16 MB of VMEM
        variants = [("parent (ssm_step, two fusions)", None)] \
            + [(f"kernel hb {hb}", hb) for hb in legal] \
            + [("kernel, its own hb", 0)]
        ref = None
        for label, hb in variants:
            if args.only and args.only not in label:
                continue
            if hb is None:
                step = parent_step
            else:
                SSM.step_head_block = (lambda *_, hb=hb: hb) if hb else own
                SSM._step_call.clear_cache()
                step = SSM.state_step
                if not hb:
                    label += f" {own(H, P, N, G)}"
            fn = jax.jit(lambda st, *r: step(st, layer, *r),
                         donate_argnums=(0,))
            rest = (how, x, dt, a, b, c, d)
            t0 = time.perf_counter()
            try:
                y, state = fn(fresh_state(), *rest)
                y.block_until_ready()
            except Exception as e:          # a block the compiler refuses
                print(f"{label:34s} FAILED {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)
                continue
            compile_s = time.perf_counter() - t0
            got = np.asarray(state[layer])
            others = all(bool((state[k] == start[k]).all())
                         for k in range(L) if k != layer)
            y = np.asarray(y)
            if ref is None:
                ref = (got, y)
            stepped = np.asarray(how) != 0
            err_h = float(np.abs(got - ref[0]).max())
            err_y = float(np.abs(y - ref[1])[stepped].max())
            kept = bool((got[KEPT] == first[KEPT]).all()
                        and (got[S] == first[S]).all() and others)
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                t0 = time.perf_counter()
                for _ in range(CALLS):
                    y, state = fn(state, *rest)
                jax.block_until_ready(state)
                wall = time.perf_counter() - t0
                jax.profiler.stop_trace()
                try:
                    red = TR.reduce_trace(TR.latest_xplane(tmp), wall)
                except ValueError:          # no chip: a rehearsal, no times
                    red = {"busy_s": float("nan"), "ops": {}}
            del state
            ms = 1e3 * red["busy_s"] / CALLS
            gbs = two_passes / 1e6 / ms
            ops = [[k, round(1e3 * v / CALLS, 4)] for k, v in
                   sorted(red["ops"].items(), key=lambda kv: -kv[1])][:3]
            line = dict(shape=name, variant=label, hb=hb, ms=round(ms, 4),
                        gbs=round(gbs, 1),
                        hbm_share=round(100 * gbs / HBM_GBS, 1),
                        wall_ms=round(1e3 * wall / CALLS, 4),
                        state_err=err_h, y_err=err_y, kept_bit_equal=kept,
                        compile_s=round(compile_s, 2), ops=ops)
            sink.write(json.dumps(line) + "\n")
            sink.flush()
            print(f"{label:34s} {ms:8.3f} ms  {gbs:6.1f} GB/s "
                  f"({100 * gbs / HBM_GBS:4.1f}%)  state err {err_h:.1e} "
                  f"y err {err_y:.1e} kept {kept}  compile {compile_s:5.2f} s"
                  f"  {ops}", flush=True)
        SSM.step_head_block = own
        SSM._step_call.clear_cache()
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
