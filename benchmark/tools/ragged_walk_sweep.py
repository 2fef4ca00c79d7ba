"""How the TPU's ``jax.lax.ragged_dot`` walks the row layout it is handed:
the expert layer's ``routed_experts`` (``paddle_tpu/models/axk1.py``) alone
on one chip, on random bf16 weights at the routed-expert cells' shapes,
with the experts chosen by a random router as the cells' are — under the
parent's layout (pairs sorted back to back, trips of ``M`` pairs clipped
inside an expert) and under the aligned one (every expert's rows begin on
a multiple of ``T``, trips of ``M`` rows), each ``(T, M, combine)`` forced
in place of ``routed_plan``'s, and last under ``routed_plan``'s own.

A line a variant: device ms a call of the whole function (the union of the
device's busy intervals over the calls of one trace), the ms of it in the
events named ``ragged-dot`` (what ``moe_experts_roofline`` divides by), ms
a product (a third of that), the rows walked a call, and the three largest
other ops. Written to ``chiprun_out/ragged_walk_sweep.jsonl`` too.

    python3 benchmark/tools/ragged_walk_sweep.py [--shapes lfm2,sdar,...]

(PERF.md §6, PR 44, holds the table this made.)"""
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

# name: rows Q, share of them real, k, experts N, held n, hidden E, width I,
# the parent's trip (pairs)
SHAPES = {
    "lfm2": dict(Q=1152, real=1.0, k=4, N=64, n=64, E=2048, I=1536,
                 parent_m=4608),
    "sdar": dict(Q=1024, real=0.62, k=8, N=128, n=128, E=2048, I=768,
                 parent_m=6144),
    "sdar_chunk": dict(Q=2048, real=0.47, k=8, N=128, n=128, E=2048, I=768,
                       parent_m=12288),
    "axk1": dict(Q=1152, real=1.0, k=8, N=192, n=12, E=7168, I=2048,
                 parent_m=256),
    "axk1_plain": dict(Q=128, real=1.0, k=8, N=192, n=12, E=7168, I=2048,
                       parent_m=128),
    "mimo": dict(Q=1152, real=1.0, k=8, N=256, n=16, E=4096, I=2048,
                 parent_m=256),
    "toy": dict(Q=64, real=0.8, k=2, N=8, n=8, E=64, I=32, parent_m=128),
}
CALLS = 8


def parent_routed_experts(x, valid, idx, w, experts, held, pair_chunk):
    """``routed_experts`` as it stood before PR 44: the yardstick."""
    import jax
    import jax.numpy as jnp
    gate, up, down = experts
    lo, hi = held
    n = hi - lo
    Q, k = idx.shape
    M = int(pair_chunk)
    on = (idx >= lo) & (idx < hi) & valid[:, None]
    flat_e = jnp.where(on, idx - lo, n).reshape(-1)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    counts = jnp.sum(flat_e[:, None] == jnp.arange(n, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    pairs = ends[-1]
    flat_w = jnp.where(on, w, 0.0).reshape(-1)
    order = jnp.concatenate([order, jnp.zeros(M, jnp.int32)])
    rows_iota = jnp.arange(Q, dtype=jnp.int32)[:, None]

    def chunk(c, y):
        a = c * M
        sel = jax.lax.dynamic_slice(order, (a,), (M,))
        live = (a + jnp.arange(M, dtype=jnp.int32)) < pairs
        rows = sel // k
        sizes = jnp.clip(ends, a, a + M) - jnp.clip(starts, a, a + M)
        xs = x[rows]
        rd = lambda l, r: jax.lax.ragged_dot(
            l, r, sizes, preferred_element_type=jnp.float32)
        h = (jax.nn.silu(rd(xs, gate)) * rd(xs, up)).astype(x.dtype)
        o = rd(h, down)
        o = jnp.where(live[:, None], o * flat_w[sel][:, None], 0.0)
        pick = (rows_iota == rows[None, :]) & live[None, :]
        return y + jnp.dot(pick.astype(x.dtype), o.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    y = jax.lax.fori_loop(jnp.int32(0), (pairs + M - 1) // M, chunk,
                          jnp.zeros(x.shape, jnp.float32))
    return y, (pairs, jnp.sum(counts > 0, dtype=jnp.int32),
               jnp.sum(valid, dtype=jnp.int32), pairs)


def variants(name, s):
    """(label, T, M, combine) of the shape's sweep; T None: the parent,
    T 0: the plan's own. The TPU compiler's row tile is the largest power
    of two up to 512 that divides the trip's rows (``ragged_dot_tiling`` in the
    compiled text): ``M = T x odd`` makes it ``T``."""
    P = s["Q"] * s["k"]
    m = s["parent_m"]
    out = [("parent", None, m, "product")]
    if s["n"] == s["N"]:                     # every expert held: thin groups
        out += [(f"parent M{v}", None, v, "product")
                for v in (256, 512, 1024, 1152, m + 128)]
        whole = lambda T: ((P + s["n"] * (T - 1)) // T // 2 * 2 + 1) * T
        out += [(f"aligned T{T} one trip M{whole(T)}", T, whole(T), "gather")
                for T in (16, 32, 64, 128, 256)]
        for T, odd in ((32, (17, 33, 65)), (64, (9, 17, 33, 65)),
                       (128, (5, 9, 17, 33)), (256, (3, 5, 9, 17))):
            out += [(f"aligned T{T} M{T * o}", T, T * o, "gather")
                    for o in odd]
        out += [("aligned T128 M1024 (tile 512)", 128, 1024, "gather"),
                ("aligned T128 M2048 (tile 512)", 128, 2048, "gather"),
                ("aligned T512 M4608", 512, 4608, "gather"),
                ("unaligned T1 M1152", 1, 1152, "gather"),
                ("aligned T128 M1152 product", 128, 1152, "product"),
                ("aligned T64 M576 product", 64, 576, "product")]
    else:                                    # a share held: fat groups
        out += [(f"unaligned T1 M{m}", 1, m, "product")]
        if m == 128:
            grid = ((8, (136, 264)), (16, (144, 272)), (32, (160, 288)),
                    (64, (192, 320)), (128, (128, 384)))
        else:
            grid = ((16, (272, 528)), (32, (288, 544)), (64, (320, 576)),
                    (128, (384, 640)), (256, (256, 768)))
        for T, ms in grid:
            out += [(f"aligned T{T} M{v}", T, v, "product")
                    for v in ms]
        out += [("aligned T64 M320 gather", 64, 320, "gather")]
    if name.startswith("sdar"):              # the second shape: a trimmed grid
        drop = ("T16 one", "T32 one", "T256 one", "T32 M2080", "T64 M4160",
                "T256 M768", "T256 M4352", "T512", "M2048 (tile",
                "parent M256")
        out = [v for v in out if not any(d in v[0] for d in drop)]
    return out + [("the plan's own", 0, 0, "")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes",
                    default=",".join(k for k in SHAPES if k != "toy"))
    ap.add_argument("--seed", type=int, default=44)
    ap.add_argument("--only", default="",
                    help="substring of the labels to run")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark.lib import trace_reduce as TR
    from paddle_tpu.models import axk1 as AX

    plan = AX.routed_plan
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/ragged_walk_sweep.jsonl", "a")
    for name in args.shapes.split(","):
        s = SHAPES[name]
        Q, k, N, n, E, I = (s[v] for v in "Q k N n E I".split())
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        bf = jnp.bfloat16
        x = jax.random.normal(keys[0], (Q, E), bf)
        router = (jax.random.normal(keys[1], (N, E), jnp.float32)
                  * E ** -0.5).astype(bf)
        experts = tuple(
            (jax.random.normal(kk, shape, bf) * 0.02).astype(bf)
            for kk, shape in zip(keys[2:5], ((n, E, I), (n, E, I), (n, I, E))))
        valid = jnp.asarray(
            np.random.default_rng(args.seed).uniform(size=Q) < s["real"])
        idx, w, _ = jax.jit(
            lambda a, b: AX.route_top_k(a, b, k, 1.0))(x, router)
        held = (0, n)
        on = np.asarray((idx < n) & valid[:, None])
        counts = np.bincount(np.asarray(idx)[on], minlength=n)[:n]
        print(f"== {name}: Q {Q} k {k} held {n} of {N}, E {E} I {I}; "
              f"{int(on.sum())} pairs, an expert {counts.min()}-"
              f"{int(np.median(counts))}-{counts.max()}", flush=True)
        ref = None
        for label, T, M, combine in variants(name, s):
            if args.only and args.only not in label:
                continue
            if T is None:
                fn = jax.jit(
                    lambda *a, M=M: parent_routed_experts(*a, held, M))
            else:
                if T:
                    forced = (T, M, combine == "gather")
                    AX.routed_plan = lambda *a, p=forced: p
                else:
                    AX.routed_plan = plan
                    T, M, gather = plan(n, N, Q, k, E, I)
                    combine = "gather" if gather else "product"
                    label += f" T{T} M{M} {combine}"
                fn = jax.jit(lambda *a: AX.routed_experts(*a, held, N))
            t0 = time.perf_counter()
            try:
                y, counters = fn(x, valid, idx, w, experts)
                y.block_until_ready()
            except Exception as e:          # a layout the compiler refuses
                print(f"{label:38s} FAILED {type(e).__name__}: "
                      f"{str(e)[:200]}", flush=True)
                continue
            compile_s = time.perf_counter() - t0
            y = np.asarray(y, np.float32)
            if ref is None:
                ref = y
            err = float(np.abs(y - ref).max() / (np.abs(ref).max() + 1e-30))
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                t0 = time.perf_counter()
                for _ in range(CALLS):
                    out = fn(x, valid, idx, w, experts)
                jax.block_until_ready(out)
                wall = time.perf_counter() - t0
                jax.profiler.stop_trace()
                try:
                    red = TR.reduce_trace(TR.latest_xplane(tmp), wall)
                except ValueError:          # no chip: a rehearsal, no times
                    red = {"busy_s": float("nan"), "ops": {}}
            ms = 1e3 * red["busy_s"] / CALLS
            rd = 1e3 * TR.op_seconds(red, "ragged-dot") / CALLS
            others = [[kk, round(1e3 * v / CALLS, 4)] for kk, v in
                      sorted(red["ops"].items(), key=lambda kv: -kv[1])
                      if "ragged-dot" not in kk][:3]
            line = dict(shape=name, layout=label, T=T, M=M, combine=combine,
                        ms=round(ms, 4), ragged_dot_ms=round(rd, 4),
                        product_ms=round(rd / 3, 4),
                        wall_ms=round(1e3 * wall / CALLS, 4),
                        rows_walked=int(counters[3]), pairs=int(counters[0]),
                        rel_err=err, compile_s=round(compile_s, 2),
                        others=others)
            sink.write(json.dumps(line) + "\n")
            sink.flush()
            print(f"{label:38s} {ms:8.3f} ms  ragged-dot {rd:7.3f} "
                  f"({rd / 3:6.3f} a product)  walked {int(counters[3]):6d}"
                  f"  err {err:.1e}  {others}", flush=True)
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
