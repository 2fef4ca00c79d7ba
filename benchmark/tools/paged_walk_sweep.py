"""What a walk of the per-head ragged kernel pays for its FIRST group: ONE
layer's call of ``ragged_paged_attention`` (``paddle_tpu/ops/
ragged_paged_attention.py``) alone on one chip, on random bf16 data with
page tables shuffled over a pool of the cell's order of blocks, at the
launches of the five cells that run the kernel (the blocks a call walks
are made to come out as tabled in PERF.md section 6, PR 49):

    a         gpt2-large's plain launch: 64 decode rows, Qp 512, 20 heads
              of 64, contexts 128-1,024 tokens (1,721 blocks of 80 KB)
    b         the same beside a chunk of 512 rows at position 320: Qp
              1,024, the chunk 16 wide steps (2,313 blocks)
    d-window  mimo-v2-flash's window layers: 128 decode rows, Qp 1,024, 64
              heads of 192 | 128 on 8 KV heads, W 128, sinks (1,142)
    c         its global layers: 4 KV heads, contexts ~4.4 k (35,015)
    d         lfm2's: 32 heads on 8 KV heads of 64, the contexts of c
    e         sdar's: 128 blocks of 4 rows under the block mask, 32 heads
              on 4 KV heads of 128, contexts ~1.4 k tokens (11,244)
    f         falcon-h1's: 64 decode rows, Qp 512, 20 heads on 4 KV heads
              of 128 (q_group 5, staged q blocks), ~1.1 k tokens (4,476)

under the walk of the parent (``parent_kernel``: commit 60f37f0's, every
walk starts its own first group and waits for it with nothing before it;
kept here as the yardstick) and under the kernel the library ships (PR
49: a walk's first group is started from the last trip of the walk
before it).

A line a variant: device us a call (the events of the kernel in one
trace), ns a block walked, the share of 819 GB/s the blocks' bytes make,
the walks of the call and how many began on a handed group, the time of
a first call (trace, lower, compile, run, the persistent compile cache
off, the shorter of two: ``compile_s``) and the
largest difference from the parent's output (of the first call and of
the last, which runs on what the others left behind: 0.0 is bit-equal).
Written to ``chiprun_out/paged_walk_sweep.jsonl`` too.

    python3 benchmark/tools/paged_walk_sweep.py [--shapes a,b,...] [--only shipped]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import ragged_paged_attention as rpa
from paddle_tpu.ops.pallas_kernels import _interpret, _x64_off

BS = 16
HBM_BYTES_PER_S = 819e9
CALLS = 16

# name: query heads, KV heads, K lanes, V lanes, a stored row's lanes, the
# kernel's options; sequences, their rows (a decode row, a block of 4),
# their contexts (tokens, log-uniform, then scaled until the call walks
# ``blocks``); a chunk's (rows, position); the table's width (the cell's
# max_len in blocks), the pool's blocks a layer and its layers
_MIMO = dict(hq=64, dk=192, dv=128, lanes=384, seqs=128, ctx=(1331, 9000),
             T=576, L=2)
SHAPES = {
    "a": dict(hq=20, hkv=20, dk=64, dv=64, lanes=128, seqs=64,
              ctx=(128, 1024), blocks=1721, T=64, NB=4423, L=4),
    "b": dict(hq=20, hkv=20, dk=64, dv=64, lanes=128, seqs=64,
              ctx=(128, 1024), blocks=1721, chunk=(512, 320), T=64,
              NB=4423, L=4),
    "d-window": dict(_MIMO, hkv=8, kw=dict(window=128, sinks=True),
                     blocks=35015, NB=1344),
    "c": dict(_MIMO, hkv=4, blocks=35015, NB=36000),
    "d": dict(hq=32, hkv=8, dk=64, dv=64, lanes=128, seqs=128,
              ctx=(1331, 9000), blocks=35015, T=576, NB=36000, L=2),
    "e": dict(hq=32, hkv=4, dk=128, dv=128, lanes=256,
              kw=dict(mask_block=4), seqs=128, rows=4, ctx=(400, 3000),
              blocks=11244, T=192, NB=29984, L=2),
    "f": dict(hq=20, hkv=4, dk=128, dv=128, lanes=256, seqs=64,
              ctx=(300, 3000), blocks=4476, T=192, NB=12054, L=2),
    "toy": dict(hq=4, hkv=2, dk=64, dv=64, lanes=128, seqs=6, ctx=(20, 600),
                blocks=90, chunk=(40, 64), T=48, NB=200, L=2),
    "toy-window": dict(hq=4, hkv=2, dk=64, dv=64, lanes=128,
                       kw=dict(window=40, sinks=True), seqs=6,
                       ctx=(20, 600), blocks=90, T=48, NB=200, L=2),
}


def parent_kernel(layer_ref, blk_seq_ref, qstart_ref, pos0_ref, tables_ref,
                  lo_ref, kvlen_ref, *rest, block_q, step_blocks, block_size,
                  group, scale, q_group=1, mask_block=1, window=0,
                  sinks=False):
    """``_rpa_kernel`` as commit 60f37f0 had it (less the int8 path, which
    no shape here takes): a walk starts its own group 0 into buffer 0 and
    group ``grp`` lives in buffer ``grp % 2``."""
    sinks_ref = None
    if sinks:
        q_ref, sinks_ref, pool_ref, o_ref, kv_scr, kv_sem, *staged = rest
    else:
        q_ref, pool_ref, o_ref, kv_scr, kv_sem, *staged = rest
    layer = layer_ref[0]
    blk0 = pl.program_id(0) * jnp.int32(step_blocks)
    n_heads, _, dh = q_ref.shape
    blk_rows = block_q * q_group
    dv = o_ref.shape[-1]
    v0 = kv_scr.shape[-1] - dv
    split = v0 % 128 == 0 and dv % 128 == 0
    cols_g = group * block_size
    t_len = tables_ref.shape[1]
    _BS = jnp.int32(block_size)
    _BQ = jnp.int32(block_q)
    _G = jnp.int32(group)
    _CG = jnp.int32(cols_g)

    def walk(seq, blk, n_blocks, rows, q_src, o_dst):
        q_rows = n_blocks * blk_rows
        q = q_src[:, rows, :].astype(q_ref.dtype)
        if not split:
            q = jnp.concatenate(
                [q, jnp.zeros(q.shape[:-1] + (v0 + dv - dh,), q.dtype)],
                axis=-1)
        p_first = pos0_ref[seq] + blk * _BQ - qstart_ref[seq]
        q_row = jax.lax.broadcasted_iota(jnp.int32, (q_rows, 1), 0)
        if q_group > 1:
            q_row = q_row // jnp.int32(q_group)
        qpos = p_first + q_row
        q_last = qpos if mask_block == 1 else \
            qpos // jnp.int32(mask_block) * jnp.int32(mask_block) \
            + jnp.int32(mask_block - 1)
        lo = lo_ref[seq]
        kv_len = kvlen_ref[seq]
        # the parent's arithmetic: jnp operators
        j_first, n_kv = rpa._walk_extent(
            jnp, p_first, jnp.int32(n_blocks * block_q), lo, kv_len, t_len,
            block_size=block_size, mask_block=mask_block, window=window)
        n_grp = (n_kv - j_first + _G - 1) // _G
        col0 = j_first * _BS
        kv_end = jnp.minimum(kv_len, n_kv * _BS)

        def block_copies(grp, slot, act):
            j0 = grp * _G + j_first

            def one(g, carry):
                at = pl.ds(pl.multiple_of(g * _BS, block_size), block_size)
                act(pltpu.make_async_copy(
                    pool_ref.at[layer, tables_ref[seq, j0 + g]],
                    kv_scr.at[slot, :, at, :], kv_sem.at[slot, g]))
                return carry

            jax.lax.fori_loop(jnp.int32(0), jnp.minimum(_G, n_kv - j0),
                              one, jnp.int32(0))

        block_copies(jnp.int32(0), jnp.int32(0), lambda cp: cp.start())

        def body(grp, carry):
            m_prev, l_prev, acc = carry
            slot = grp % 2

            @pl.when(grp + 1 < n_grp)
            def _prefetch():
                block_copies(grp + 1, 1 - slot, lambda cp: cp.start())

            block_copies(grp, slot, lambda cp: cp.wait())
            kv_rows = col0 + grp * _CG + jax.lax.broadcasted_iota(
                jnp.int32, (cols_g, 1), 0)
            kv = kv_scr[slot]
            kv = jnp.where((kv_rows < kv_end)[None], kv,
                           jnp.zeros_like(kv)).astype(q.dtype)
            s = jax.lax.dot_general(
                q, kv[:, :, :v0] if split else kv,
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            cols = col0 + grp * _CG + jax.lax.broadcasted_iota(
                jnp.int32, (q_rows, cols_g), 1)
            seen = (cols >= lo) & (cols <= q_last) & (cols < kv_len)
            if window:
                seen = seen & (cols > qpos - jnp.int32(window))
            s = jnp.where(seen[None], s, jnp.float32(rpa._NEG_INF))
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(q.dtype), kv[:, :, v0:] if split else kv,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        if sinks:
            m0 = sinks_ref[:, :q_rows, :]
            l0 = jnp.ones((n_heads, q_rows, 1), jnp.float32)
        else:
            m0 = jnp.full((n_heads, q_rows, 1), rpa._NEG_INF, jnp.float32)
            l0 = jnp.zeros((n_heads, q_rows, 1), jnp.float32)
        acc0 = jnp.zeros((n_heads, q_rows, dv if split else v0 + dv),
                         jnp.float32)
        _, l, acc = jax.lax.fori_loop(jnp.int32(0), n_grp, body,
                                      (m0, l0, acc0))
        o_dst[:, rows, :] = ((acc if split else acc[:, :, v0:])
                             / jnp.maximum(l, 1e-30)).astype(o_dst.dtype)

    def q_block(i, q_src, o_dst):
        seq = blk_seq_ref[blk0 + i]
        rows = slice(None) if step_blocks == 1 else pl.ds(
            pl.multiple_of(i * jnp.int32(blk_rows), blk_rows), blk_rows)

        @pl.when(seq < 0)
        def _pad_block():
            o_dst[:, rows, :] = jnp.zeros(
                (n_heads, blk_rows, dv), o_dst.dtype)

        @pl.when(seq >= 0)
        def _attend():
            walk(seq, blk0 + i, 1, rows, q_src, o_dst)

    if step_blocks == 1:
        q_block(jnp.int32(0), q_ref, o_ref)
        return
    one_seq = rpa._one_sequence([blk_seq_ref[blk0 + jnp.int32(i)]
                                 for i in range(step_blocks)])

    @pl.when(one_seq)
    def _wide():
        walk(blk_seq_ref[blk0], blk0, step_blocks, slice(None), q_ref,
             o_ref)

    @pl.when(jnp.logical_not(one_seq))
    def _each():
        q_src, o_dst = staged or (q_ref, o_ref)
        if staged:
            q_src[...] = q_ref[...].astype(jnp.float32)

        def one(i, carry):
            q_block(i, q_src, o_dst)
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(step_blocks), one,
                          jnp.int32(0))
        if staged:
            o_ref[...] = o_dst[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "interpret",
                                             "mask_block", "window",
                                             "v_lanes"))
def parent_call(layer, q, pool, blk_seq, seq_qstart, seq_pos0, tables, lo,
                kv_len, scales, *, scale, block_q, interpret, mask_block=1,
                window=0, sinks=None, v_lanes=0):
    """``_rpa_call`` as commit 60f37f0 had it, around ``parent_kernel``
    (``scales`` is taken and must be None)."""
    assert scales is None
    h, qp, dh = q.shape
    hkv, bs, lanes = pool.shape[2:]
    g = h // hkv
    dv = v_lanes or dh
    v0 = lanes - dv
    if dh < v0 and v0 % 128 == 0 and dv % 128 == 0:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, v0 - dh)))
        dh = v0
    if g > 1:
        q = jnp.swapaxes(q.reshape(hkv, g, qp, dh), 1, 2).reshape(
            hkv, qp * g, dh)
    group = rpa.kv_group_blocks(hkv, bs, 0, pool.dtype, lanes=lanes)
    m = rpa.q_step_blocks(hkv, g, bs, lanes, pool.dtype, v_lanes=v_lanes,
                          q_blocks=qp // block_q)
    kernel = functools.partial(
        parent_kernel, block_q=block_q, step_blocks=m, block_size=int(bs),
        group=group, scale=scale, q_group=g, mask_block=mask_block,
        window=window, sinks=sinks is not None)
    q_rows = m * block_q * g
    staged = m > 1 and (block_q * g) % (8 * 4 // q.dtype.itemsize) != 0
    operands, sink_specs = [q], []
    if sinks is not None:
        operands.append(jnp.tile(sinks.reshape(hkv, 1, g),
                                 (1, m * block_q, 1)).reshape(hkv, q_rows, 1))
        sink_specs.append(pl.BlockSpec(
            (hkv, q_rows, 1), lambda b, *_: (0, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(qp // (m * block_q),),
        in_specs=[
            pl.BlockSpec((hkv, q_rows, dh), lambda b, *_: (0, b, 0)),
            *sink_specs,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((hkv, q_rows, dv), lambda b, *_: (0, b, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, hkv, group * bs, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2, group)),
            *([pltpu.VMEM((hkv, q_rows, dh), jnp.float32),
               pltpu.VMEM((hkv, q_rows, dv), jnp.float32)] if staged
              else []),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_walk_parent_window" if window else "paged_walk_parent",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, qp * g, dv), q.dtype),
        interpret=interpret,
    )(layer, blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len, *operands,
      pool)
    if g > 1:
        out = jnp.swapaxes(out.reshape(hkv, qp, g, dv), 1, 2).reshape(
            h, qp, dv)
    return out


def launch(s, seed):
    """One launch's metadata (numpy) as ``engine._ragged_operands`` lays
    it out: the sequences' rows first, the chunk last, the tables
    shuffled over the whole pool (under a window: the entries a walk
    reads; the freed ones name block 0, as the pool's do). The contexts
    are scaled until the sequences' walks fetch ``s["blocks"]`` blocks.
    Returns the kernel's int32 operands, the padded rows ``Qp`` and
    ``ragged_walk_counts`` of the call."""
    rng = np.random.default_rng(seed)
    kw = s.get("kw", {})
    n, rows, window = s["seqs"], s.get("rows", 1), kw.get("window", 0)
    ctx = np.exp(rng.uniform(np.log(s["ctx"][0]), np.log(s["ctx"][1]), n))
    want = s["blocks"]
    # whole blocks a sequence, scaled to the total, the rest dealt out
    nb = np.clip(np.floor(ctx * want / ctx.sum()).astype(np.int64), 1,
                 s["T"])
    longest = np.argsort(-ctx)
    for i in range(10 * n):
        if nb.sum() == want:
            break
        nb[longest[i % n]] += nb[longest[i % n]] < s["T"]
    # a context ends anywhere in its last block (in whole rows of the
    # sequence's own; one in sixteen on the block's border)
    fill = rng.integers(1, BS // rows + 1, n) * rows
    kv = (nb - 1) * BS + fill
    q_lens = [rows] * n
    pos0s = [int(k) - rows for k in kv]
    if s.get("chunk"):
        q_lens.append(s["chunk"][0])
        pos0s.append(s["chunk"][1])
    S = len(q_lens)
    kv_len = np.asarray([p + m for p, m in zip(pos0s, q_lens)], np.int32)
    qp = sum(-(-m // rpa.BLOCK_Q) * rpa.BLOCK_Q for m in q_lens)
    blk_seq, qstart, pos0, _, _ = rpa.ragged_layout(q_lens, pos0s,
                                                    q_bucket=qp)
    lo = np.zeros(S, np.int32)
    tables = np.zeros((S, s["T"]), np.int32)
    ids = rng.permutation(np.arange(1, s["NB"] + 1))
    at = 0
    for i in range(S):
        last = -(-int(kv_len[i]) // BS)
        first = max(0, pos0s[i] - window + 1) // BS if window else 0
        tables[i, first:last] = ids[at:at + last - first]
        at += last - first
        lo[i] = first * BS
    g = s["hq"] // s["hkv"]
    walked = rpa.ragged_walk_counts(
        blk_seq, qstart, pos0, lo, kv_len, s["T"],
        step_blocks=rpa.q_step_blocks(
            s["hkv"], g, BS, s["lanes"], "bfloat16",
            v_lanes=s["dv"] if s["dv"] != s["dk"] else 0,
            q_blocks=qp // rpa.BLOCK_Q),
        block_size=BS, group=rpa.kv_group_blocks(
            s["hkv"], BS, 0, "bfloat16", lanes=s["lanes"]),
        mask_block=kw.get("mask_block", 1), window=window)
    return (blk_seq, qstart, pos0, tables, lo, kv_len), qp, walked


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="a,b,d-window,c,d,e,f")
    ap.add_argument("--seed", type=int, default=49)
    ap.add_argument("--only", default="",
                    help="substrings of the labels to run, | between them "
                         "(the parent always runs: it is what the outputs "
                         "are held to)")
    args = ap.parse_args(argv)

    from benchmark.lib import trace_reduce as TR

    # compile_s is a compile: no variant may find its program stored
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/paged_walk_sweep.jsonl", "a")
    calls = [("parent", "paged_walk_parent", parent_call),
             ("shipped", "ragged_paged_attention", rpa._rpa_call)]
    for name in args.shapes.split(","):
        s = SHAPES[name]
        kw = dict(s.get("kw", {}))
        meta, qp, walked = launch(s, args.seed)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        bf = jnp.bfloat16
        q = jax.random.normal(keys[0], (s["hq"], qp, s["dk"]), bf)
        pool = jax.random.normal(
            keys[1], (s["L"], s["NB"] + 1, s["hkv"], BS, s["lanes"]), bf)
        sinks = jax.random.normal(keys[2], (s["hq"],), jnp.float32) \
            if kw.pop("sinks", False) else None
        static = dict(scale=float(s["dk"]) ** -0.5, block_q=rpa.BLOCK_Q,
                      interpret=_interpret(), v_lanes=s["dv"], **kw)
        layer = jnp.asarray([s["L"] - 1], jnp.int32)
        ops = tuple(jnp.asarray(m, jnp.int32) for m in meta)
        blocks = walked["kv_steps"]
        stored = blocks * s["hkv"] * BS * s["lanes"] * 2
        print(f"== {name}: Qp {qp}, {len(meta[5])} sequences, {blocks} "
              f"blocks walked a call ({stored / 1e6:.1f} MB), "
              f"{walked['kv_walks']} walks, {walked['kv_fetches']} groups",
              flush=True)
        ref = None
        for label, kernel_name, fn in calls:
            if args.only and label != "parent" and not any(
                    o in label for o in args.only.split("|")):
                continue
            run = lambda: fn(layer, q, pool, *ops, None, sinks=sinks,
                             **static)
            # twice from nothing, the shorter: the first call of a
            # process pays for more than its own kernel
            compile_s = float("inf")
            for _ in range(2):
                fn.clear_cache()
                t0 = time.perf_counter()
                with _x64_off():
                    out = run()
                    out.block_until_ready()
                compile_s = min(compile_s, time.perf_counter() - t0)
            y = np.asarray(out, np.float32)
            if ref is None:
                ref = y
            err = float(np.abs(y - ref).max())
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                t0 = time.perf_counter()
                with _x64_off():
                    for _ in range(CALLS):
                        out = run()
                jax.block_until_ready(out)
                wall = time.perf_counter() - t0
                jax.profiler.stop_trace()
                try:
                    red = TR.reduce_trace(TR.latest_xplane(tmp), wall)
                    us = 1e6 * TR.op_seconds(red, kernel_name) / CALLS
                except ValueError:          # no chip: a rehearsal, no times
                    us = float("nan")
            # the last of the calls ran on what the others left behind
            err = max(err, float(np.abs(
                np.asarray(out, np.float32) - ref).max()))
            if not us:
                print(f"{label:10s} no event named {kernel_name}: "
                      f"{TR.top_ops(red, 3)}", flush=True)
                continue
            handed = walked["kv_walks_handed"] if label == "shipped" else 0
            line = dict(shape=name, variant=label, us=round(us, 2),
                        ns_block=round(1e3 * us / blocks, 2),
                        hbm_share=round(stored / (us * 1e-6)
                                        / HBM_BYTES_PER_S, 4),
                        blocks=blocks, qp=qp, walks=walked["kv_walks"],
                        walks_handed=handed,
                        wall_us=round(1e6 * wall / CALLS, 1),
                        max_abs_diff=err, compile_s=round(compile_s, 2))
            sink.write(json.dumps(line) + "\n")
            sink.flush()
            print(json.dumps(line), flush=True)
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
