"""How the latent kernel's walk brings a sequence's blocks into VMEM: ONE
layer's call of ``mla_paged_attention`` (``paddle_tpu/ops/
mla_paged_attention.py``) alone on one chip, at the two latent cells'
widths (64 heads, rows of 640 lanes, ``v_lanes`` 512, blocks of 16
tokens), on random bf16 data with page tables shuffled over a pool of the
cells' order of blocks, at three launches:

    a  Qp 1,024 = 128 decode rows, contexts log-uniform 0.5-2.5 k tokens
       (``longcat-flash-ep32.decode``'s plain launch)
    b  the same at 1.3-5 k (``axk1-ep16.decode``'s plain launch)
    c  Qp 2,048 = the decode rows of b + one chunk of 1,024 rows at
       ``pos0`` 1-3 k (``axk1-ep16.decode``'s chunk launch)

under the parent's walk (``parent_kernel``: PR 29's, one DMA, one
semaphore and one wait a block, issued by a loop; the yardstick), under
each part of PR 47's pipeline alone and together (``lab_kernel``, whose
flags are the parts), and under the kernel the library ships.

A line a variant: device us a call (the events of the kernel in one
trace), ns a stored block, the share of 819 GB/s its stored bytes make,
and the largest difference from the parent's output (of the first call
and of the last, which runs on what the others left behind). Written to
``chiprun_out/latent_walk_sweep.jsonl`` too.

    python3 benchmark/tools/latent_walk_sweep.py [--shapes a,b,c] [--only shipped]

(PERF.md §6, PR 47, holds the table this made.)"""
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

import numpy as np

H, LANES, V_LANES, BS = 64, 640, 512, 16
HBM_BYTES_PER_S = 819e9
CALLS = 16

# name: decode rows, their contexts (tokens, log-uniform), a chunk's rows
# and where it starts, the table's width (the cell's max_len in blocks),
# the pool's blocks a layer and its layers (2.7 GB: the order of the
# cells' pools, 33,000 x 8 and 44,000 x 7)
SHAPES = {
    "a": dict(decode=128, ctx=(512, 2560), chunk=0, pos0=(0, 0), T=192,
              NB=33000, L=4),
    "b": dict(decode=128, ctx=(1331, 5120), chunk=0, pos0=(0, 0), T=320,
              NB=33000, L=4),
    "c": dict(decode=128, ctx=(1331, 5120), chunk=1024, pos0=(1024, 3072),
              T=320, NB=33000, L=4),
    "toy": dict(decode=6, ctx=(40, 700), chunk=20, pos0=(100, 300), T=48,
                NB=300, L=2),
}

# the lab kernel's flags. ``sem``: a semaphore a block or a buffer;
# ``pad``: how far a partial group's copies are filled up with the pool's
# scratch block (the unit its waits are made in); ``issue``: a loop or
# unrolled; ``order``: the next group's issue before or after this
# group's wait; ``ahead``: the next q block's first group started in
# this step's last trip; ``zero``: rows past kv_len silenced in every
# group or in the last; ``checks``: Mosaic's bounds checks of every DMA;
# ``flat``: the page tables as ONE row of SMEM; ``static_slot``: the
# issue compiled once a buffer
PARENT_FLAGS = dict(sem="block", pad="none", issue="loop", order="issue",
                    ahead=False, zero="every", checks=True, flat=False,
                    static_slot=False)


def _flags(**changed):
    return dict(PARENT_FLAGS, **changed)


_QUARTERS = dict(sem="slot", pad="part", issue="unrolled")
_GROUPS = dict(sem="slot", pad="group", issue="unrolled", order="wait")
VARIANTS = {
    "1 one wait (quarters)": _flags(sem="slot", pad="part"),
    "1 one wait (whole groups)": _flags(sem="slot", pad="group"),
    "2 unrolled issue": _flags(pad="part", issue="unrolled"),
    "3 first group ahead": _flags(ahead=True),
    "4 zeros in the last group": _flags(zero="last"),
    "1+2 quarters": _flags(**_QUARTERS),
    "1+2 quarters, wait first": _flags(**_QUARTERS, order="wait"),
    "1+2 whole groups, wait first": _flags(**_GROUPS),
    "1+2+3 quarters": _flags(**_QUARTERS, ahead=True),
    "1+2+3 quarters, wait first": _flags(**_QUARTERS, ahead=True,
                                         order="wait"),
    "1+2+3 whole groups, wait first": _flags(**_GROUPS, ahead=True),
    "1+2+3+4 quarters": _flags(**_QUARTERS, ahead=True, zero="last"),
    "1+2+3+4 quarters, wait first": _flags(**_QUARTERS, ahead=True,
                                           zero="last", order="wait"),
    "1+2+3+4 whole groups, wait first": _flags(**_GROUPS, ahead=True,
                                               zero="last"),
    "parent, no bounds checks": _flags(checks=False),
    "1+2+3 quarters, no checks": _flags(**_QUARTERS, ahead=True,
                                        checks=False),
    "1+2+3 quarters, flat tables": _flags(**_QUARTERS, ahead=True,
                                          flat=True),
    "1+2+3 quarters, flat, no checks": _flags(**_QUARTERS, ahead=True,
                                              flat=True, checks=False),
    "1+2+3 quarters, static slot": _flags(**_QUARTERS, ahead=True,
                                          static_slot=True),
    "1+2+3 quarters, flat, static slot": _flags(**_QUARTERS, ahead=True,
                                                flat=True,
                                                static_slot=True),
    "1+2+3 whole groups, flat, no checks": _flags(**_GROUPS, ahead=True,
                                                  flat=True, checks=False),
}


def softmax_step(q, kv, carry, col0, lo, qpos, kv_len, scale, v_lanes):
    """One group of the parent's online softmax — the products every walk
    of this file keeps: ``q [M, lanes]`` against the group's rows ``kv
    [G * bs, lanes]``, whose first column is cache column ``col0``."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.ragged_paged_attention import _NEG_INF
    m_prev, l_prev, acc = carry
    s = jax.lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where((cols >= lo) & (cols <= qpos) & (cols < kv_len), s,
                  jnp.float32(_NEG_INF))
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jax.lax.dot_general(
        p.astype(q.dtype), kv[:, :v_lanes], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def parent_kernel(layer_ref, blk_seq_ref, qstart_ref, pos0_ref, tables_ref,
                  lo_ref, kvlen_ref, q_ref, pool_ref, o_ref, kv_scr, kv_sem,
                  *, block_q, n_heads, block_size, group, scale, v_lanes):
    """``_mla_kernel`` as it stood before PR 47: the yardstick."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops.ragged_paged_attention import _NEG_INF
    b = pl.program_id(0)
    layer = layer_ref[0]
    seq = blk_seq_ref[b]
    cols_g = group * block_size
    t_len = tables_ref.shape[1]

    @pl.when(seq < 0)
    def _pad_block():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(seq >= 0)
    def _attend():
        _BS = jnp.int32(block_size)
        _G = jnp.int32(group)
        _CG = jnp.int32(cols_g)
        pos_first = pos0_ref[seq] + b * jnp.int32(block_q) - qstart_ref[seq]
        lo = lo_ref[seq]
        kv_len = kvlen_ref[seq]
        n_kv = jnp.minimum((kv_len + _BS - 1) // _BS, jnp.int32(t_len))
        n_grp = (n_kv + _G - 1) // _G

        def block_copies(grp, slot, act):
            j0 = grp * _G

            def one(g, carry):
                rows = pl.ds(pl.multiple_of(g * _BS, block_size),
                             block_size)
                act(pltpu.make_async_copy(
                    pool_ref.at[layer, tables_ref[seq, j0 + g]],
                    kv_scr.at[slot, :, rows, :], kv_sem.at[slot, g]))
                return carry

            jax.lax.fori_loop(jnp.int32(0), jnp.minimum(_G, n_kv - j0),
                              one, jnp.int32(0))

        block_copies(jnp.int32(0), jnp.int32(0), lambda cp: cp.start())

        def walk(q):
            m_rows = q.shape[0]
            qpos = pos_first + jax.lax.broadcasted_iota(
                jnp.int32, (m_rows, 1), 0) // jnp.int32(n_heads)

            def body(grp, carry):
                slot = grp % 2

                @pl.when(grp + 1 < n_grp)
                def _prefetch():
                    block_copies(grp + 1, 1 - slot, lambda cp: cp.start())

                block_copies(grp, slot, lambda cp: cp.wait())
                kv_rows = grp * _CG + jax.lax.broadcasted_iota(
                    jnp.int32, (cols_g, 1), 0)
                kv = kv_scr[slot, 0]
                kv = jnp.where(kv_rows < kv_len, kv,
                               jnp.zeros_like(kv)).astype(q.dtype)
                return softmax_step(q, kv, carry, grp * _CG, lo, qpos, kv_len,
                                    scale, v_lanes)

            m0 = jnp.full((m_rows, 1), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((m_rows, 1), jnp.float32)
            acc0 = jnp.zeros((m_rows, v_lanes), jnp.float32)
            _, l, acc = jax.lax.fori_loop(jnp.int32(0), n_grp, body,
                                          (m0, l0, acc0))
            return acc / jnp.maximum(l, 1e-30)

        one_row = kv_len - pos_first == 1

        @pl.when(one_row)
        def _decode_row():
            o_ref[...] = jnp.zeros_like(o_ref)
            o_ref[0:n_heads, :] = walk(q_ref[0:n_heads, :]).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(one_row))
        def _chunk_rows():
            o_ref[...] = walk(q_ref[...]).astype(o_ref.dtype)


def lab_kernel(layer_ref, blk_seq_ref, qstart_ref, pos0_ref, tables_ref,
               lo_ref, kvlen_ref, q_ref, pool_ref, o_ref, kv_scr, kv_sem,
               slot_ref, *, block_q, n_heads, block_size, group, scale,
               v_lanes, sem, pad, issue, order, ahead, zero, checks, flat,
               static_slot, t_len):
    """The parent's products under a walk whose parts are flags (see
    ``VARIANTS``); ``PARENT_FLAGS`` is the parent's walk again."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops.ragged_paged_attention import _NEG_INF
    b = pl.program_id(0)
    n_blk = pl.num_programs(0)
    layer = layer_ref[0]
    seq = blk_seq_ref[b]
    cols_g = group * block_size
    del checks                                  # the call's, not the body's
    scratch_block = jnp.int32(pool_ref.shape[1] - 1)
    parts = 4 if group % 4 == 0 else 1
    part = group // parts                       # blocks of one wait
    unit = {"none": 1, "part": part, "group": group}[pad]
    _BS = jnp.int32(block_size)
    _G = jnp.int32(group)
    _CG = jnp.int32(cols_g)

    def blocks_of(s):
        return jnp.minimum((kvlen_ref[s] + _BS - 1) // _BS, jnp.int32(t_len))

    def filled(s_blocks, j0):
        """blocks of the group at ``j0`` that a copy fills: the
        sequence's own, rounded up to ``unit``"""
        own = jnp.minimum(_G, s_blocks - j0)
        return (own + jnp.int32(unit - 1)) // jnp.int32(unit) * jnp.int32(unit)

    def block_id(s, s_blocks, j0, g):
        """the pool's block ``j0 + g`` of sequence ``s`` (past its last
        block: the pool's scratch block)"""
        j = j0 + g
        at = jnp.minimum(j, jnp.int32(t_len - 1))
        pid = (tables_ref[s * jnp.int32(t_len) + at] if flat
               else tables_ref[s, at])
        if pad != "none":
            pid = jnp.where(j < s_blocks, pid, scratch_block)
        return pid

    def copy_of(pid, slot, g):
        """pool block ``pid`` -> rows ``g`` of ``slot``; ``g`` a Python
        int or a traced scalar"""
        if isinstance(g, int):
            rows = pl.ds(g * block_size, block_size)
        else:
            rows = pl.ds(pl.multiple_of(g * _BS, block_size), block_size)
        return pltpu.make_async_copy(
            pool_ref.at[layer, pid], kv_scr.at[slot, :, rows, :],
            kv_sem.at[slot, g] if sem == "block" else kv_sem.at[slot])

    def start_blocks(s, s_blocks, j0, slot, blocks):
        for g in blocks:
            copy_of(block_id(s, s_blocks, j0, g), slot, g).start()

    def start_group(s, s_blocks, j0, slot, cond):
        if issue == "loop":
            @pl.when(cond)
            def _():
                def one(g, carry):
                    copy_of(block_id(s, s_blocks, j0, g), slot, g).start()
                    return carry
                jax.lax.fori_loop(jnp.int32(0), filled(s_blocks, j0), one,
                                  jnp.int32(0))
        elif pad == "group":
            @pl.when(cond)
            def _():
                for p in range(parts):
                    start_blocks(s, s_blocks, j0, slot,
                                 range(p * part, (p + 1) * part))
        elif static_slot:
            for sl in (0, 1):
                for p in range(parts):
                    @pl.when(cond & (slot == sl)
                             & (j0 + jnp.int32(p * part) < s_blocks))
                    def _(p=p, sl=sl):
                        start_blocks(s, s_blocks, j0, sl,
                                     range(p * part, (p + 1) * part))
        else:
            for p in range(parts):
                @pl.when(cond & (j0 + jnp.int32(p * part) < s_blocks))
                def _(p=p):
                    start_blocks(s, s_blocks, j0, slot,
                                 range(p * part, (p + 1) * part))

    def wait_group(s, s_blocks, j0, slot):
        if sem == "block":
            def one(g, carry):
                copy_of(scratch_block, slot, g).wait()
                return carry
            jax.lax.fori_loop(jnp.int32(0), filled(s_blocks, j0), one,
                              jnp.int32(0))
            return

        def wait_rows(r0, n):
            dst = kv_scr.at[slot, :, pl.ds(r0, n), :]
            pltpu.make_async_copy(dst, dst, kv_sem.at[slot]).wait()

        if pad == "group":
            wait_rows(0, cols_g)
            return
        n_parts = filled(s_blocks, j0) // jnp.int32(part)

        @pl.when(n_parts == parts)
        def _():
            wait_rows(0, cols_g)

        for p in range(parts - 1):
            @pl.when((n_parts < parts) & (p < n_parts))
            def _(p=p):
                wait_rows(p * part * block_size, part * block_size)

    @pl.when(seq < 0)
    def _pad_block():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(seq >= 0)
    def _attend():
        pos_first = pos0_ref[seq] + b * jnp.int32(block_q) - qstart_ref[seq]
        lo = lo_ref[seq]
        kv_len = kvlen_ref[seq]
        n_kv = blocks_of(seq)
        n_grp = (n_kv + _G - 1) // _G
        if ahead:
            first = (b == 0) | (blk_seq_ref[jnp.maximum(b - 1, 0)] < 0)
            slot0 = jnp.where(first, jnp.int32(0), slot_ref[0])
            nxt = jnp.where(b + 1 < n_blk,
                            blk_seq_ref[jnp.minimum(b + 1, n_blk - 1)],
                            jnp.int32(-1))
            nxt_seq = jnp.maximum(nxt, 0)
            nxt_blocks = blocks_of(nxt_seq)
            slot_ref[0] = (slot0 + n_grp) % 2
        else:
            first = True
            slot0 = jnp.int32(0)
        start_group(seq, n_kv, jnp.int32(0), slot0, first)

        def walk(q):
            m_rows = q.shape[0]
            qpos = pos_first + jax.lax.broadcasted_iota(
                jnp.int32, (m_rows, 1), 0) // jnp.int32(n_heads)

            def body(grp, carry):
                slot = (slot0 + grp) % 2
                in_seq = grp + 1 < n_grp

                def issue_next():
                    if ahead:
                        start_group(
                            jnp.where(in_seq, seq, nxt_seq),
                            jnp.where(in_seq, n_kv, nxt_blocks),
                            jnp.where(in_seq, (grp + 1) * _G, 0), 1 - slot,
                            in_seq | (nxt >= 0))
                    else:
                        start_group(seq, n_kv, (grp + 1) * _G, 1 - slot,
                                    in_seq)

                if order == "issue":
                    issue_next()
                wait_group(seq, n_kv, grp * _G, slot)
                if order == "wait":
                    issue_next()
                kv_rows = grp * _CG + jax.lax.broadcasted_iota(
                    jnp.int32, (cols_g, 1), 0)
                if zero == "every":
                    kv = kv_scr[slot, 0]
                    kv = jnp.where(kv_rows < kv_len, kv,
                                   jnp.zeros_like(kv)).astype(q.dtype)
                else:
                    @pl.when(grp == n_grp - 1)
                    def _():
                        tile = kv_scr[slot, 0]
                        kv_scr[slot, 0] = jnp.where(
                            kv_rows < kv_len, tile, jnp.zeros_like(tile))
                    kv = kv_scr[slot, 0].astype(q.dtype)
                return softmax_step(q, kv, carry, grp * _CG, lo, qpos, kv_len,
                                    scale, v_lanes)

            m0 = jnp.full((m_rows, 1), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((m_rows, 1), jnp.float32)
            acc0 = jnp.zeros((m_rows, v_lanes), jnp.float32)
            _, l, acc = jax.lax.fori_loop(jnp.int32(0), n_grp, body,
                                          (m0, l0, acc0))
            return acc / jnp.maximum(l, 1e-30)

        one_row = kv_len - pos_first == 1

        @pl.when(one_row)
        def _decode_row():
            o_ref[...] = jnp.zeros_like(o_ref)
            o_ref[0:n_heads, :] = walk(q_ref[0:n_heads, :]).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(one_row))
        def _chunk_rows():
            o_ref[...] = walk(q_ref[...]).astype(o_ref.dtype)


def walk_call(name, flags=None, *, block_q, interpret):
    """The jitted call of the parent's kernel (``flags`` None) or of the
    lab's under ``flags``, on ``_mla_call``'s operands; the kernel's trace
    name is ``name``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import mla_paged_attention as M

    @jax.jit
    def call(layer, q2, pool, blk_seq, qstart, pos0, tables, lo, kv_len):
        rows, lanes = q2.shape
        bs = pool.shape[3]
        m_blk = block_q * H
        group = M.latent_group_blocks(bs, lanes, pool.dtype)
        static = dict(block_q=block_q, n_heads=H, block_size=int(bs),
                      group=group, scale=0.1, v_lanes=V_LANES)
        if flags is None:
            kernel = functools.partial(parent_kernel, **static)
            sems = [pltpu.SemaphoreType.DMA((2, group))]
        else:
            kernel = functools.partial(lab_kernel, **static, **flags,
                                       t_len=tables.shape[1])
            if flags["flat"]:
                tables = tables.reshape(-1)
            sems = [pltpu.SemaphoreType.DMA(
                (2, group) if flags["sem"] == "block" else (2,)),
                pltpu.SMEM((1,), jnp.int32)]
        checks = True if flags is None else flags["checks"]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(rows // m_blk,),
            in_specs=[pl.BlockSpec((m_blk, lanes), lambda b, *_: (b, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((m_blk, V_LANES), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((2, 1, group * bs, lanes),
                                       pool.dtype)] + sems)
        return pl.pallas_call(
            kernel, name=name, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, V_LANES), q2.dtype),
            compiler_params=pltpu.CompilerParams(
                disable_bounds_checks=not checks),
            interpret=interpret,
        )(layer, blk_seq, qstart, pos0, tables, lo, kv_len, q2, pool)

    return call


def launch(s, seed, block_q):
    """One launch's metadata (numpy) as ``engine._ragged_operands`` lays
    it out: the decode rows first, the chunk last; tables shuffled over
    the whole pool. Returns the kernel's int32 operands, the padded rows
    ``Qp`` and the stored blocks the call reads."""
    from paddle_tpu.ops.ragged_paged_attention import ragged_layout
    rng = np.random.default_rng(seed)
    n = s["decode"]
    ctx = np.exp(rng.uniform(np.log(s["ctx"][0]), np.log(s["ctx"][1]),
                             n)).astype(np.int64)
    q_lens = [1] * n
    pos0s = [int(c) - 1 for c in ctx]
    if s["chunk"]:
        q_lens.append(s["chunk"])
        pos0s.append(int(rng.integers(s["pos0"][0], s["pos0"][1] + 1)))
    S = len(q_lens)
    kv_len = np.asarray([p + m for p, m in zip(pos0s, q_lens)], np.int32)
    qp = n * block_q + -(-s["chunk"] // block_q) * block_q
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, pos0s,
                                                block_q=block_q, q_bucket=qp)
    ids = rng.permutation(s["NB"])
    tables = np.zeros((S, s["T"]), np.int32)
    at = 0
    for i in range(S):
        nb = -(-int(kv_len[i]) // BS)
        tables[i, :nb] = ids[at:at + nb]
        at += nb
    # every q block walks its sequence's whole context
    blocks = sum(-(-int(kv_len[q]) // BS) for q in blk_seq if q >= 0)
    return (blk_seq, qstart, pos0, tables, np.zeros(S, np.int32),
            kv_len), qp, blocks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="a,b,c")
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--only", default="",
                    help="substrings of the labels to run, | between them "
                         "(the parent always runs: it is what the outputs "
                         "are held to)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark.lib import trace_reduce as TR
    from paddle_tpu.ops import mla_paged_attention as M
    from paddle_tpu.ops.pallas_kernels import _interpret, _x64_off
    from paddle_tpu.ops.ragged_paged_attention import BLOCK_Q

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/latent_walk_sweep.jsonl", "a")
    interpret = _interpret()

    def shipped(layer, q2, pool, *meta):
        return M._mla_call(layer, q2, pool, *meta, n_heads=H,
                           v_lanes=V_LANES, scale=0.1, block_q=BLOCK_Q,
                           interpret=interpret)

    calls = [("parent", "latent_walk_parent",
              walk_call("latent_walk_parent", block_q=BLOCK_Q,
                        interpret=interpret))]
    # one name for all of the lab's: a trace holds one variant, and the
    # reader drops a name's trailing digits
    calls += [(label, "latent_walk_lab",
               walk_call("latent_walk_lab", flags, block_q=BLOCK_Q,
                         interpret=interpret))
              for label, flags in VARIANTS.items()]
    calls += [("shipped", "mla_paged_attention", shipped)]
    for name in args.shapes.split(","):
        s = SHAPES[name]
        meta, qp, blocks = launch(s, args.seed, BLOCK_Q)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
        bf = jnp.bfloat16
        # rows as the cells': 576 real lanes of 640
        lane_on = (jnp.arange(LANES) < 576).astype(bf)
        q2 = jax.random.normal(keys[0], (qp * H, LANES), bf) * lane_on
        pool = jax.random.normal(
            keys[1], (s["L"], s["NB"] + 1, 1, BS, LANES), bf) * lane_on
        layer = jnp.asarray([s["L"] - 1], jnp.int32)
        ops = tuple(jnp.asarray(m, jnp.int32) for m in meta)
        stored = blocks * BS * LANES * 2
        print(f"== {name}: Qp {qp}, {len(meta[5])} sequences, {blocks} "
              f"blocks walked a call ({stored / 1e6:.1f} MB stored)",
              flush=True)
        ref = None
        for label, kernel_name, fn in calls:
            if args.only and label != "parent" and not any(
                    o in label for o in args.only.split("|")):
                continue
            t0 = time.perf_counter()
            try:
                with _x64_off():
                    out = fn(layer, q2, pool, *ops)
                    out.block_until_ready()
            except Exception as e:          # a walk Mosaic refuses
                print(f"{label:36s} FAILED {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)
                continue
            compile_s = time.perf_counter() - t0
            y = np.asarray(out, np.float32)
            if ref is None:
                ref = y
            err = float(np.abs(y - ref).max())
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                t0 = time.perf_counter()
                with _x64_off():
                    for _ in range(CALLS):
                        out = fn(layer, q2, pool, *ops)
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
                print(f"{label:36s} no event named {kernel_name}: "
                      f"{TR.top_ops(red, 3)}", flush=True)
                continue
            line = dict(shape=name, variant=label, us=round(us, 2),
                        ns_block=round(1e3 * us / blocks, 2),
                        hbm_share=round(stored / (us * 1e-6)
                                        / HBM_BYTES_PER_S, 4),
                        blocks=blocks, qp=qp,
                        wall_us=round(1e6 * wall / CALLS, 1),
                        max_abs_diff=err, compile_s=round(compile_s, 2))
            sink.write(json.dumps(line) + "\n")
            sink.flush()
            print(json.dumps(line), flush=True)
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
