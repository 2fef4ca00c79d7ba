"""Latent (MLA) paged attention — the fused serving kernel of a decoder
whose layers cache ONE latent row a token for all heads
(``models/axk1.py``; ``models/decoder_spec.py`` kind ``latent``).

In the absorbed form every head's query is ``[q_lat | q_pe | 0]`` over
the lanes of the cached row ``[c_kv | k_pe | 0]``, the score is their
product, and the value is the first ``v_lanes`` lanes of the SAME row:
K and V are the same stored bytes, fetched once. So the heads of a query
row fold into the MXU's row dimension against the one cached row — a
``[rows * H, lanes] x [lanes, G * bs]`` product a group, then ``[rows *
H, G * bs] x [G * bs, v_lanes]`` — where the per-head kernel
(``ops/ragged_paged_attention.py``) runs ``H`` small products on ``H``
K|V tiles.

The walk is that kernel's (PR 28): the grid is over q blocks of
``BLOCK_Q`` rows, a grid step reads its sequence's page table once, a
block comes in ONE DMA (``pool[layer, pid]``: ``[1, bs, lanes]``), ``G``
blocks go out together into one of two VMEM buffers, the next group is
started before this one is waited for, and the online softmax runs once
a group. What differs: a latent block is small (20 KB at 16 x 640
bfloat16 against 80 KB of GPT-2 large's K|V), so a group is 512 cache
columns (``LATENT_COLUMNS``), four score tiles wide, which quarters the
trips of the loop; and a q block that holds ONE real row (a decode row)
computes on that row's ``H`` MXU rows alone — it is not padded to
``BLOCK_Q x H``. Both bodies are in the one kernel; ``kv_len - pos0``
(the sequence's real rows this launch) picks.

A block that small makes what the walk pays a block weigh as much as its
bytes, so the walk is a pipeline of three parts (PR 47; ``benchmark/
tools/latent_walk_sweep.py`` times each):

* **one fetch = one wait.** The copies of a group signal ONE semaphore a
  buffer and the group is awaited by one wait for the bytes of the whole
  buffer. A sequence's last group may be partial: copies start, and are
  waited for, in QUARTERS of a group (8 blocks), the last quarter filled
  up with the pool's scratch block ``NB`` (finite junk the pad rows of a
  launch are written to) — at most 7 blocks a sequence are read for
  nothing, and their rows lie past ``kv_len``, where the select below
  zeroes them and the mask silences their columns.
* **the issue is unrolled a quarter at a time**: a loop over the
  quarters a group holds, its body 8 copies started back to back — no
  loop trip and no branch a block. In the compiled schedule a started
  DMA holds back every vector load after it, so the issue shares no
  bundle with the products wherever it stands; it stands where the
  parent's did, before this group's wait (after it the next group's
  copies start later and nothing is won: measured).
* **a sequence's first group is in flight before its grid step begins.**
  The last trip of a step's walk starts group 0 of the NEXT q block's
  sequence (a chunk's next q block: the same sequence again) into the
  buffer it does not hold, and leaves the buffer's index in SMEM; the
  grid is sequential and scratch persists, so the next step finds it
  started. The first real block of a call (and one after a pad block)
  starts its own; a block followed by a pad block or by the end of the
  grid starts nothing, so no copy is outstanding when the call returns.

Layout contract: ``q [Qp, H, lanes]`` flattened padded rows as
``ragged_layout`` lays them out; the pool ``[L, NB + 1, 1, bs, lanes]``;
the same scalar-prefetch metadata as the per-head kernel, with
``kv_len[s] = pos0[s] + (real rows of s this launch)`` — the fused step's
own (``engine._ragged_operands``). Returns ``[Qp, H, v_lanes]``.

Mosaic legality: q and o go in as 2-D ``[Qp * H, lanes]`` (the reshape
is XLA's, free) in blocks of ``BLOCK_Q * H`` rows, ``lanes`` and
``v_lanes`` whole 128-lane tiles and ``H`` a multiple of 8
(``check_kv_tile`` with ``lanes=``; the ``pallas-block-tiling`` self-lint
reads this file as it reads every file of ``ops/``). Off-TPU the kernel
runs in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret, _x64_off
from .ragged_paged_attention import (BLOCK_Q, _NEG_INF, check_kv_tile,
                                     kv_group_blocks)

__all__ = ["mla_paged_attention", "latent_group_blocks", "LATENT_COLUMNS",
           "reference_mla_attention"]

# cache columns of one group: four 128-lane score tiles (see module doc)
LATENT_COLUMNS = 512


def latent_group_blocks(block_size: int, lanes: int, dtype) -> int:
    """``G`` of the latent walk, from the pool's shape and dtype alone
    (the engine's ``kv_fetches`` counter asks here too)."""
    return kv_group_blocks(1, block_size, 0, dtype, lanes=lanes,
                           columns=LATENT_COLUMNS)


def _mla_kernel(layer_ref, blk_seq_ref, qstart_ref, pos0_ref, tables_ref,
                lo_ref, kvlen_ref, q_ref, pool_ref, o_ref, kv_scr, kv_sem,
                slot_ref, *, block_q, n_heads, block_size, group, scale,
                v_lanes):
    """One q-block grid step: walk the owning sequence's page table once,
    a group of ``group`` latent blocks at a time, and stream the online
    softmax of ``[rows * H, group * block_size]`` score tiles against the
    one cached row. ``kv_scr`` / ``kv_sem`` are the two group buffers and
    their semaphores, ``slot_ref`` the buffer the NEXT step's walk begins
    on (the module doc's pipeline). i32-typed constants throughout (the
    framework's global x64, as in ``_rpa_kernel``)."""
    b = pl.program_id(0)
    n_blk = pl.num_programs(0)
    layer = layer_ref[0]
    seq = blk_seq_ref[b]
    cols_g = group * block_size
    t_len = tables_ref.shape[1]
    scratch_block = jnp.int32(pool_ref.shape[1] - 1)
    # copies start and are waited for in quarters of a group
    parts = 4 if group % 4 == 0 else 1
    part = group // parts
    _BS = jnp.int32(block_size)
    _G = jnp.int32(group)
    _CG = jnp.int32(cols_g)

    # the scalar arithmetic of the issue is spelled in lax primitives: a
    # jnp operator on a traced scalar is a jitted function of its own,
    # traced again at every one of the unrolled starts (~180 of them a
    # step program: 0.6 s a program on the chip's host, PERF.md 47.5)
    def blocks_of(s):
        return jax.lax.min(
            jax.lax.div(jax.lax.add(kvlen_ref[s], jnp.int32(block_size - 1)),
                        _BS), jnp.int32(t_len))

    def quarters_of(s_blocks, j0):
        """quarters of the group at block ``j0`` that hold a block of a
        sequence of ``s_blocks`` blocks"""
        own = jax.lax.min(_G, jax.lax.sub(s_blocks, j0))
        return jax.lax.div(jax.lax.add(own, jnp.int32(part - 1)),
                           jnp.int32(part))

    def quarter_rows(p):
        return pl.ds(pl.multiple_of(
            jax.lax.mul(p, jnp.int32(part * block_size)),
            part * block_size), part * block_size)

    def start_group(s, s_blocks, j0, slot, cond):
        """If ``cond``: start the copies of sequence ``s``'s group at
        block ``j0`` into buffer ``slot`` — the quarters that hold a
        block of it, each whole (past ``s_blocks``: the scratch block)."""
        def quarter(p, carry):
            dst = kv_scr.at[slot, :, quarter_rows(p), :]
            j_first = jax.lax.add(j0, jax.lax.mul(p, jnp.int32(part)))
            for g in range(part):
                j = jax.lax.add(j_first, jnp.int32(g))
                pid = tables_ref[s, jax.lax.min(j, jnp.int32(t_len - 1))]
                pid = jax.lax.select(jax.lax.lt(j, s_blocks), pid,
                                     scratch_block)
                pltpu.make_async_copy(
                    pool_ref.at[layer, pid],
                    dst.at[:, pl.ds(g * block_size, block_size), :],
                    kv_sem.at[slot]).start()
            return carry

        @pl.when(cond)
        def _issue():
            jax.lax.fori_loop(jnp.int32(0), quarters_of(s_blocks, j0),
                              quarter, jnp.int32(0))

    def wait_group(s_blocks, j0, slot):
        """Wait for what ``start_group`` started there: a whole group in
        ONE wait for the buffer's bytes, a partial one a quarter a wait."""
        def wait_for(dst):
            # a wait reads its byte count off the destination alone
            pltpu.make_async_copy(dst, dst, kv_sem.at[slot]).wait()

        def quarter(p, carry):
            wait_for(kv_scr.at[slot, :, quarter_rows(p), :])
            return carry

        n_parts = quarters_of(s_blocks, j0)

        @pl.when(jax.lax.eq(n_parts, jnp.int32(parts)))
        def _whole():
            wait_for(kv_scr.at[slot])

        @pl.when(jax.lax.lt(n_parts, jnp.int32(parts)))
        def _partial():
            jax.lax.fori_loop(jnp.int32(0), n_parts, quarter, jnp.int32(0))

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
        # the step before this one started group 0 unless it was a pad
        # block's (or there was none): then this step starts its own
        first = (b == 0) | (blk_seq_ref[jnp.maximum(b - 1, 0)] < 0)
        slot0 = jnp.where(first, jnp.int32(0), slot_ref[0])
        nxt = jnp.where(b + 1 < n_blk,
                        blk_seq_ref[jnp.minimum(b + 1, n_blk - 1)],
                        jnp.int32(-1))
        nxt_seq = jnp.maximum(nxt, 0)
        nxt_blocks = blocks_of(nxt_seq)
        slot_ref[0] = (slot0 + n_grp) % 2
        start_group(seq, n_kv, jnp.int32(0), slot0, first)

        def walk(q):
            """Online softmax of ``q [M, lanes]`` (``M = rows * H``, row
            ``r`` at position ``pos_first + r // H``) over the whole
            context -> ``[M, v_lanes]`` float32."""
            m_rows = q.shape[0]
            qpos = pos_first + jax.lax.broadcasted_iota(
                jnp.int32, (m_rows, 1), 0) // jnp.int32(n_heads)

            def body(grp, carry):
                m_prev, l_prev, acc = carry
                slot = (slot0 + grp) % 2
                # what follows this group: the sequence's next, or after
                # its last the first of the next q block's sequence
                in_seq = grp + 1 < n_grp
                start_group(jnp.where(in_seq, seq, nxt_seq),
                            jnp.where(in_seq, n_kv, nxt_blocks),
                            jnp.where(in_seq, (grp + 1) * _G, 0), 1 - slot,
                            in_seq | (nxt >= 0))
                wait_group(n_kv, grp * _G, slot)
                # rows no copy of THIS walk filled, the scratch block's
                # and a last block's rows past kv_len go to the MXU as
                # zeros (a 0 weight does not silence a NaN); in every
                # group: under the products' loads the select is free,
                # in the last group alone it cost a pass of its own
                kv_rows = grp * _CG + jax.lax.broadcasted_iota(
                    jnp.int32, (cols_g, 1), 0)
                kv = kv_scr[slot, 0]                      # [G*bs, lanes]
                kv = jnp.where(kv_rows < kv_len, kv,
                               jnp.zeros_like(kv)).astype(q.dtype)
                s = jax.lax.dot_general(
                    q, kv, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                cols = grp * _CG + jax.lax.broadcasted_iota(
                    jnp.int32, (m_rows, cols_g), 1)
                s = jnp.where((cols >= lo) & (cols <= qpos)
                              & (cols < kv_len), s, jnp.float32(_NEG_INF))
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                # V is the row's first v_lanes lanes: whole 128-lane tiles
                acc_new = acc * alpha + jax.lax.dot_general(
                    p.astype(q.dtype), kv[:, :v_lanes],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            m0 = jnp.full((m_rows, 1), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((m_rows, 1), jnp.float32)
            acc0 = jnp.zeros((m_rows, v_lanes), jnp.float32)
            _, l, acc = jax.lax.fori_loop(jnp.int32(0), n_grp, body,
                                          (m0, l0, acc0))
            return acc / jnp.maximum(l, 1e-30)

        # the sequence's real rows from this q block on: exactly one is a
        # decode row (or a chunk's last), and computes on H MXU rows
        one_row = kv_len - pos_first == 1

        @pl.when(one_row)
        def _decode_row():
            o_ref[...] = jnp.zeros_like(o_ref)
            o_ref[0:n_heads, :] = walk(q_ref[0:n_heads, :]).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(one_row))
        def _chunk_rows():
            o_ref[...] = walk(q_ref[...]).astype(o_ref.dtype)


def mla_paged_attention(q, pool, layer, blk_seq, seq_qstart, seq_pos0,
                        tables, lo, kv_len, *, v_lanes: int, scale: float,
                        block_q: int = BLOCK_Q):
    """Latent paged attention over one layer of a latent block pool.

    * ``q`` — ``[Qp, H, lanes]`` flattened padded query rows in the
      absorbed form (``[q_lat | q_pe | 0]``);
    * ``pool`` — the FULL latent pool ``[L, NB + 1, 1, bs, lanes]``; it
      stays in HBM and ``layer`` indexes it inside the kernel's DMAs;
    * the int32 metadata of ``ragged_paged_attention`` (see the module
      doc for ``kv_len``);
    * ``v_lanes`` — the leading lanes of a cached row that are its value;
      ``scale`` — the softmax scale (the model's: YaRN changes it);
    * returns ``[Qp, H, v_lanes]`` in ``q``'s dtype.
    """
    qp, h, lanes = q.shape
    L, _, rows, bs, pool_lanes = pool.shape
    if (rows, pool_lanes) != (1, lanes):
        raise ValueError(
            f"pool rows/lanes {(rows, pool_lanes)} != (1, {lanes}): a "
            f"latent pool holds one row of the query's width a token")
    if not 0 < int(v_lanes) <= lanes:
        raise ValueError(f"v_lanes {v_lanes} outside (0, {lanes}]")
    check_kv_tile(pool.dtype, bs, lanes=lanes)
    if not _interpret() and (int(v_lanes) % 128 or h % 8):
        raise ValueError(
            f"v_lanes {v_lanes} must be whole 128-lane tiles and heads {h} "
            f"a multiple of 8: the value slice and a decode row's {h} MXU "
            f"rows are taken along tile borders")
    if qp % block_q:
        raise ValueError(
            f"padded q rows {qp} must be a multiple of block_q {block_q}")
    if not 0 <= int(layer) < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    with _x64_off():
        out = _mla_call(
            i32([layer]), q.reshape(qp * h, lanes), pool, i32(blk_seq),
            i32(seq_qstart), i32(seq_pos0), i32(tables), i32(lo),
            i32(kv_len), n_heads=int(h), v_lanes=int(v_lanes),
            scale=float(scale), block_q=int(block_q),
            interpret=_interpret())
    return out.reshape(qp, h, int(v_lanes))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "v_lanes", "scale", "block_q", "interpret"))
def _mla_call(layer, q2, pool, blk_seq, seq_qstart, seq_pos0, tables, lo,
              kv_len, *, n_heads, v_lanes, scale, block_q, interpret):
    """The Pallas call; ``layer`` is a scalar-prefetch operand and the
    call a jitted function of its own, so a step program traces the
    kernel once, not once a layer (PERF.md 28.2)."""
    rows, lanes = q2.shape
    bs = pool.shape[3]
    m_blk = block_q * n_heads
    group = latent_group_blocks(bs, lanes, pool.dtype)
    kernel = functools.partial(
        _mla_kernel, block_q=block_q, n_heads=n_heads, block_size=int(bs),
        group=group, scale=scale, v_lanes=v_lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(rows // m_blk,),
        in_specs=[
            pl.BlockSpec((m_blk, lanes), lambda b, *_: (b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
        ],
        out_specs=pl.BlockSpec((m_blk, v_lanes), lambda b, *_: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, group * bs, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),          # one a group buffer
            pltpu.SMEM((1,), jnp.int32),    # the buffer the next step begins on
        ],
    )
    return pl.pallas_call(
        kernel,
        name="mla_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, v_lanes), q2.dtype),
        interpret=interpret,
    )(layer, blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len, q2, pool)


def reference_mla_attention(q_rows, pool, layer, row_seq, row_pos, tables,
                            lo, *, v_lanes, scale):
    """Numpy oracle for the kernel (tests): per row and head, float32
    softmax attention of ``q_rows [N, H, lanes]`` over the row's ``[lo,
    pos]`` window of latent rows gathered through the page table; the
    value is each row's first ``v_lanes`` lanes."""
    import numpy as np
    q_rows = np.asarray(q_rows, np.float32)
    pool = np.asarray(pool, np.float32)
    bs = pool.shape[3]
    out = np.zeros(q_rows.shape[:2] + (int(v_lanes),), np.float32)
    for i in range(q_rows.shape[0]):
        s, p = int(row_seq[i]), int(row_pos[i])
        cols = np.arange(int(lo[s]), p + 1)
        kv = np.stack([pool[layer, tables[s][c // bs], 0, c % bs]
                       for c in cols])                    # [ctx, lanes]
        logits = q_rows[i] @ kv.T * float(scale)          # [H, ctx]
        w = np.exp(logits - logits.max(axis=-1, keepdims=True))
        w /= w.sum(axis=-1, keepdims=True)
        out[i] = w @ kv[:, :int(v_lanes)]
    return out
