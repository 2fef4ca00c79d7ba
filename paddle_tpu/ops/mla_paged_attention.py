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
                lo_ref, kvlen_ref, q_ref, pool_ref, o_ref, kv_scr, kv_sem, *,
                block_q, n_heads, block_size, group, scale, v_lanes):
    """One q-block grid step: walk the owning sequence's page table once,
    a group of ``group`` latent blocks at a time, and stream the online
    softmax of ``[rows * H, group * block_size]`` score tiles against the
    one cached row. i32-typed constants throughout (the framework's
    global x64, as in ``_rpa_kernel``)."""
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
            """Online softmax of ``q [M, lanes]`` (``M = rows * H``, row
            ``r`` at position ``pos_first + r // H``) over the whole
            context -> ``[M, v_lanes]`` float32."""
            m_rows = q.shape[0]
            qpos = pos_first + jax.lax.broadcasted_iota(
                jnp.int32, (m_rows, 1), 0) // jnp.int32(n_heads)

            def body(grp, carry):
                m_prev, l_prev, acc = carry
                slot = grp % 2

                @pl.when(grp + 1 < n_grp)
                def _prefetch():
                    block_copies(grp + 1, 1 - slot, lambda cp: cp.start())

                block_copies(grp, slot, lambda cp: cp.wait())
                # rows no block of this sequence filled, and a last
                # block's rows past kv_len, go to the MXU as zeros (a 0
                # weight does not silence a NaN)
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
            pltpu.SemaphoreType.DMA((2, group)),
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
