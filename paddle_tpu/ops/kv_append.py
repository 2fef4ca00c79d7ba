"""KV append — the cache write of the fused serving step as one Pallas
TPU kernel.

The fused step (models/generation.py ``_fused_tower``) writes every real
token's K|V row, all heads, into the block pool before the attention
kernel of the layer runs. Written as an XLA scatter
(``pool.at[li, wb, heads, off, :].set(rows)``, ``_write_rows``) that is
``rows x heads`` updates of one 256-byte row each, and XLA's TPU scatter
walks its updates one by one, pad rows included: 10,240 updates a layer
on the plain decode program of GPT-2 large, 0.86 ms, 7/8 of them into
the scratch block nobody reads — 61% of the launch's device time (PERF.md
section 6, PR 30). This kernel touches only real rows and moves a token's
rows for every head in one block-sized DMA each way.

A single row cannot be DMA'd (a bf16 pool packs two rows a sublane:
Mosaic refuses a one-row slice as off the tiling), so the unit is the
token's whole block ``pool[layer, wb]`` — ``[H, bs, lanes]`` (``2 * Dh``
lanes, or what the cache descriptor stores: K 192 | V 128 in 384), one
contiguous region, 80 KB at GPT-2 large. ``pool`` is ONE cache group's
array and ``layer`` the layer's place in it (``models/decoder_spec.py``):
a model of window and global layers appends through this kernel once a
layer, into its group's array with its group's write targets. A REWRITE of a block is: DMA it
into VMEM, replace the rows this launch writes (a select on an iota of
the block's rows, every head at once), DMA it back. A pad row
(``write_block == 0``) starts no DMA and the scratch block is never
written.

Layout contract (the attention kernel's ``Q`` rows,
``ops.ragged_paged_attention.ragged_layout``; a step whose tower runs on
fewer rows lays a layer's K|V rows and the write targets out so before
the call, ``models/generation.py:_fused_tower``): the rows of one q block of
``BLOCK_Q`` are consecutive cache positions of ONE sequence, real rows
first, and no two sequences write one block. So a q block's rows name at
most two blocks (``block_size >= BLOCK_Q``), all rows of a launch that
land in one block are consecutive rows, and a block is rewritten ONCE a
launch (under block generation the SAME rows of a block are rewritten by
every pass of it, one launch each; a diffusion block never straddles a
cache block, so its rows are one rewrite): a rewrite opened by one q block stays open in VMEM while the next
q block's first rows land in the same block (two q blocks of 8 rows share
a KV block of 16; a chunk that starts mid-block), and is written back when
the row after its last names another block. No block is read again while
a write to it is pending, because no block is read twice.

Rewrites in flight: a blocking 80 KB round trip a token is 3-4 us, 64
tokens x 36 layers a third of the gain. The rewrites of a launch are
numbered in row order and take the slots of a ring of ``ring`` VMEM
blocks in turn; a q block's reads are started ``depth`` q blocks ahead of
the grid step that fills them in, the write of a slot's previous rewrite
is waited for only when the slot comes round again, and the last grid
step waits for what is still out. ``ring`` is read from the pool's shape
and dtype against ``APPEND_VMEM_BUDGET`` (``append_ring_blocks``); a q
block opens at most two rewrites and one may stay open from before, so
``depth = (ring - 3) // 2`` q blocks never reach a slot whose write has
not been started.

Operands: ``layer`` rides the scalar-prefetch path (one trace a program,
not one a layer, as in ``_rpa_call``), with ``write_block`` and
``write_off``; the rows come through VMEM a q block a grid step as ``[H,
Q, 2 * Dh]`` (a row of every head is then a sublane of ``H`` tiles, which
broadcasts over the block's rows without a relayout); the pool stays in
HBM (``pl.ANY``) and is aliased to the output, so a donated pool is
updated in place. Heads are a leading dimension of every tile: a
tensor-parallel shard passes its ``[L, NB + 1, H / mp, bs, 2 * Dh]`` and
the kernel reads ``H / mp`` from the shape. Off-TPU the kernel runs in
interpret mode (tests/test_kv_append.py, against ``_write_rows``).

Who calls it: the unquantized full-attention layers of the fused step,
of the speculative verify step and of the tensor-parallel fused step.
The quantized append and the latent append keep their XLA writes
(models/generation.py, the comment
above ``_kv_lanes``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret, _x64_off
from .ragged_paged_attention import BLOCK_Q, check_kv_tile

__all__ = ["kv_append", "append_ring_blocks", "APPEND_VMEM_BUDGET",
           "APPEND_RING_MAX"]

# the ring of block rewrites in flight may take this much VMEM: 1.3 MB at
# gpt2-large (16 blocks of 80 KB); a pool whose blocks are fatter keeps
# fewer in flight
APPEND_VMEM_BUDGET = 4 << 20

# more slots than this hide nothing more: 6 q blocks of reads ahead cover
# a DMA's latency (the same launch: 2.33 ms with 3 slots, 1.44 with 5, 1.15
# with 8, 0.88 with 16, 0.87 with 24)
APPEND_RING_MAX = 16

# q blocks one grid step handles: a grid step costs ~0.35 us whatever it
# does, a decode q block's rewrite about as much (36 layers of 64 decode
# rows at gpt2-large: 1.20 ms at 1, 0.99 at 2, 0.88 at 4 and at 8; PERF.md
# PR 30)
_STEP_Q_BLOCKS = 4


def append_ring_blocks(heads: int, block_size: int, head_dim: int,
                       dtype) -> int:
    """How many block rewrites the kernel keeps in VMEM — read from the
    pool's shape and dtype and from nothing else: as many whole blocks
    (every head, K and V) as fit ``APPEND_VMEM_BUDGET``, at most
    ``APPEND_RING_MAX``, at least the 3 a q block needs (its two, and
    one still open from the q block before)."""
    block_bytes = (int(heads) * int(block_size) * 2 * int(head_dim)
                   * jnp.dtype(dtype).itemsize)
    return max(3, min(APPEND_RING_MAX, APPEND_VMEM_BUDGET // block_bytes))


def _append_kernel(layer_ref, wb_ref, off_ref, rows_ref, pool_in, pool_ref,
                   buf, rsem, wsem, cnt, *, block_q, step_q_blocks, ring,
                   depth):
    """One grid step: ``step_q_blocks`` q blocks, in row order. For each,
    start the reads of the q block ``depth`` ahead, then fill this q
    block's rows into its (at most two) open blocks and start the write
    of each block the next row leaves.

    ``cnt`` (SMEM, kept across grid steps): rewrites whose read was
    started, rewrites whose read was waited for. Both sides find a q
    block's rewrites from ``write_block`` by the same rule
    (``blocks_of``), so the n-th rewrite started is the n-th filled in,
    in slot ``n % ring``. ``pool_in`` is the aliased input: the same HBM
    as ``pool_ref``, which is the one read and written.

    i32-typed constants throughout (the framework's global x64, as in
    ``_rpa_kernel``)."""
    del pool_in
    g = pl.program_id(0)
    layer = layer_ref[0]
    n_rows = wb_ref.shape[0]
    n_qb = n_rows // block_q
    _, bs, lanes = buf.shape[1:]
    _BQ = jnp.int32(block_q)
    _RING = jnp.int32(ring)
    _ONE = jnp.int32(1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (bs, lanes), 0)[None]

    def blocks_of(qb):
        # the blocks q block qb writes: `first`, its first row's (0: a
        # pad q block); `cont`, if the q block before left that block
        # open; `second`, the block its later rows cross into (0: none).
        # Rows are consecutive positions, so the crossing is bs - off
        # rows on; a pad row there reads 0
        r0 = qb * _BQ
        first = wb_ref[r0]
        cont = (qb > 0) & (wb_ref[jnp.maximum(r0 - _ONE, 0)] == first)
        cross = jnp.int32(bs) - off_ref[r0]
        w = wb_ref[r0 + jnp.minimum(cross, block_q - 1)]
        second = jnp.where((cross < block_q) & (w != first), w, 0)
        return first, cont, second

    def read(slot, blk):
        return pltpu.make_async_copy(pool_ref.at[layer, blk], buf.at[slot],
                                     rsem.at[slot])

    def write(slot, blk):
        return pltpu.make_async_copy(buf.at[slot], pool_ref.at[layer, blk],
                                     wsem.at[slot])

    def start_reads(qb):
        first, cont, second = blocks_of(qb)

        def open_rewrite(blk):
            n = cnt[0]
            slot = n % _RING

            @pl.when(n >= _RING)
            def _slot_free():
                # the slot's previous rewrite: wait for its write (any
                # block names the same byte count)
                write(slot, blk).wait()

            read(slot, blk).start()
            cnt[0] = n + _ONE

        pl.when((first > 0) & jnp.logical_not(cont))(
            lambda: open_rewrite(first))
        pl.when(second > 0)(lambda: open_rewrite(second))

    def fill(qb, local):
        first, cont, second = blocks_of(qb)
        r0 = qb * _BQ
        n = cnt[1]
        opens = (first > 0) & jnp.logical_not(cont)
        # the first block's rewrite is the newest one open, or the next
        slot_a = jnp.where(opens, n, jnp.maximum(n - _ONE, 0)) % _RING
        n = n + opens.astype(jnp.int32)
        slot_b = n % _RING
        pl.when(opens)(lambda: read(slot_a, first).wait())
        pl.when(second > 0)(lambda: read(slot_b, second).wait())
        cnt[1] = n + (second > 0).astype(jnp.int32)

        for j in range(block_q):
            r = r0 + jnp.int32(j)
            w = wb_ref[r]

            @pl.when(w > 0)
            def _row(j=j, r=r, w=w):
                k = local * block_q + j
                slot = jnp.where(w == first, slot_a, slot_b)
                buf[slot] = jnp.where(row_ids == off_ref[r],
                                      rows_ref[:, k:k + 1, :], buf[slot])

        # a rewrite is whole when the row after its last names another
        # block (or is a pad row, or there is none)
        last = wb_ref[r0 + jnp.int32(block_q - 1)]
        nxt = jnp.where(qb + _ONE < n_qb,
                        wb_ref[jnp.minimum(r0 + _BQ, n_rows - 1)], 0)
        pl.when((first > 0) & ((last != first) | (nxt != first)))(
            lambda: write(slot_a, first).start())
        pl.when((second > 0) & ((last != second) | (nxt != second)))(
            lambda: write(slot_b, second).start())

    def each(count, body):
        jax.lax.fori_loop(jnp.int32(0), count,
                          lambda i, carry: (body(i), carry)[1], jnp.int32(0))

    @pl.when(g == 0)
    def _prologue():
        cnt[0] = jnp.int32(0)
        cnt[1] = jnp.int32(0)
        each(jnp.int32(min(depth, n_qb)), start_reads)

    for local in range(step_q_blocks):
        qb = g * jnp.int32(step_q_blocks) + jnp.int32(local)
        ahead = qb + jnp.int32(depth)
        pl.when(ahead < n_qb)(lambda ahead=ahead: start_reads(ahead))
        fill(qb, local)

    @pl.when(g == pl.num_programs(0) - 1)
    def _epilogue():
        # every rewrite is closed by now; the last one of each slot that
        # was used still has its write out
        each(jnp.minimum(cnt[0], _RING),
             lambda slot: write(slot, jnp.int32(0)).wait())


def kv_append(pool, layer, write_block, write_off, rows):
    """Write this launch's K|V rows into layer ``layer`` of the block
    pool, in place where the pool is donated.

    * ``pool`` — ``[L, NB + 1, H, bs, 2 * Dh]`` (see
      ops/ragged_paged_attention.py), unquantized;
    * ``write_block`` / ``write_off`` ``[Q]`` int32 — each flattened
      row's physical block and offset in it; ``write_block == 0`` is a
      pad row, and writes nothing (module doc: the layout contract);
    * ``rows`` — ``[Q, H, 2 * Dh]``, each row's K|V of every head
      (``_kv_lanes(k, v)``), cast to the pool's dtype here.

    Returns the pool: equal to ``_write_rows`` everywhere but the
    scratch block 0, which this leaves alone.
    """
    L, nb1, h, bs, lanes = pool.shape
    n_rows = int(write_block.shape[0])
    if pool.dtype.name in ("int8", "float8_e4m3fn"):
        raise ValueError(
            f"a {pool.dtype.name} pool is quantized storage: its append "
            f"rescales whole blocks (models/generation.py _quant_append)")
    if tuple(rows.shape) != (n_rows, h, lanes):
        raise ValueError(
            f"rows shape {tuple(rows.shape)} != (rows, pool heads, pool "
            f"lanes) {(n_rows, h, lanes)}")
    if n_rows % BLOCK_Q:
        raise ValueError(
            f"padded rows {n_rows} must be a multiple of {BLOCK_Q}")
    check_kv_tile(pool.dtype, bs, lanes=lanes)
    if not 0 <= int(layer) < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    with _x64_off():
        return _append_call(
            i32([layer]), i32(write_block), i32(write_off),
            jnp.swapaxes(rows.astype(pool.dtype), 0, 1), pool,
            interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _append_call(layer, write_block, write_off, rows, pool, *, interpret):
    """The Pallas call, a jitted function of its own with ``layer`` an
    operand, so a step program traces and lowers the kernel once
    (``_rpa_call``'s reason). ``rows`` is ``[H, Q, 2 * Dh]`` here."""
    h, n_rows, lanes = rows.shape
    bs = pool.shape[3]
    ring = append_ring_blocks(h, bs, lanes // 2, pool.dtype)
    n_qb = n_rows // BLOCK_Q
    step_q_blocks = _STEP_Q_BLOCKS
    while n_qb % step_q_blocks:
        step_q_blocks //= 2
    kernel = functools.partial(
        _append_kernel, block_q=BLOCK_Q, step_q_blocks=step_q_blocks,
        ring=ring, depth=(ring - 3) // 2)
    step_rows = step_q_blocks * BLOCK_Q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_qb // step_q_blocks,),
        in_specs=[
            pl.BlockSpec((h, step_rows, lanes), lambda g, *_: (0, g, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((ring, h, bs, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((ring,)),
            pltpu.SemaphoreType.DMA((ring,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="kv_append",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands count the scalar-prefetch three: the pool is the fifth
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, write_block, write_off, rows, pool)
