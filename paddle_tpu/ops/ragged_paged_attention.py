"""Ragged paged attention — the fused Pallas TPU serving kernel.

A gather-based paged decode step (what this engine had before the
kernel) materializes ``pool[li, :, tables]`` per
layer: every request's WHOLE KV window is copied out of the block pool
on every decode step, and attention then runs over the padded
``table_bucket * block_size`` columns for every slot. This kernel is
the TPU-native form per "Ragged Paged Attention" (PAPERS.md):
the block pool stays in HBM (``memory_space=pl.ANY``), the kernel walks
each sequence's page table directly and streams online softmax over
exactly the blocks a sequence owns. Nothing is gathered, nothing is
padded to the table bucket, and a single launch serves a RAGGED batch of
mixed prefill-chunk and decode rows (the chunked-prefill unlock).

The KV walk (PR 28; the one statement of it): a WALK serves a run of
query rows of one sequence for EVERY head, so the page table is walked
once, not ``H`` times. A KV block comes in ONE
async DMA — ``pool[layer, pid]`` whole, every head's K|V, 80 KB at
GPT-2 large — and ``G`` of them go out together as a group, into one of
two VMEM buffers: the next group's DMAs are started before this group
is waited for, so only a walk's first group is exposed. Compute is
once a group: per head the scores are ``[rows, G * block_size]``, a
full 128 lanes, and the online-softmax update (max, exp, rescale,
``p . V``) runs on that tile; a block's K|V tile goes to the MXU whole
(q zero-extended over the V lanes), never split along its lanes. ``G``
is read from the pool's shape and
dtype alone (``kv_group_blocks``). Before it the walk was one blocking
4 KB copy per (block, head) and two 16-lane products a copy: 0.65 us a
step, 0.86 M steps a decode launch of GPT-2 large (PERF.md section 6).

Wide q steps (PR 36): the grid is over SUPER blocks of ``M`` q blocks
(``q_step_blocks``: 4, 32 query rows, unless the launch is narrower or
a wide step's working set would pass ``Q_VMEM_BUDGET``). A grid step
reads the ``blk_seq`` entries of its ``M`` q blocks. Where all name the
same sequence — the inside of a prompt chunk — it makes ONE walk for all
``32 * g`` folded rows: a KV block is fetched once for 32 query rows,
not four times, in the same two buffers. Otherwise (decode rows, a
chunk's first and last q blocks beside other sequences, pad blocks) it
makes a walk a q block, as before, in a loop over the same code. And a
walk ENDS where its rows stop seeing: at the block of its last row's
last visible column (its own position, or its diffusion block's end),
whatever ``kv_len`` is — a chunk's early rows do not fetch the chunk's
later blocks (``_walk_extent``; a decode row's last column is ``kv_len
- 1``, its walk is what it was). ``ragged_walk_counts`` counts, on the
host, the DMAs and group waits of a launch from the same two functions
the kernel decides by.

The hand-over (PR 49): **a walk's first group is in flight before the
walk begins.** The last trip of a walk starts group 0 of the NEXT walk —
whatever the kernel will really do next, decided by the functions it
decides by: the next q block of this grid step, or from a step's last
walk the next step's wide walk or its first q block's — into the buffer
it does not hold, where its own next group's start would stand (before
this group's wait: after it nothing is won), and leaves that buffer's
index, and that it did, in SMEM; the grid is sequential and scratch
persists, so the next walk finds its group started, skips its own start
and begins on that buffer. The handed group's ``j_first`` and ``n_kv``
are the receiver's own (its window, its block mask, its ``lo``), so the
copies started are exactly those it waits for, on the same semaphores.
The first real walk of a call (and one after a pad block, or after a
walk of no blocks) starts its own; a walk followed by a pad block or by
the end of the grid starts nothing, so no copy is outstanding and no
semaphore signalled when the call returns. A decode walk is 1-8 groups
long and its first group's latency was the one thing nothing hid
(``benchmark/tools/paged_walk_sweep.py``; PERF.md section 6, PR 49). The
issue and the waits STAY loops with a traced trip count, one semaphore
and one wait a block: on blocks of 80 KB the per-block issue and wait
that the latent kernel's 20 KB blocks made it unroll (``ops/
mla_paged_attention.py``) cost a walk of a few groups nothing (one wait a
group and an unrolled issue read +-2% there at PR 48, and paid 6-11% only
on walks of ten groups and more), while their text cost every warm-up
4-6 s — every ``(Q, T)`` step program traces and lowers this kernel, and
``tests/test_ragged_attention.py`` holds its size. ``ragged_walk_counts``
counts the walks and the handed ones too.

Layout contract (the serving engine's fused step builds these):

* queries are FLATTENED over the batch: each sequence's ``q_len[s]``
  rows sit contiguously, padded up to a multiple of ``block_q`` (8, the
  fp32 sublane) so one q block never mixes sequences — decode rows
  cost one padded q block, prefill chunks amortize theirs (a grid step
  of ``M`` q blocks may hold several sequences: it then walks q block
  by q block). These are the KERNEL's ``Q`` rows, and since PR 41 they
  exist only around the kernel call: the step's tower — embedding,
  projections, cache write, mixer, FFN, head — runs on ``R <= Q`` TOWER
  rows, the same sequences in the same slot order WITHOUT the padding
  to ``block_q`` (sequence ``s`` starts at the sum of the real rows of
  the sequences before it, pad rows only at the end), and
  ``models/generation.py`` lays a layer's q out into the ``Q`` rows
  just before the call and reads the output back at the real rows just
  after it. ``R`` is a pure function of the program's ``Q``
  (:func:`tower_rows`); where it equals ``Q`` the two layouts are one
  and nothing is moved. What is ``[R]`` and what ``[Q]``: the per-row
  operands of a step (``token_ids``, ``qpos``, ``write_block``,
  ``write_off``, ``token_src``, a block step's ``row_blk``) and
  ``last_row``'s values are TOWER rows; everything this kernel takes
  (``q``, ``blk_seq``, ``seq_qstart``) is in kernel rows;
* scalar-prefetch metadata maps q blocks back to sequences:
  ``blk_seq`` names the sequence of each q block (−1 = pad block),
  ``seq_qstart``/``seq_pos0`` recover every row's virtual cache
  position, ``tables`` is the page table, ``kv_len`` bounds the KV walk
  and ``lo`` the valid-window floor (the first position the sequence
  still holds: 0 unless the pool has freed blocks behind a sliding
  window, ``serving/paging.py``);
* a row at position ``p`` attends to cache columns ``[lo, p]`` — the
  history PLUS the causal prefix of its own chunk, whose K/V the fused
  step scatters into the pool before the kernel runs. With a static
  ``mask_block`` B > 1 (generation by diffusion over blocks,
  ``models/decoder_spec.py``) the row sees columns up to the END of its
  block of B, ``p // B * B + B - 1``, bounded by ``kv_len`` as ever: the
  block's rows were appended before the kernel runs too. A diffusion
  block never straddles a cache block (the engine requires ``block_size
  % B == 0``), so the rows of one are one DMA.

A static ``window`` W > 0 (a sliding-window layer): the row sees
``[max(lo, p - W + 1), p]``, W keys with its own, and a walk
STARTS at the block of its first row's ``p - W + 1`` and ends at its last
row's block — a decode row walks at most ``ceil(W / block_size) + 1``
blocks whatever the context, the 32 rows of a wide step two more, and the table entries of the blocks before
(freed, and pointing at the scratch block) are never read. ``sinks [H]``
float32: a learned logit a query head joins the softmax's denominator
and adds no value — the running max STARTS at the sink and the running
sum at 1, which is ``exp(s_h - m)`` kept current by the same rescale as
every other term. The window form carries its own kernel name
(``ragged_paged_attention_window``) so that a device trace tells the two
apart.

Grouped-query heads: the pool holds ``Hkv`` KV heads and ``q`` has ``H =
g * Hkv`` query heads, query head ``j`` reading KV head ``j // g``. A KV
head's group of query heads is FOLDED INTO THE ROWS of the products: the
q block of a grid step is ``[Hkv, M * block_q * g, Dh]`` (row ``r`` is
query row ``r // g``, head ``r % g`` of the group: 8 x 8 = 64 MXU rows a
KV tile and q block at ``g`` 8), so a block is still fetched once for all
its readers and the walk is unchanged. The fold and its inverse are two transposes
in the wrapper, which XLA joins with the caller's own. With ``g`` 1 and
B 1 the kernel compiles to what it was.

Pool layout: ``[L, NB + 1, H, block_size, lanes]`` — one block of one
head is a ``(block_size, lanes)`` tile whose lanes hold K in the first
``Dk`` and V in the LAST ``Dv``: ``lanes = 2 * Dh``, K in ``[0, Dh)`` and V
in ``[Dh, 2 * Dh)`` where the two are equally wide (``v_lanes`` 0), and
where they are not (``v_lanes`` given: K 192 | V 128 is stored 384 wide,
K in ``[0, 192)``, zeros to 256, V in ``[256, 384)``) the score product
runs over the K side and the value product over the V lanes, each whole
128-lane tiles. HBM arrays are tiled ``(sublane,
128)`` on their two minor dims and a DMA slice must cover whole tiles:
a ``(block_size, Dh)`` tile at ``Dh = 64`` is refused by Mosaic ("slice
shape must be aligned to tiling (128)") and padded 2x in HBM, while K|V
folded into the lanes is exactly 128 wide at ``Dh = 64`` (and 256 at
``Dh = 128``). ``pool[layer, pid]`` is one contiguous ``[H, bs, lanes]``
region, so one DMA brings a block's K and V for every head. Everything
that touches the pool — ``serving/paging.py``, ``ops/kv_append.py``,
``serving/host_tier.py``, the int8 scales, the head-partitioned TP
shard — reads this one layout.

Mosaic legality (enforced by the ``pallas-block-tiling`` self-lint):
q/o blocks are ``(H, M * block_q * g, Dh)`` with ``block_q = 8``
sublane-aligned and ``Dh`` the full array dim (a q block on its own is a
dynamic slice of ``block_q * g`` of those rows); a KV buffer is ``(H,
G * block_size, 2 * Dh)`` and a block's DMA lands in ``block_size`` of
its rows, with
``block_size`` at least the storage dtype's sublane count and, on a TPU,
``2 * Dh`` a multiple of 128 (``check_kv_tile``; compiled for a described
v5e at GPT-2 widths in tests/test_tpu_compile.py).

Off-TPU the kernel runs in interpret mode — that is how the tier-1
parity suite (tests/test_ragged_attention.py) executes the kernel body
on CPU.

Tensor-parallel use (ISSUE 15): the kernel is head-count agnostic —
heads are a batch dimension of one grid step, so the sharded serving step
(``build_sharded_fused_step_fn``) simply launches it inside a
``shard_map`` with the LOCAL head count ``H/mp`` against each device's
own pool shard ``[L, blocks, H/mp, bs, 2 * Dh]`` (a shard's
``pool[layer, pid]`` is contiguous too). No kernel change: the
page tables and scalar-prefetch metadata are replicated (block indices
are shard-invariant), the per-head outputs are partial sums of the
attention projection, and one downstream ``psum`` joins them.
"""
from __future__ import annotations

import functools
import math
import types
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret, _x64_off

__all__ = ["ragged_paged_attention", "ragged_layout", "BLOCK_Q",
           "MIN_KV_BLOCK", "KV_VMEM_BUDGET", "Q_VMEM_BUDGET",
           "Q_STEP_BLOCKS", "min_kv_block_for", "check_kv_tile",
           "kv_group_blocks", "q_step_blocks", "ragged_walk_counts",
           "TOWER_ROW_MULTIPLE", "tower_rows"]

_NEG_INF = -1e30

# q rows of a q block, the unit a sequence's rows are padded to and the
# rows of a walk that serves one sequence alone: the fp32 sublane count —
# the smallest Mosaic-legal second-to-last block dim, so a decode row (1
# real query) wastes at most 7 pad rows while a prefill chunk fills whole
# blocks. A GRID STEP covers q_step_blocks() of them
BLOCK_Q = 8

# the KV scratch block is (block_size, 2 * Dh): block_size below the
# sublane count has no legal TPU layout
MIN_KV_BLOCK = 8

# the two group buffers of the KV walk (2 x G whole blocks, every head,
# K and V) may take this much VMEM: 1.3 MB at gpt2-large (G = 8 blocks of
# 80 KB), far under it; a pool whose blocks are fatter walks smaller groups
KV_VMEM_BUDGET = 4 << 20

# QUANTIZED storage needs a taller minimum tile (the Mosaic
# (sublane, 128) law: int8/fp8 sublane count is 32) — float pools keep
# the historical MIN_KV_BLOCK floor (sub-sublane float blocks already
# ran on the padded-layout path)
_MIN_KV_BLOCK_BY_DTYPE = {"int8": 32, "float8_e4m3fn": 32}


def min_kv_block_for(dtype) -> int:
    """Smallest Mosaic-legal KV ``block_size`` for a pool storage
    dtype (the scratch block's sublane count)."""
    return _MIN_KV_BLOCK_BY_DTYPE.get(jnp.dtype(dtype).name,
                                      MIN_KV_BLOCK)


def check_kv_tile(dtype, block_size: int, head_dim: int = 0, *,
                  lanes: int = 0) -> None:
    """Raise ``ValueError`` unless one (block, row) tile of the pool —
    ``(block_size, lanes)`` of ``dtype``, ``lanes = 2 * head_dim`` for a
    per-head K|V row unless given (a latent row states its own) — is
    something a kernel can DMA: at least the dtype's sublane count tall
    and, on a TPU, whole 128-lane tiles wide. The ONE statement of the
    rule, for both kernels and for the engine's constructor."""
    name = jnp.dtype(dtype).name
    need = min_kv_block_for(dtype)
    lanes = int(lanes) or 2 * int(head_dim)
    if int(block_size) < need:
        raise ValueError(
            f"block_size {block_size} < {need}: the {name} KV block tile "
            f"has no legal (sublane, 128) TPU tiling below the dtype's "
            f"sublane count")
    if not _interpret() and lanes % 128:
        raise ValueError(
            f"head_dim {head_dim}: the K|V block tile is "
            f"{lanes} lanes wide, and a TPU DMA slice must "
            f"cover whole 128-lane tiles")


def kv_group_blocks(heads: int, block_size: int, head_dim: int,
                    dtype, *, lanes: int = 0, columns: int = 128) -> int:
    """``G``: how many KV blocks the kernel fetches, and computes on, at
    a time — read from the pool's shape and dtype and from nothing else.
    As many as fill one 128-lane score tile (``G * block_size = 128``: 8
    at ``block_size`` 16, 4 at the int8 minimum 32), halved only while
    the two group buffers of ``G`` whole blocks (every head, K and V)
    would pass ``KV_VMEM_BUDGET``. The engine's ``kv_fetches`` counter
    asks here too, so it counts the groups the kernel waits for.
    ``lanes`` (a row's width where it is not ``2 * head_dim``) and
    ``columns`` (the score tile's width, 128 unless the caller's kernel
    takes wider ones) are the latent kernel's."""
    g = max(1, int(columns) // int(block_size))
    block_bytes = (int(heads) * int(block_size)
                   * (int(lanes) or 2 * int(head_dim))
                   * jnp.dtype(dtype).itemsize)
    while g > 1 and 2 * g * block_bytes > KV_VMEM_BUDGET:
        g //= 2
    return g


# a grid step's own working set — its q and o blocks (double-buffered by
# the pipeline), one group's scores and weights, the accumulator old and
# new — may take this much VMEM beside the two group buffers: 7.3 MB at
# MiMo-V2-Flash's global layers (4 KV heads x 512 folded rows), 1.6 MB at
# gpt2-large
Q_VMEM_BUDGET = 8 << 20

# the widest grid step, in q blocks: 32 query rows share one walk
Q_STEP_BLOCKS = 4


def q_step_blocks(heads: int, q_group: int, block_size: int, lanes: int,
                  dtype, *, v_lanes: int = 0, q_blocks: int = 0) -> int:
    """``M``: how many q blocks one grid step covers — read, like ``G``,
    from the pool's shape and dtype (``heads`` KV heads, rows of ``lanes``
    with V in the last ``v_lanes``), the query heads a KV head
    (``q_group``) and the launch's q blocks, and from nothing else.
    ``Q_STEP_BLOCKS``, halved while a wide step's working set would pass
    ``Q_VMEM_BUDGET`` and until it divides ``q_blocks`` (the engine's
    buckets are powers of two). The engine's counters ask here too."""
    m = Q_STEP_BLOCKS
    cols = kv_group_blocks(heads, block_size, 0, dtype,
                           lanes=lanes) * int(block_size)
    # the accumulator is V's lanes wide where K and V are whole tiles
    # apart, a stored row's otherwise
    dv = int(v_lanes) or int(lanes) // 2
    acc_lanes = dv if (int(lanes) - dv) % 128 == 0 and dv % 128 == 0 \
        else int(lanes)
    row_bytes = (2 * int(lanes) * max(jnp.dtype(dtype).itemsize, 2)
                 + 2 * cols * 4 + 2 * acc_lanes * 4)
    rows = int(heads) * BLOCK_Q * int(q_group)
    while m > 1 and (m * rows * row_bytes > Q_VMEM_BUDGET
                     or int(q_blocks) % m):
        m //= 2
    return m


def _one_sequence(seqs):
    """Whether a grid step's q blocks (``seqs``: their ``blk_seq``
    entries) are rows of ONE sequence, so that one walk serves them all:
    the inside of a prompt chunk. Decode rows, a chunk's first and last q
    blocks beside their neighbours and pad blocks are not. Scalars in the
    kernel, arrays (a column a q block of the step) on the host."""
    same = seqs[0] >= 0
    for s in seqs[1:]:
        same = same & (s == seqs[0])
    return same


# numpy's names for jax.lax's scalar primitives: what ``_walk_extent``
# computes with inside the kernel. A jnp operator on a traced scalar is a
# jitted function of its own, traced again wherever it stands (PERF.md
# 47.5); the operands are never negative, so ``lax.div`` is the floor
_LAX = types.SimpleNamespace(
    int32=jnp.int32, add=jax.lax.add, subtract=jax.lax.sub,
    multiply=jax.lax.mul, floor_divide=jax.lax.div, minimum=jax.lax.min,
    maximum=jax.lax.max)


def _walk_extent(xp, p_first, n_rows, lo, kv_len, t_len, *, block_size,
                 mask_block, window):
    """``(j_first, n_kv)``: the entries ``[j_first, n_kv)`` of a
    sequence's page table that ``n_rows`` query rows at consecutive
    positions from ``p_first`` walk. The walk ends at the block of the
    last row's last visible column — its own position, or its diffusion
    block's end — bounded by ``kv_len`` and the table; under a window it
    starts at the block of the first row's ``p - W + 1`` (the entries
    before name freed blocks). ``xp`` is ``_LAX`` in the kernel (int32
    scalars, of the walk it makes and of the walk it hands a first group
    to) and ``np`` in ``ragged_walk_counts`` (int32 arrays, an entry a
    walk): the ONE statement of what is fetched."""
    i32 = xp.int32
    bs = i32(block_size)
    p_last = xp.add(p_first, xp.subtract(n_rows, i32(1)))
    if mask_block > 1:
        b = i32(mask_block)
        p_last = xp.add(xp.multiply(xp.floor_divide(p_last, b), b),
                        i32(mask_block - 1))
    n_kv = xp.minimum(
        xp.minimum(xp.floor_divide(xp.add(kv_len, i32(block_size - 1)), bs),
                   i32(t_len)),
        xp.add(xp.floor_divide(p_last, bs), i32(1)))
    if not window:
        return i32(0), n_kv
    j_first = xp.floor_divide(
        xp.maximum(xp.maximum(xp.subtract(p_first, i32(window - 1)), lo),
                   i32(0)), bs)
    return j_first, n_kv


def ragged_walk_counts(blk_seq, seq_qstart, seq_pos0, lo, kv_len, t_len, *,
                       step_blocks, block_size, group, mask_block=1,
                       window=0):
    """What the kernel does on a launch's layout, a layer (host, numpy):
    ``kv_steps`` block DMAs, ``kv_fetches`` waits for a group of ``group``
    blocks, ``q_blocks`` real q blocks and ``q_blocks_wide`` of them
    served by a wide step, ``kv_walks`` walks of at least one block and
    ``kv_walks_handed`` of them that found their first group started by
    the walk before (every walk whose q blocks follow, with no pad block
    between, those of another walk of at least one block) — from
    ``_one_sequence`` and ``_walk_extent``, the functions the kernel
    itself decides by."""
    m = int(step_blocks)
    blk_seq = np.asarray(blk_seq, np.int32)
    steps = blk_seq.reshape(-1, m)
    wide = _one_sequence([steps[:, i] for i in range(m)]) if m > 1 \
        else np.zeros(len(steps), bool)
    in_wide = np.repeat(wide, m)
    # every walk by its first q block, in the order the kernel makes
    # them: a real q block on its own, or a wide step's first
    opens = ~in_wide & (blk_seq >= 0)
    opens[np.flatnonzero(wide) * m] = True
    first = np.flatnonzero(opens)
    n_blocks = np.where(in_wide[first], m, 1).astype(np.int32)
    seq = blk_seq[first]
    p_first = (np.asarray(seq_pos0, np.int32)[seq] + first * BLOCK_Q
               - np.asarray(seq_qstart, np.int32)[seq]).astype(np.int32)
    j_first, n_kv = _walk_extent(
        np, p_first, n_blocks * np.int32(BLOCK_Q),
        np.asarray(lo, np.int32)[seq], np.asarray(kv_len, np.int32)[seq],
        t_len, block_size=block_size, mask_block=mask_block, window=window)
    blocks = np.maximum(n_kv - j_first, 0)
    walks = blocks > 0
    handed = walks[1:] & walks[:-1] \
        & (first[:-1] + n_blocks[:-1] == first[1:])
    return dict(kv_steps=int(blocks.sum()),
                kv_fetches=int((-(-blocks // group)).sum()),
                q_blocks=int((blk_seq >= 0).sum()),
                q_blocks_wide=m * int(wide.sum()),
                kv_walks=int(walks.sum()), kv_walks_handed=int(handed.sum()))


def _rpa_kernel(layer_ref, blk_seq_ref, qstart_ref, pos0_ref, tables_ref,
                lo_ref, kvlen_ref, *rest, block_q, step_blocks, block_size,
                group, scale, quantized=False, q_group=1, mask_block=1,
                window=0, sinks=False):
    """One grid step: ``step_blocks`` q blocks, every head at once. Where
    they are rows of ONE sequence (``_one_sequence``: the inside of a
    chunk) the step makes one WALK for all of them; otherwise it makes a
    walk a q block (a pad block is written as zeros), in a loop over the
    same code.

    A walk goes over the owning sequence's page table ONCE, a group of
    ``group`` KV blocks at a time — one DMA a block brings ``pool[layer,
    pid]`` whole (all heads, K|V), the next group's DMAs are started
    before this group is waited for and computed on (two buffers) — and
    streams online softmax over ``[rows, group * block_size]`` score
    tiles, one update a group. It ends at the block its last row stops
    seeing at (``_walk_extent``). From its last trip it starts the first
    group of the walk after it (``walk_from``; the module doc's
    hand-over): ``hand_ref`` says whether, and into which buffer.

    Only the blocks the walk covers are fetched (``j_first <= j < n_kv
    <= T``: the table is never read past its width, the scratch block
    its padding names never fetched). The rest of a partial group's
    buffer holds whatever an earlier group left there, so those columns
    — and a last block's rows past ``kv_len`` — are masked out of BOTH
    products: the K|V rows by ``where`` to 0 before either (a 0 weight
    does not silence a NaN), the scores by ``where`` (so ``p`` is
    exactly 0).

    Quantized pools (int8/fp8 blocks) ride an 8th scalar-prefetch
    operand: THIS layer's per-block max-abs scale slice ``[2, NB + 1,
    H]`` f32. The stored values go to the MXU as they are (exact in the
    compute dtype) and the scale of each (block, head) multiplies that
    block's columns of the f32 scores (K) and of ``p`` (V) — one
    scalar per (block, head), read in a static loop over heads; HBM
    traffic stays at the narrow storage width.

    i32-typed constants: bare python ints in kernel index math get
    materialized as i64 by Mosaic under the framework's global x64 (the
    pallas_kernels idiom; the call sites also trace under _x64_off)."""
    scales_ref = sinks_ref = None
    if quantized:
        scales_ref, q_ref, pool_ref, o_ref, kv_scr, kv_sem, hand_ref, \
            *staged = rest
    elif sinks:
        q_ref, sinks_ref, pool_ref, o_ref, kv_scr, kv_sem, hand_ref, \
            *staged = rest
    else:
        q_ref, pool_ref, o_ref, kv_scr, kv_sem, hand_ref, *staged = rest
    layer = layer_ref[0]
    blk0 = pl.program_id(0) * jnp.int32(step_blocks)
    n_heads, _, dh = q_ref.shape
    blk_rows = block_q * q_group            # folded rows of one q block
    # V is the last dv lanes of a stored row, K what q multiplies before
    # them (the wrapper zero-extends q to the first V lane where the two
    # sides are whole tiles)
    dv = o_ref.shape[-1]
    v0 = kv_scr.shape[-1] - dv
    # K and V lanes are whole 128-lane tiles each: slicing them apart is
    # free, and neither product then runs over the other's lanes
    split = v0 % 128 == 0 and dv % 128 == 0
    cols_g = group * block_size             # KV columns of one group
    t_len = tables_ref.shape[1]
    _BS = jnp.int32(block_size)
    _BQ = jnp.int32(block_q)
    _G = jnp.int32(group)
    _CG = jnp.int32(cols_g)
    # the hand-over's scalar arithmetic is spelled in lax primitives on
    # int32 values (``_LAX`` says why)
    lax = jax.lax
    i32 = jnp.int32
    n_blk = lax.mul(pl.num_programs(0), i32(step_blocks))

    @pl.when(lax.eq(pl.program_id(0), i32(0)))
    def _nothing_handed_yet():
        hand_ref[0] = i32(0)

    def block_copies(s, j0, s_kv, slot, act):
        # the page-table walk: the group of sequence `s` at its block
        # `j0`, each block ONE copy of pool[layer, pid] — every head's
        # (bs, 2*Dh) K|V tile — into its rows of buffer `slot`; `act`
        # starts or waits. Blocks past `s_kv`, the walk's last, are not
        # touched.
        def one(g, carry):
            at = pl.ds(pl.multiple_of(g * _BS, block_size), block_size)
            act(pltpu.make_async_copy(
                pool_ref.at[layer, tables_ref[s, j0 + g]],
                kv_scr.at[slot, :, at, :], kv_sem.at[slot, g]))
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.minimum(_G, s_kv - j0),
                          one, jnp.int32(0))

    def walk_from(nb):
        # the walk the kernel makes next, at q block `nb`, decided as the
        # kernel will decide it when it gets there: -> (whether it is one
        # of at least a block, its sequence, its j_first, its n_kv). A
        # pad block and the end of the grid are none
        seqs = [blk_seq_ref[lax.min(lax.add(nb, i32(i)),
                                    lax.sub(n_blk, i32(1)))]
                for i in range(step_blocks)]
        s = lax.max(seqs[0], i32(0))
        n_rows = i32(block_q)
        if step_blocks > 1:
            # a step's first q block opens a wide walk where the step's
            # q blocks are all one sequence's
            wide = lax.bitwise_and(
                lax.eq(lax.rem(nb, i32(step_blocks)), i32(0)),
                _one_sequence(seqs))
            n_rows = lax.select(wide, i32(step_blocks * block_q), n_rows)
        p_first = lax.sub(lax.add(pos0_ref[s], lax.mul(nb, _BQ)),
                          qstart_ref[s])
        j_first, n_kv = _walk_extent(
            _LAX, p_first, n_rows, lo_ref[s], kvlen_ref[s], t_len,
            block_size=block_size, mask_block=mask_block, window=window)
        real = lax.bitwise_and(lax.lt(nb, n_blk), lax.ge(seqs[0], i32(0)))
        return lax.bitwise_and(real, lax.gt(n_kv, j_first)), s, j_first, n_kv

    def walk(seq, blk, n_blocks, rows, q_src, o_dst):
        # the q blocks [blk, blk + n_blocks) of sequence `seq`: rows
        # `rows` of this step's q and o blocks (or of their staged copies)
        q_rows = n_blocks * blk_rows
        # a block's K|V tile goes to the MXU whole, never split along
        # its lanes: q is zero-extended over the V lanes, so q . [K|V]^T
        # is q . K^T, and p . [K|V] holds p . V in its upper Dh lanes
        q = q_src[:, rows, :].astype(q_ref.dtype)       # [H, rows, Dh]
        if not split:
            q = jnp.concatenate(
                [q, jnp.zeros(q.shape[:-1] + (v0 + dv - dh,), q.dtype)],
                axis=-1)
        # virtual cache position of each row: rows of a sequence are
        # consecutive tokens starting at seq_pos0 (pad rows past the
        # real q_len see the whole context and nobody reads them); a
        # folded row r is query row r // q_group
        p_first = pos0_ref[seq] + blk * _BQ - qstart_ref[seq]
        q_row = jax.lax.broadcasted_iota(jnp.int32, (q_rows, 1), 0)
        if q_group > 1:
            q_row = q_row // jnp.int32(q_group)
        qpos = p_first + q_row                          # [rows, 1]
        # the last column a row sees: its own, or its block's last
        q_last = qpos if mask_block == 1 else \
            qpos // jnp.int32(mask_block) * jnp.int32(mask_block) \
            + jnp.int32(mask_block - 1)
        lo = lo_ref[seq]
        kv_len = kvlen_ref[seq]
        j_first, n_kv = _walk_extent(
            _LAX, p_first, i32(n_blocks * block_q), lo, kv_len, t_len,
            block_size=block_size, mask_block=mask_block, window=window)
        n_grp = (n_kv - j_first + _G - 1) // _G
        col0 = j_first * _BS
        # rows of the buffers past the walk's last block hold what an
        # earlier walk left there
        kv_end = jnp.minimum(kv_len, n_kv * _BS)
        # the hand-over: the walk before this one started this walk's
        # first group, into the buffer the scratch names, unless there
        # was none (a call's first walk, one after a pad block): then
        # the walk starts its own, in buffer 0. And this walk will start
        # the next one's from its last trip — what it leaves in the
        # scratch for that walk to read
        handed = lax.eq(hand_ref[0], i32(1))
        slot0 = lax.select(handed, hand_ref[1], i32(0))
        give, nxt_seq, nxt_first, nxt_kv = walk_from(
            lax.add(blk, i32(n_blocks)))
        give = lax.bitwise_and(give, lax.gt(n_grp, i32(0)))
        hand_ref[0] = lax.convert_element_type(give, jnp.int32)
        hand_ref[1] = lax.rem(lax.add(slot0, n_grp), i32(2))

        @pl.when(lax.bitwise_not(handed))
        def _own_first_group():
            block_copies(seq, j_first, n_kv, i32(0), lambda cp: cp.start())

        def col_scales(grp):
            # K's and V's [H, 1, G*bs] f32: scales_ref[0|1, pid, h] over
            # the columns of the group's block holding pid (a block past
            # the walk's last reads the last one's: masked columns)
            blk_of = jax.lax.broadcasted_iota(
                jnp.int32, (1, cols_g), 1) // _BS
            pids = [tables_ref[seq, jnp.minimum(grp * _G + jnp.int32(g),
                                                n_kv - 1)]
                    for g in range(group)]

            def over_columns(which, h):
                row = jnp.zeros((1, cols_g), jnp.float32)
                for g, pid in enumerate(pids):
                    row = jnp.where(blk_of == g,
                                    scales_ref[which, pid, h], row)
                return row

            return [jnp.stack([over_columns(which, h)
                               for h in range(n_heads)])
                    for which in (0, 1)]

        def body(grp, carry):
            # running softmax stats stay [H, rows, 1] (sublane-oriented);
            # rank-1 carries would force lane<->sublane relayouts
            m_prev, l_prev, acc = carry
            slot = lax.rem(lax.add(slot0, grp), i32(2))
            # started before this group is waited for (after it nothing
            # is won): the walk's next group or, from its last trip, the
            # first group of the walk after it
            more = lax.lt(lax.add(grp, i32(1)), n_grp)

            @pl.when(lax.bitwise_or(more, give))
            def _prefetch():
                block_copies(
                    lax.select(more, seq, nxt_seq),
                    lax.select(more, lax.add(lax.mul(lax.add(grp, i32(1)),
                                                     _G), j_first),
                               nxt_first),
                    lax.select(more, n_kv, nxt_kv), lax.sub(i32(1), slot),
                    lambda cp: cp.start())

            block_copies(seq, lax.add(lax.mul(grp, _G), j_first), n_kv, slot,
                         lambda cp: cp.wait())
            # rows no block of this walk filled, and a last block's rows
            # past kv_len, go to the MXU as zeros
            kv_rows = col0 + grp * _CG + jax.lax.broadcasted_iota(
                jnp.int32, (cols_g, 1), 0)
            kv = kv_scr[slot]                           # [H, G*bs, 2*Dh]
            kv = jnp.where((kv_rows < kv_end)[None], kv,
                           jnp.zeros_like(kv)).astype(q.dtype)
            # operands in storage dtype (a quantized pool's values are
            # exact in q's), f32 accumulation (MXU contract shared with
            # the flash kernels)
            s = jax.lax.dot_general(
                q, kv[:, :, :v0] if split else kv,
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # [H, rows, G*bs]
            if quantized:
                k_scale, v_scale = col_scales(grp)
                s = s * k_scale
            cols = col0 + grp * _CG + jax.lax.broadcasted_iota(
                jnp.int32, (q_rows, cols_g), 1)
            seen = (cols >= lo) & (cols <= q_last) & (cols < kv_len)
            if window:
                seen = seen & (cols > qpos - jnp.int32(window))
            # f32-typed fill: a bare python float is weak f64 under the
            # framework's global x64
            s = jnp.where(seen[None], s, jnp.float32(_NEG_INF))
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * v_scale
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(q.dtype), kv[:, :, v0:] if split else kv,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # [H, rows, 2*Dh]
            return m_new, l_new, acc_new

        if sinks:
            # the sink's term of the denominator: exp(s_h - m) with the
            # running max starting AT the sink — 1, rescaled from here on
            # like every other term; it adds nothing to acc (every q
            # block's rows of the operand are the same heads)
            m0 = sinks_ref[:, :q_rows, :]
            l0 = jnp.ones((n_heads, q_rows, 1), jnp.float32)
        else:
            m0 = jnp.full((n_heads, q_rows, 1), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((n_heads, q_rows, 1), jnp.float32)
        acc0 = jnp.zeros((n_heads, q_rows, dv if split else v0 + dv),
                         jnp.float32)
        # i32 bounds: a bare python 0 becomes an i64 induction variable
        # under the framework's global x64, and the interpret-mode body
        # trace happens outside the call site's _x64_off scope
        _, l, acc = jax.lax.fori_loop(jnp.int32(0), n_grp, body,
                                      (m0, l0, acc0))
        o_dst[:, rows, :] = ((acc if split else acc[:, :, v0:])
                             / jnp.maximum(l, 1e-30)).astype(o_dst.dtype)

    def q_block(i, q_src, o_dst):
        # the step's i-th q block on its own: a pad block, or one walk
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
    one_seq = _one_sequence([blk_seq_ref[blk0 + jnp.int32(i)]
                             for i in range(step_blocks)])

    @pl.when(one_seq)
    def _wide():
        walk(blk_seq_ref[blk0], blk0, step_blocks, slice(None), q_ref,
             o_ref)

    @pl.when(jnp.logical_not(one_seq))
    def _each():
        # a q block of its own is a dynamic slice of the step's rows.
        # Where it is not whole tiles of the packed dtype (8 rows of
        # bf16 at g = 1: half a tile) the step's q and o blocks are
        # staged in float32, in which 8 rows are a tile: one aligned
        # conversion a step each way, the values bit for bit
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


def ragged_paged_attention(q, pool, layer, blk_seq, seq_qstart, seq_pos0,
                           tables, lo, kv_len, *, scales=None, scale=None,
                           block_q: int = BLOCK_Q, mask_block: int = 1,
                           window: int = 0, sinks=None, v_lanes: int = 0):
    """Fused paged attention over one layer of the serving block pool.

    * ``q`` — ``[H, Qp, Dh]`` flattened padded query rows (``Qp`` a
      multiple of ``block_q``; per-sequence contiguous, see module doc);
    * ``pool`` — the FULL block pool ``[L, NB + 1, Hkv, bs, lanes]``
      (K|V folded into the lanes, see module doc: ``lanes = 2 * Dh``
      unless ``v_lanes`` says that V is the last ``v_lanes`` of them and
      K the first ``Dh``; ``H`` a multiple of
      ``Hkv``: grouped-query heads); it stays in HBM
      (``memory_space=pl.ANY``) and ``layer`` (a host int) indexes it
      inside the kernel's DMAs, so no per-layer slice is ever
      materialized;
    * ``blk_seq [Qp / block_q]``, ``seq_qstart [S]``, ``seq_pos0 [S]``,
      ``tables [S, T]``, ``lo [S]``, ``kv_len [S]`` — int32
      scalar-prefetch metadata (``ragged_layout`` builds the first
      three); ``kv_len[s] <= T * bs``;
    * ``scales`` — REQUIRED for quantized pools (int8/fp8 storage):
      the per-block max-abs scale array ``[L, 2, NB + 1, H]`` f32,
      riding the scalar-prefetch path into SMEM so each DMA'd block
      dequantizes in-register;
    * ``mask_block`` — static B: a row sees columns up to the end of its
      block of B (1: causal; module doc);
    * ``window`` — static W: a row sees its last W columns only (0: all
      of ``[lo, p]``), and the walk starts at the window (module doc);
    * ``sinks`` — ``[H]`` float32, a logit a query head in the softmax's
      denominator (None: none);
    * returns ``[H, Qp, Dv]`` in ``q``'s dtype (``Dv = v_lanes or Dh``).
    """
    h, qp, dh = q.shape
    L, nb1, hp, bs, dh2 = pool.shape
    quantized = pool.dtype.name in ("int8", "float8_e4m3fn")
    dv = int(v_lanes) or dh
    if h % hp or (dh + dv > dh2 if v_lanes else dh2 != 2 * dh):
        raise ValueError(
            f"pool KV heads/lanes {(hp, dh2)} do not fit q heads / "
            f"K + V lanes {(h, dh + dv)}: the query heads must be a "
            f"multiple of the pool's KV heads")
    if int(window) < 0 or (window and int(mask_block) != 1):
        raise ValueError(
            f"window {window} with mask_block {mask_block}: a sliding "
            f"window is built for the causal mask only")
    if quantized and (window or sinks is not None or v_lanes):
        raise ValueError(
            "a sliding window, sink logits and split K|V lanes over "
            "int8/fp8 blocks are not built")
    if sinks is not None and tuple(sinks.shape) != (h,):
        raise ValueError(
            f"sinks shape {tuple(sinks.shape)} != one logit a query head "
            f"{(h,)}")
    if int(mask_block) < 1 or bs % int(mask_block):
        raise ValueError(
            f"mask_block {mask_block} must divide block_size {bs}: a "
            f"diffusion block never straddles a cache block")
    if quantized and h != hp:
        raise ValueError(
            "grouped-query heads over int8/fp8 blocks are not built: the "
            "per-block scales are read per query head")
    check_kv_tile(pool.dtype, bs, lanes=dh2)
    if qp % block_q:
        raise ValueError(
            f"padded q rows {qp} must be a multiple of block_q {block_q}")
    if quantized and scales is None:
        raise ValueError(
            f"a {pool.dtype.name} pool is quantized storage: pass the "
            f"per-block scale array (PagedKVPool.scales)")
    if scales is not None and tuple(scales.shape) != (L, 2, nb1, hp):
        raise ValueError(
            f"scales shape {tuple(scales.shape)} != per-block layout "
            f"{(L, 2, nb1, hp)}")
    if not 0 <= int(layer) < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    with _x64_off():
        return _rpa_call(
            i32([layer]), q, pool, i32(blk_seq), i32(seq_qstart),
            i32(seq_pos0), i32(tables), i32(lo), i32(kv_len),
            None if scales is None else jnp.asarray(scales, jnp.float32),
            scale=scale, block_q=int(block_q), interpret=_interpret(),
            mask_block=int(mask_block), window=int(window),
            sinks=None if sinks is None else jnp.asarray(sinks, jnp.float32),
            v_lanes=dv)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "interpret",
                                             "mask_block", "window",
                                             "v_lanes"))
def _rpa_call(layer, q, pool, blk_seq, seq_qstart, seq_pos0, tables, lo,
              kv_len, scales, *, scale, block_q, interpret, mask_block=1,
              window=0, sinks=None, v_lanes=0):
    """The Pallas call. ``layer`` is a ``[1]`` int32 scalar-prefetch
    operand, not a constant of the kernel, and the call is a jitted
    function of its own: the layers of a step program differ in nothing
    the trace can see, so the kernel is traced and lowered once a
    program, not once a layer (36 times at GPT-2 large, in every
    warm-up, compile cache warm or not)."""
    h, qp, dh = q.shape
    hkv, bs, lanes = pool.shape[2:]
    quant = scales is not None
    g = h // hkv
    dv = v_lanes or dh
    v0 = lanes - dv
    if dh < v0 and v0 % 128 == 0 and dv % 128 == 0:
        # K and V sides are whole tiles with padding between them: q is
        # zero-extended to the first V lane here, so that the kernel's
        # score product is over aligned lanes (192 -> 256)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, v0 - dh)))
        dh = v0
    if g > 1:
        # fold a KV head's group of query heads into the rows: [Hkv, g,
        # Qp, Dh] -> [Hkv, Qp * g, Dh], row r = query row r // g
        q = jnp.swapaxes(q.reshape(hkv, g, qp, dh), 1, 2).reshape(
            hkv, qp * g, dh)
    group = kv_group_blocks(hkv, bs, 0, pool.dtype, lanes=lanes)
    m = q_step_blocks(hkv, g, bs, lanes, pool.dtype, v_lanes=v_lanes,
                      q_blocks=qp // block_q)
    kernel = functools.partial(
        _rpa_kernel, block_q=block_q, step_blocks=m, block_size=int(bs),
        group=group, scale=scale, quantized=quant, q_group=g,
        mask_block=mask_block, window=window, sinks=sinks is not None)
    q_rows = m * block_q * g                # folded rows of a grid step
    # a q block's rows on their own are a dynamic slice of the step's:
    # staged in float32 where they are not whole tiles of q's dtype
    # (the kernel says why)
    staged = m > 1 and (block_q * g) % (8 * 4 // q.dtype.itemsize) != 0
    operands, sink_specs = [q], []
    if sinks is not None:
        # folded row r is head r % g of its KV head's group
        operands.append(jnp.tile(sinks.reshape(hkv, 1, g),
                                 (1, m * block_q, 1)).reshape(hkv, q_rows, 1))
        # the whole [Hkv, M * block_q * g, 1] operand is the block: its last
        # dim EQUALS the array's (compiled for a described v5e in
        # tests/test_tpu_compile.py), the layout of the kernel's running
        # max and sum
        sink_specs.append(pl.BlockSpec(  # lint: ok
            (hkv, q_rows, 1), lambda b, *_: (0, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8 if quant else 7,
        grid=(qp // (m * block_q),),
        in_specs=[
            pl.BlockSpec((hkv, q_rows, dh), lambda b, *_: (0, b, 0)),
            *sink_specs,
            pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
        ],
        out_specs=pl.BlockSpec((hkv, q_rows, dv), lambda b, *_: (0, b, 0)),
        scratch_shapes=[
            # two buffers of one group: block g of a group is rows
            # [g*bs, (g+1)*bs) of every head, so a head's K|V of the
            # whole group is one [G*bs, 2*Dh] tile stack
            pltpu.VMEM((2, hkv, group * bs, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2, group)),
            # whether the walk that ended started the next one's first
            # group, and the buffer it went into (the hand-over)
            pltpu.SMEM((2,), jnp.int32),
            *([pltpu.VMEM((hkv, q_rows, dh), jnp.float32),
               pltpu.VMEM((hkv, q_rows, dv), jnp.float32)] if staged
              else []),
        ],
    )
    prefetch = [layer, blk_seq, seq_qstart, seq_pos0, tables, lo, kv_len]
    if quant:
        # only THIS layer's [2, NB+1, H] scale slice goes to SMEM
        prefetch.append(jax.lax.dynamic_index_in_dim(
            scales, layer[0], 0, keepdims=False))
    out = pl.pallas_call(
        kernel,
        name="ragged_paged_attention_window" if window
        else "ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, qp * g, dv), q.dtype),
        interpret=interpret,
    )(*prefetch, *operands, pool)
    if g > 1:
        out = jnp.swapaxes(out.reshape(hkv, qp, g, dv), 1, 2).reshape(
            h, qp, dv)
    return out


# the tower's rows come in whole MXU passes: 128, which is also whole
# (16, 128) tiles of a packed bf16 activation. A matmul on 64 rows and
# one on 128 both stream their weights at the HBM's pace, so rounding 64
# decode rows up to 128 costs nothing a launch can see
TOWER_ROW_MULTIPLE = 128


def tower_rows(q_rows: int, num_slots: int, chunk_budget: int,
               decode_rows: int = 1, *, block_q: int = BLOCK_Q) -> int:
    """``R(Q)``: the rows the step program of ``q_rows`` KERNEL rows runs
    its tower on (module doc, Layout contract) — a pure function of the
    program's ``Q`` and of what an engine knows when it is built, so a
    ``(Q, T)`` program has ONE shape and ``R`` is no bucket of its own.
    With ``decode_rows`` the real rows a decode slot holds at most (1, or
    2 B under block generation, or the candidates a speculating slot
    verifies):

    * a bucket that holds no more than every slot's padded decode rows
      (``Q <= num_slots`` q blocks of a slot) is the plain launch's: the
      slots' real decode rows;
    * a bucket that holds them AND a whole chunk budget beside them is
      the steady chunk launch's: the decode rows and the budget;
    * a bucket between the two serves the mixes of a part-filled engine
      (a ramp's chunk beside few decode rows, the tail of a prompt) and
      keeps one axis: ``Q``.

    Rounded up to ``TOWER_ROW_MULTIPLE``, never past ``Q``; where the
    result is ``Q`` the program is the padded one (a launch of blocks of
    8 rows a slot fills its q blocks). A launch whose real rows pass
    ``R`` of the smallest bucket its padded rows fit goes to the next
    bucket up (``serving/engine.py:_launch_bucket``): a chunk with few
    decode rows beside it, which pays pad q blocks the kernel skips."""
    Q, S, d = int(q_rows), int(num_slots), int(decode_rows)
    slots_rows = S * -(-d // block_q) * block_q     # padded, every slot
    if Q <= slots_rows:
        rows = S * d
    elif Q >= slots_rows + int(chunk_budget):
        rows = S * d + int(chunk_budget)
    else:
        return Q
    m = TOWER_ROW_MULTIPLE
    return min(Q, -(-rows // m) * m)


def ragged_layout(q_lens: Sequence[int], pos0s: Sequence[int], *,
                  block_q: int = BLOCK_Q,
                  q_bucket: int = 0) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray, np.ndarray, int]:
    """Host-side row layout of a ragged batch (numpy, scheduler thread).

    ``q_lens[s]`` query rows for sequence ``s`` (0 = absent this
    launch), first token at virtual position ``pos0s[s]``. Each present
    sequence's rows are laid out contiguously and padded to a multiple
    of ``block_q`` so no q block straddles sequences.

    Returns ``(blk_seq, seq_qstart, seq_pos0, last_row, total_rows)``:
    ``blk_seq [q_bucket / block_q]`` int32 (−1 pads), ``seq_qstart`` /
    ``seq_pos0`` ``[S]`` int32, ``last_row [S]`` int32 (flattened row of
    each present sequence's LAST real token; 0 for absent sequences —
    its logits row is garbage the caller ignores), and the unpadded
    ``total_rows``. ``q_bucket`` (a multiple of ``block_q``) fixes the
    padded width; 0 sizes it to the content.

    Every row index here is a KERNEL row (the ``Q`` axis). A step whose
    tower runs on fewer rows (:func:`tower_rows`) has its sequences'
    real rows back to back: sequence ``s`` starts at ``sum(q_lens[:s])``
    and its last real token sits ``q_lens[s] - 1`` after that — the
    engine lays the per-row operands out so, and the step derives the
    same starts on the device from ``kv_len - seq_pos0``.
    """
    S = len(q_lens)
    if len(pos0s) != S:
        raise ValueError(f"q_lens/pos0s length mismatch: {S} vs "
                         f"{len(pos0s)}")
    rows_padded = sum(-(-int(n) // block_q) * block_q
                      for n in q_lens if n > 0)
    if q_bucket:
        if q_bucket % block_q:
            raise ValueError(
                f"q_bucket {q_bucket} must be a multiple of block_q "
                f"{block_q}")
        if q_bucket < rows_padded:
            raise ValueError(
                f"q_bucket {q_bucket} cannot hold {rows_padded} padded "
                f"rows")
    else:
        q_bucket = max(rows_padded, block_q)
    blk_seq = np.full(q_bucket // block_q, -1, np.int32)
    seq_qstart = np.zeros(S, np.int32)
    seq_pos0 = np.zeros(S, np.int32)
    last_row = np.zeros(S, np.int32)
    cursor = 0
    total = 0
    for s, n in enumerate(q_lens):
        n = int(n)
        if n <= 0:
            continue
        nblk = -(-n // block_q)
        seq_qstart[s] = cursor
        seq_pos0[s] = int(pos0s[s])
        last_row[s] = cursor + n - 1
        blk_seq[cursor // block_q: cursor // block_q + nblk] = s
        cursor += nblk * block_q
        total += n
    return blk_seq, seq_qstart, seq_pos0, last_row, total


def reference_ragged_attention(q_rows, pool, layer, row_seq, row_pos,
                               tables, lo, scale=None, scales=None,
                               mask_block=1, kv_len=None, window=0,
                               sinks=None, v_lanes=0):
    """Numpy oracle for the kernel (tests): per-row full-precision
    softmax attention over the row's ``[lo, pos]`` window gathered
    through the page table. ``q_rows [N, H, Dh]``, ``row_seq/row_pos
    [N]``; ``scales`` dequantizes an int8 pool (per-block max-abs,
    the kernel's in-register multiply done up front). Query head ``j``
    reads the pool's KV head ``j // (H / Hkv)``; with ``mask_block`` B the
    window ends at the row's block's last column, bounded by
    ``kv_len[seq]``. With ``window`` W the row sees its last W columns
    only, with ``sinks [H]`` a head's logit joins the denominator, with
    ``v_lanes`` V is the last ``v_lanes`` lanes of a row and K the first
    ``Dh``. Returns ``[N, H, Dv]``."""
    q_rows = np.asarray(q_rows, np.float32)
    n, h, dh = q_rows.shape
    dv = int(v_lanes) or dh
    # [L, NB+1, H, bs, lanes] -> K and V planes [L, NB+1, H, bs, Dk | Dv]
    pool = np.asarray(pool, np.float32)
    planes = [pool[..., :dh], pool[..., pool.shape[-1] - dv:]]
    if scales is not None:
        planes = [pl_ * np.asarray(scales, np.float32)[:, i, ..., None, None]
                  for i, pl_ in enumerate(planes)]
    bs = pool.shape[3]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    out = np.zeros((n, h, dv), np.float32)
    g = h // pool.shape[2]
    B = int(mask_block)
    for i in range(n):
        s = int(row_seq[i])
        p = int(row_pos[i])
        if B > 1:
            p = min(p // B * B + B - 1, int(kv_len[s]) - 1)
        first = int(lo[s])
        if window:
            first = max(first, int(row_pos[i]) - int(window) + 1)
        cols = np.arange(first, p + 1)
        k = np.stack([planes[0][layer, tables[s][c // bs], :, c % bs, :]
                      for c in cols])                    # [ctx, H, Dh]
        v = np.stack([planes[1][layer, tables[s][c // bs], :, c % bs, :]
                      for c in cols])
        for hh in range(h):
            logits = (k[:, hh // g] @ q_rows[i, hh]) * scale
            m = logits.max() if sinks is None \
                else max(logits.max(), float(sinks[hh]))
            w = np.exp(logits - m)
            w /= w.sum() + (0.0 if sinks is None
                            else np.exp(float(sinks[hh]) - m))
            out[i, hh] = w @ v[:, hh // g]
    return out
