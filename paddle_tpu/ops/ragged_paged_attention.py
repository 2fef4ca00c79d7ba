"""Ragged paged attention — the fused Pallas TPU serving kernel.

The gather-based paged decode step (models/generation.py
``build_paged_decode_fn``) materializes ``pool[li, :, tables]`` per
layer: every request's WHOLE KV window is copied out of the block pool
on every decode step, and attention then runs over the padded
``table_bucket * block_size`` columns for every slot. This kernel is
the TPU-native replacement per "Ragged Paged Attention" (PAPERS.md):
the block pool stays in HBM (``memory_space=pl.ANY``), the kernel walks
each sequence's page table directly — one async DMA per (KV block,
head) into VMEM scratch — and streams online softmax over exactly the
blocks a sequence owns. Nothing is gathered, nothing is padded to the
table bucket, and a single launch serves a RAGGED batch of mixed
prefill-chunk and decode rows (the chunked-prefill unlock).

Layout contract (the serving engine's fused step builds these):

* queries are FLATTENED over the batch: each sequence's ``q_len[s]``
  rows sit contiguously, padded up to a multiple of ``block_q`` (8, the
  fp32 sublane) so one grid step never mixes sequences — decode rows
  cost one padded q block, prefill chunks amortize theirs;
* scalar-prefetch metadata maps grid steps back to sequences:
  ``blk_seq`` names the sequence of each q block (−1 = pad block),
  ``seq_qstart``/``seq_pos0`` recover every row's virtual cache
  position, ``tables`` is the page table, ``kv_len`` bounds the KV walk
  and ``lo`` the valid-window floor (always 0 for paged sequences);
* a row at position ``p`` attends to cache columns ``[lo, p]`` — the
  history PLUS the causal prefix of its own chunk, whose K/V the fused
  step scatters into the pool before the kernel runs.

Pool layout: ``[L, NB + 1, H, block_size, 2 * Dh]`` — one block of one
head is a ``(block_size, 2 * Dh)`` tile whose lanes hold K in
``[0, Dh)`` and V in ``[Dh, 2 * Dh)``. HBM arrays are tiled ``(sublane,
128)`` on their two minor dims and a DMA slice must cover whole tiles:
a ``(block_size, Dh)`` tile at ``Dh = 64`` is refused by Mosaic ("slice
shape must be aligned to tiling (128)") and padded 2x in HBM, while K|V
folded into the lanes is exactly 128 wide at ``Dh = 64`` (and 256 at
``Dh = 128``). One DMA per (block, head) brings both K and V. Everything
that touches the pool — ``serving/paging.py``, the gather decode path,
``serving/host_tier.py``, the int8 scales, the head-partitioned TP
shard — reads this one layout.

Mosaic legality (enforced by the ``pallas-block-tiling`` self-lint):
q/o blocks are ``(1, block_q, Dh)`` with ``block_q = 8`` sublane-aligned
and ``Dh`` the full array dim; the KV scratch is ``(block_size, 2 * Dh)``
with ``block_size`` at least the storage dtype's sublane count and, on
a TPU, ``2 * Dh`` a multiple of 128.

Off-TPU the kernel runs in interpret mode — that is how the tier-1
parity suite (tests/test_ragged_attention.py) executes the kernel body
on CPU.

Tensor-parallel use (ISSUE 15): the kernel is head-count agnostic —
its grid is per-(q block, head), so the sharded serving step
(``build_sharded_fused_step_fn``) simply launches it inside a
``shard_map`` with the LOCAL head count ``H/mp`` against each device's
own pool shard ``[L, blocks, H/mp, bs, 2 * Dh]``. No kernel change: the
page tables and scalar-prefetch metadata are replicated (block indices
are shard-invariant), the per-head outputs are partial sums of the
attention projection, and one downstream ``psum`` joins them.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret, _x64_off

__all__ = ["ragged_paged_attention", "ragged_layout", "BLOCK_Q",
           "MIN_KV_BLOCK", "min_kv_block_for", "check_kv_tile"]

_NEG_INF = -1e30

# q rows per grid step: the fp32 sublane count — the smallest
# Mosaic-legal second-to-last block dim, so a decode row (1 real query)
# wastes at most 7 pad rows while a prefill chunk fills whole blocks
BLOCK_Q = 8

# the KV scratch block is (block_size, 2 * Dh): block_size below the
# sublane count has no legal TPU layout
MIN_KV_BLOCK = 8

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


def check_kv_tile(dtype, block_size: int, head_dim: int) -> None:
    """Raise ``ValueError`` unless one (block, head) tile of the pool —
    ``(block_size, 2 * head_dim)`` of ``dtype`` — is something the
    kernel can DMA: at least the dtype's sublane count tall and, on a
    TPU, whole 128-lane tiles wide. The ONE statement of the rule, for
    the kernel and for the engine's constructor."""
    name = jnp.dtype(dtype).name
    need = min_kv_block_for(dtype)
    if int(block_size) < need:
        raise ValueError(
            f"block_size {block_size} < {need}: the {name} KV block tile "
            f"has no legal (sublane, 128) TPU tiling below the dtype's "
            f"sublane count")
    if not _interpret() and (2 * int(head_dim)) % 128:
        raise ValueError(
            f"head_dim {head_dim}: the K|V block tile is "
            f"{2 * int(head_dim)} lanes wide, and a TPU DMA slice must "
            f"cover whole 128-lane tiles")


def _rpa_kernel(blk_seq_ref, qstart_ref, pos0_ref, tables_ref, lo_ref,
                kvlen_ref, *rest, layer, block_q, block_size, scale,
                quantized=False):
    """One (head, q-block) grid step: walk the owning sequence's page
    table, DMA each KV block HBM→VMEM, stream online softmax.

    Quantized pools (int8 blocks) ride a 7th scalar-prefetch operand:
    THIS layer's per-block max-abs scale slice ``[2, NB + 1, H]`` f32 —
    each DMA'd block is dequantized IN-REGISTER (one scalar multiply
    per (block, head) after the VMEM read), so the HBM traffic stays at
    the narrow storage width and nothing quantized ever reaches the
    MXU.

    i32-typed constants: bare python ints in kernel index math get
    materialized as i64 by Mosaic under the framework's global x64 (the
    pallas_kernels idiom; the call sites also trace under _x64_off)."""
    if quantized:
        scales_ref, q_ref, pool_ref, o_ref, kv_scr, kv_sem = rest
    else:
        scales_ref = None
        q_ref, pool_ref, o_ref, kv_scr, kv_sem = rest
    h = pl.program_id(0)
    b = pl.program_id(1)
    seq = blk_seq_ref[b]

    @pl.when(seq < 0)
    def _pad_block():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(seq >= 0)
    def _attend():
        _BS = jnp.int32(block_size)
        _BQ = jnp.int32(block_q)
        q = q_ref[0]                                    # [bq, Dh]
        bq, dh = q.shape
        # virtual cache position of each row: rows of a sequence are
        # consecutive tokens starting at seq_pos0 (pad rows past the
        # real q_len compute masked garbage nobody reads)
        row0 = b * _BQ - qstart_ref[seq]
        qpos = pos0_ref[seq] + row0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)                 # [bq, 1]
        lo = lo_ref[seq]
        n_kv = (kvlen_ref[seq] + _BS - 1) // _BS

        def body(j, carry):
            # running softmax stats stay 2D [bq, 1] (sublane-oriented);
            # rank-1 carries would force lane<->sublane relayouts
            m_prev, l_prev, acc = carry
            pid = tables_ref[seq, j]
            # the page-table walk: this sequence's j-th block, this
            # head, K|V in one (bs, 2*Dh) tile copied HBM -> VMEM — the
            # ONLY KV bytes this grid step touches (the gather path
            # would have materialized the whole padded table bucket for
            # every slot)
            cp = pltpu.make_async_copy(
                pool_ref.at[layer, pid, h], kv_scr, kv_sem)
            cp.start()
            cp.wait()
            k_blk = kv_scr[:, :dh]                      # [bs, Dh]
            v_blk = kv_scr[:, dh:]
            if quantized:
                # in-register dequant: the per-(block, head) max-abs
                # scale rides the scalar-prefetch metadata; HBM moved
                # int8, compute sees floats. scales_ref is THIS
                # layer's [2, NB+1, H] slice — prefetching all L
                # layers' scales into SMEM would waste an L-fold
                # bigger scalar-memory footprint per launch
                k_blk = (k_blk.astype(jnp.float32)
                         * scales_ref[0, pid, h]).astype(q.dtype)
                v_blk = (v_blk.astype(jnp.float32)
                         * scales_ref[1, pid, h]).astype(q.dtype)
            # operands in storage dtype, f32 accumulation (MXU contract
            # shared with the flash kernels)
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [bq, bs]
            cols = j * _BS + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_size), 1)
            # f32-typed fill: a bare python float is weak f64 under the
            # framework's global x64
            s = jnp.where((cols >= lo) & (cols <= qpos), s,
                          jnp.float32(_NEG_INF))
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc0 = jnp.zeros((block_q, dh), jnp.float32)
        # i32 bounds: a bare python 0 becomes an i64 induction variable
        # under the framework's global x64, and the interpret-mode body
        # trace happens outside the call site's _x64_off scope
        _, l, acc = jax.lax.fori_loop(jnp.int32(0), n_kv, body,
                                      (m0, l0, acc0))
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def ragged_paged_attention(q, pool, layer, blk_seq, seq_qstart, seq_pos0,
                           tables, lo, kv_len, *, scales=None, scale=None,
                           block_q: int = BLOCK_Q):
    """Fused paged attention over one layer of the serving block pool.

    * ``q`` — ``[H, Qp, Dh]`` flattened padded query rows (``Qp`` a
      multiple of ``block_q``; per-sequence contiguous, see module doc);
    * ``pool`` — the FULL block pool ``[L, NB + 1, H, bs, 2 * Dh]``
      (K|V folded into the lanes, see module doc); it stays in HBM
      (``memory_space=pl.ANY``) and ``layer`` is a static int, so no
      per-layer slice is ever materialized;
    * ``blk_seq [Qp / block_q]``, ``seq_qstart [S]``, ``seq_pos0 [S]``,
      ``tables [S, T]``, ``lo [S]``, ``kv_len [S]`` — int32
      scalar-prefetch metadata (``ragged_layout`` builds the first
      three);
    * ``scales`` — REQUIRED for quantized pools (int8/fp8 storage):
      the per-block max-abs scale array ``[L, 2, NB + 1, H]`` f32,
      riding the scalar-prefetch path into SMEM so each DMA'd block
      dequantizes in-register;
    * returns ``[H, Qp, Dh]`` in ``q``'s dtype.
    """
    h, qp, dh = q.shape
    L, nb1, hp, bs, dh2 = pool.shape
    quantized = pool.dtype.name in ("int8", "float8_e4m3fn")
    if (hp, dh2) != (h, 2 * dh):
        raise ValueError(
            f"pool heads/lanes {(hp, dh2)} != q heads / 2*head_dim "
            f"{(h, 2 * dh)}")
    check_kv_tile(pool.dtype, bs, dh)
    if qp % block_q:
        raise ValueError(
            f"padded q rows {qp} must be a multiple of block_q {block_q}")
    if quantized and scales is None:
        raise ValueError(
            f"a {pool.dtype.name} pool is quantized storage: pass the "
            f"per-block scale array (PagedKVPool.scales)")
    if scales is not None and tuple(scales.shape) != (L, 2, nb1, h):
        raise ValueError(
            f"scales shape {tuple(scales.shape)} != per-block layout "
            f"{(L, 2, nb1, h)}")
    if not 0 <= int(layer) < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    n_qblk = qp // block_q
    quant = scales is not None
    kernel = functools.partial(
        _rpa_kernel, layer=int(layer), block_q=int(block_q),
        block_size=int(bs), scale=scale, quantized=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 if quant else 6,
        grid=(h, n_qblk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda hh, b, *_: (hh, b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda hh, b, *_: (hh, b, 0)),
        scratch_shapes=[
            pltpu.VMEM((bs, dh2), pool.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    prefetch = [jnp.asarray(blk_seq, jnp.int32),
                jnp.asarray(seq_qstart, jnp.int32),
                jnp.asarray(seq_pos0, jnp.int32),
                jnp.asarray(tables, jnp.int32),
                jnp.asarray(lo, jnp.int32),
                jnp.asarray(kv_len, jnp.int32)]
    if quant:
        # only THIS layer's [2, NB+1, H] scale slice goes to SMEM
        prefetch.append(jnp.asarray(scales, jnp.float32)[int(layer)])
    with _x64_off():
        return pl.pallas_call(
            kernel,
            name="ragged_paged_attention",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((h, qp, dh), q.dtype),
            interpret=_interpret(),
        )(*prefetch, q, pool)


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
                               tables, lo, scale=None, scales=None):
    """Numpy oracle for the kernel (tests): per-row full-precision
    softmax attention over the row's ``[lo, pos]`` window gathered
    through the page table. ``q_rows [N, H, Dh]``, ``row_seq/row_pos
    [N]``; ``scales`` dequantizes an int8 pool (per-block max-abs,
    the kernel's in-register multiply done up front)."""
    q_rows = np.asarray(q_rows, np.float32)
    n, h, dh = q_rows.shape
    # [L, NB+1, H, bs, 2*Dh] -> K/V planes [L, 2, NB+1, H, bs, Dh]
    pool = np.asarray(pool, np.float32)
    pool = np.stack([pool[..., :dh], pool[..., dh:]], axis=1)
    if scales is not None:
        pool = pool * np.asarray(scales, np.float32)[..., None, None]
    bs = pool.shape[4]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    out = np.zeros_like(q_rows)
    for i in range(n):
        s = int(row_seq[i])
        p = int(row_pos[i])
        cols = np.arange(int(lo[s]), p + 1)
        k = np.stack([pool[layer, 0, tables[s][c // bs], :, c % bs, :]
                      for c in cols])                    # [ctx, H, Dh]
        v = np.stack([pool[layer, 1, tables[s][c // bs], :, c % bs, :]
                      for c in cols])
        for hh in range(h):
            logits = (k[:, hh] @ q_rows[i, hh]) * scale
            w = np.exp(logits - logits.max())
            w /= w.sum()
            out[i, hh] = w @ v[:, hh]
    return out
