"""Pallas TPU kernels — the CUDA-analog tier.

Reference analogs: paddle/fluid/operators/fused/fused_attention_op.cu,
fmha_ref.h (flash attention), fused_dropout_helper.h + layer_norm_kernel
(fused LN), operators/optimizers/adam_op (fused optimizer update).

Design: every kernel registers as an *override* of the generic lax op
(ops/registry.py:register_override) guarded by a predicate — on TPU with
supported shapes the Pallas kernel runs; anywhere else the lax composition
stands. On CPU the kernels execute in Pallas interpret mode, which is how
the parity tests run them (SURVEY §4 OpTest ≙ numpy-vs-kernel parity).

Enablement: FLAGS_use_pallas (default True). Forced interpret-mode selection
for tests: FLAGS_pallas_force (runs kernels even off-TPU, interpreted).
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework.flags import define_flag, flag_value
from .registry import register_op, register_override

define_flag("FLAGS_use_pallas", True,
            "use Pallas TPU kernels where registered")
define_flag("FLAGS_pallas_force", False,
            "force-select Pallas kernels off-TPU (interpret mode, tests)")

logger = logging.getLogger(__name__)

_NEG_INF = -1e30


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _interpret() -> bool:
    # off-TPU the kernels can only run interpreted (tests)
    return not _on_tpu()


def _pallas_enabled() -> bool:
    if not flag_value("FLAGS_use_pallas"):
        return False
    return _on_tpu() or flag_value("FLAGS_pallas_force")


def _shape_of(x):
    return tuple(getattr(x, "shape", ()))


def _dtype_of(x):
    return getattr(x, "dtype", "float32")


def _x64_off():
    """Trace-scope guard: the framework enables jax x64 globally (reference
    parity for int64/float64 tensors), but under x64 Python-int constants
    inside kernel traces become int64 scalars that Mosaic cannot lower
    (infinite int64->int32 convert recursion / malformed mixed-type index
    arithmetic).  Every pallas_call invocation — which is when the kernel
    body is traced — runs under this x64-off scope; the surrounding jaxpr
    keeps its global setting."""
    return jax.enable_x64(False)


# ===========================================================================
# Flash attention (fwd + bwd), layout [B, S, H, D]
# ===========================================================================

# Of the STREAMING kernels, LSE (and the bwd delta) travel as [BH, S, LSE_LANES]
# fp32 with the value replicated across the trailing lane dim.  A plain
# [BH, S] layout with a (1, block_q) block violates the Mosaic tiling rule
# (second-to-last block dim must be divisible by 8 or equal the array dim)
# — a crash once recorded on hardware.  With a trailing
# LSE_LANES=8 dim, blocks are (1, block_q, 8): block_q is sublane-aligned
# and the last block dim equals the array dim, so the layout is legal on
# TPU at an 8x (not 128x) replication cost.
LSE_LANES = 8


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, scale, causal,
                   block_q, block_k, n_k):
    """One (q-block, k-block) tile of streaming flash attention.

    Grid (bh, nq, nk): the k dimension iterates INNERMOST and
    sequentially on a TPU core, so the online-softmax stats live in VMEM
    scratch across k steps — K/V stream through the grid in blocks and
    the kernel never maps the full sequence (the r3-v1 kernel's VMEM
    bound). i32-typed block-size constants: bare python ints in kernel
    index math get materialized as i64 by Mosaic.
    """
    _I32_BQ = jnp.int32(block_q)
    _I32_BK = jnp.int32(block_k)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: tiles strictly above the diagonal contribute nothing
    needed = True
    if causal:
        needed = kj * _I32_BK <= (qi + 1) * _I32_BQ - 1

    @pl.when(needed)
    def _update():
        # operands stay in their storage dtype (bf16 runs the MXU at native
        # rate); preferred_element_type=f32 keeps the ACCUMULATION in f32 —
        # upcasting operands first would force fp32-rate matmuls
        q = q_ref[0]                                  # [bq, D]
        bq, d = q.shape
        k_blk = k_ref[0]                              # [bk, D]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        if causal:
            rows = qi * _I32_BQ + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            cols = kj * _I32_BK + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)         # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == n_k - 1)
    def _finalize():
        bq = acc_scr.shape[0]
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m_scr[...] + jnp.log(l_safe), (bq, LSE_LANES))


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_scr, *, scale, causal, block_q, block_k, n_k):
    _I32_BQ = jnp.int32(block_q)
    _I32_BK = jnp.int32(block_k)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    needed = True
    if causal:
        needed = kj * _I32_BK <= (qi + 1) * _I32_BQ - 1

    @pl.when(needed)
    def _update():
        # native-dtype operands + f32 accumulation (see fwd kernel note)
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                        # [bq, 1] of [bq, 8]
        delta = delta_ref[0][:, :1]
        bq, d = q.shape
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * _I32_BQ + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            cols = kj * _I32_BK + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                   block_q, block_k, n_q):
    _I32_BQ = jnp.int32(block_q)
    _I32_BK = jnp.int32(block_k)
    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    needed = True
    if causal:
        # rows >= cols somewhere in the tile: last row of this q block
        # must reach the first col of this k block
        needed = (qj + 1) * _I32_BQ - 1 >= ki * _I32_BK

    @pl.when(needed)
    def _update():
        # native-dtype operands + f32 accumulation (see fwd kernel note)
        k = k_ref[0]                                  # [bk, D]
        v = v_ref[0]
        bk, d = k.shape
        q_blk = q_ref[0]                              # [bq, D]
        do_blk = do_ref[0]
        lse_blk = lse_ref[0][:, :1]                   # [bq, 1]
        delta_blk = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            rows = qj * _I32_BQ + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            cols = ki * _I32_BK + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_blk)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk) * scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qj == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fa_call_fwd(q, k, v, scale, causal, block_q, block_k):
    """q,k,v: [BH, S, D] -> (o [BH, Sq, D], lse [BH, Sq, LSE_LANES])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k
    kernel = functools.partial(
        _fa_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=nk)
    with _x64_off():
        return pl.pallas_call(
            kernel,
            name="flash_attention_fwd",
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, LSE_LANES),
                             lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sq, LSE_LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            interpret=_interpret(),
        )(q, k, v)


def _fa_call_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [BH, Sq, 1]
    delta = jnp.broadcast_to(delta, (bh, sq, LSE_LANES))
    with _x64_off():
        dq = pl.pallas_call(
            functools.partial(_fa_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              n_k=sk // block_k),
            name="flash_attention_dq",
            grid=(bh, sq // block_q, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, LSE_LANES),
                             lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, LSE_LANES),
                             lambda b, i, j: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=_interpret(),
        )(q, k, v, do, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_fa_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              n_q=sq // block_q),
            name="flash_attention_dkv",
            grid=(bh, sk // block_k, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_q, LSE_LANES),
                             lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_q, LSE_LANES),
                             lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            interpret=_interpret(),
        )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# The RESIDENT family: a head's whole K/V (forward) or Q/dO (backward) sits
# in VMEM and one grid step walks it in wide tiles.  It works on the
# layout the model already has, [B, S, H*D]: a 128-lane block of it IS
# ``128 // D`` heads, so a step holds both heads of a pair at D = 64 and
# no transpose surrounds the call.  The heads of a step are STACKED AS
# ROWS: head h's copy of an operand keeps h's lanes and zeros the others
# (``_stack_heads``), the MXU contracts all 128 lanes and the zeros add
# exactly nothing — so the heads' products stay separate while one
# product serves them all, on dense loads and dense accumulators.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _pow2(x):
    """x * bf16 is exact: the scale may be folded into an operand."""
    return math.frexp(x)[0] == 0.5


def _head_masks(lanes, d):
    """One [1, lanes] mask a head of the step; None where it holds one."""
    if lanes == d:
        return [None]
    head = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // d
    return [head == h for h in range(lanes // d)]


def _stack_heads(x, masks):
    """[rows, lanes] -> [heads * rows, lanes]: head h's rows hold x on
    h's lanes and zeros on the others'."""
    if masks[0] is None:
        return x
    return jnp.concatenate(
        [jnp.where(m, x, jnp.zeros_like(x)) for m in masks], axis=0)


def _head_rows(x, heads):
    """The row blocks of a head-stacked [heads * rows, n] value."""
    rows = x.shape[0] // heads
    return [x[h * rows:(h + 1) * rows] for h in range(heads)]


def _to_lanes(stat, masks, lanes):
    """A head-stacked statistic [heads * rows, w] (a row's value on every
    lane) -> [rows, lanes] with each head's value on its own lanes."""
    cols = _head_rows(stat, len(masks))
    if stat.shape[1] != lanes:
        cols = [c[:, :1] for c in cols]
    out = cols[-1]
    for col, mask in zip(cols[-2::-1], masks[-2::-1]):
        out = jnp.where(mask, col, out)
    return out


def _lane_chunks(x, w):
    return [x[:, c * w:(c + 1) * w] for c in range(x.shape[1] // w)]


def _walk(step, lo, hi, masked):
    """``step(j, masked)`` for j in [lo, hi): the state lives in scratch."""
    def body(j, carry):
        step(j, masked)
        return carry
    jax.lax.fori_loop(lo, hi, body, 0)


def _fa_fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, v_scr,
                            m_scr, l_scr, acc_scr, *, scale, causal,
                            block_k, d):
    """One q block of ``lanes // d`` heads against their resident K/V.
    Key blocks wholly under the diagonal take no mask; only the block(s)
    the diagonal crosses build one.  The statistics are kept a full vreg
    wide: the row max is one cross-lane reduction a key block (after an
    elementwise max over its 128-lane chunks) and the row sum stays in
    per-lane partials until the q block ends."""
    qi = pl.program_id(2)
    bq, lanes = q_ref.shape[1:]
    heads = lanes // d
    nk = k_ref.shape[1] // block_k
    w = m_scr.shape[1]
    masks = _head_masks(lanes, d)
    fold = _pow2(scale)
    _I32_BK = jnp.int32(block_k)

    @pl.when(qi == 0)
    def _stack_v():          # once a (batch, head group)
        v_scr[...] = _stack_heads(v_ref[0], masks)

    q = q_ref[0]
    q_st = _stack_heads(q * scale if fold else q, masks)    # [R, lanes]
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(j, masked):
        k0 = pl.multiple_of(j * _I32_BK, block_k) if nk > 1 else 0
        s = jax.lax.dot_general(q_st, k_ref[0, pl.ds(k0, block_k), :], _NT,
                                preferred_element_type=jnp.float32)
        if not fold:
            s = s * scale
        if masked:
            rows = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            cols = k0 + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            seen = rows >= cols
            s = jnp.concatenate([jnp.where(seen, s_h, _NEG_INF)
                                 for s_h in _head_rows(s, heads)], axis=0)
        chunks = _lane_chunks(s, w)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(
            functools.reduce(jnp.maximum, chunks), axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = [jnp.exp(c - m_new) for c in chunks]
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + functools.reduce(jnp.add, p)
        # the heads' row blocks side by side: [bq, heads * block_k]
        # against V stacked by head, one product for every head
        p = [_head_rows(c, heads) for c in p]
        p = jnp.concatenate([c[h] for h in range(heads) for c in p], axis=1)
        v_st = jnp.concatenate(
            [v_scr[pl.ds(pl.multiple_of(h * k_ref.shape[1] + k0, block_k),
                         block_k), :] for h in range(heads)], axis=0)
        pv = jax.lax.dot_general(p.astype(v_st.dtype), v_st, _NN,
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _to_lanes(alpha, masks, lanes) + pv

    if nk == 1:              # one block: nothing to walk, no dynamic slice
        step(0, causal)
    elif causal:
        n_clear = jnp.minimum((qi * bq + 1) // block_k, nk)
        n_seen = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, nk)
        _walk(step, 0, n_clear, False)
        _walk(step, n_clear, n_seen, True)
    else:
        _walk(step, 0, nk, False)
    l_safe = jnp.maximum(jnp.sum(l_scr[...], axis=-1, keepdims=True), 1e-30)
    o_ref[0] = (acc_scr[...] / _to_lanes(l_safe, masks, lanes)
                ).astype(o_ref.dtype)
    # lse leaves as ROWS [heads, bq] (the backward wants it along lanes):
    # one transpose of a [bq, 128] tile a q block
    lse = m_scr[...] + jnp.log(l_safe)
    lse_t = jnp.broadcast_to(_to_lanes(lse, masks, 128), (bq, 128)).T
    for h in range(heads):
        lse_ref[0, 0, h:h + 1, :] = lse_t[h * d:h * d + 1, :]


def _fa_bwd_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                            *, scale, causal, block_q, d):
    """dq, dk and dv in ONE pass: a key block (grid, outer) against the
    resident q blocks (loop, inner), scores TRANSPOSED ([keys, queries]:
    lse and delta broadcast along sublanes, dv and dk are plain products)
    — S, P and dP are computed once a pair.  A head's dq gathers in
    float32 scratch over the key blocks."""
    ki = pl.program_id(2)
    bk, lanes = k_ref.shape[1:]
    heads = lanes // d
    nq = q_ref.shape[1] // block_q
    masks = _head_masks(lanes, d)
    fold = _pow2(scale)
    k = k_ref[0]
    k_st = _stack_heads(k * scale if fold else k, masks)   # [R, lanes]
    v_st = _stack_heads(v_ref[0], masks)
    _I32_BQ = jnp.int32(block_q)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(j, masked):
        q0 = pl.multiple_of(j * _I32_BQ, block_q) if nq > 1 else 0
        q_blk = q_ref[0, pl.ds(q0, block_q), :]
        do_blk = do_ref[0, pl.ds(q0, block_q), :]
        s_t = jax.lax.dot_general(k_st, q_blk, _NT,
                                  preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v_st, do_blk, _NT,
                                   preferred_element_type=jnp.float32)
        if masked:
            keys = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, block_q), 0)
            rows = q0 + jax.lax.broadcasted_iota(
                jnp.int32, (bk, block_q), 1)
            seen = rows >= keys
        p_t, ds_t = [], []
        for h, (s_h, dp_h) in enumerate(zip(_head_rows(s_t, heads),
                                            _head_rows(dp_t, heads))):
            lse = lse_ref[0, 0, h:h + 1, pl.ds(q0, block_q)]    # [1, bq]
            delta = delta_ref[0, 0, h:h + 1, pl.ds(q0, block_q)]
            if not fold:
                s_h = s_h * scale
            if masked:
                s_h = jnp.where(seen, s_h, _NEG_INF)
            p_h = jnp.exp(s_h - lse)                             # [bk, bq]
            ds_h = p_h * (dp_h - delta)
            if not fold:
                ds_h = ds_h * scale
            p_t.append(p_h.astype(do_blk.dtype))
            ds_t.append(ds_h.astype(q_blk.dtype))
        # [bk, heads * bq] against dO / Q stacked by head
        dv_scr[...] += jax.lax.dot_general(
            jnp.concatenate(p_t, axis=1), _stack_heads(do_blk, masks), _NN,
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            jnp.concatenate(ds_t, axis=1), _stack_heads(q_blk, masks), _NN,
            preferred_element_type=jnp.float32)
        dq_scr[pl.ds(q0, block_q), :] += jax.lax.dot_general(
            jnp.concatenate(ds_t, axis=0), k_st, _TN,
            preferred_element_type=jnp.float32)

    if nq == 1:              # one block: nothing to walk, no dynamic slice
        step(0, causal)
    elif causal:
        first = (ki * bk) // block_q
        n_cut = jnp.minimum(((ki + 1) * bk - 1 + block_q - 1) // block_q, nq)
        _walk(step, first, n_cut, True)
        _walk(step, n_cut, nq, False)
    else:
        _walk(step, 0, nq, False)
    # with the scale folded into k, ds was left unscaled: dq took the
    # scale through k_st, dk takes it here
    dk_ref[0] = (dk_scr[...] * scale if fold else dk_scr[...]
                 ).astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# a step's tiles are its own to size: [512, 512] float32 scores and their
# exponentials are megabytes, past the 16 MB Mosaic scopes by default on a
# v5e of 128 MB
_RESIDENT_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 * 2 ** 20)


def _fa_call_fwd_resident(q, k, v, scale, causal, block_q, block_k, d):
    """q, k, v: [B, S, lanes_all] (heads of d side by side in the lanes)
    -> (o like q, lse [B, groups, heads a step, Sq] float32)."""
    b, sq, width = q.shape
    sk = k.shape[1]
    lanes = _step_lanes(width, d)
    groups, hp = width // lanes, lanes // d
    w = 128 if block_k % 128 == 0 else block_k
    kernel = functools.partial(
        _fa_fwd_kernel_resident, scale=scale, causal=causal,
        block_k=block_k, d=d)
    with _x64_off():
        return pl.pallas_call(
            kernel,
            name="flash_attention_fwd",
            grid=(b, groups, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, lanes), lambda b, g, i: (b, i, g)),
                pl.BlockSpec((1, sk, lanes), lambda b, g, i: (b, 0, g)),
                pl.BlockSpec((1, sk, lanes), lambda b, g, i: (b, 0, g)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, lanes), lambda b, g, i: (b, i, g)),
                pl.BlockSpec((1, 1, hp, block_q),
                             lambda b, g, i: (b, g, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, sq, width), q.dtype),
                jax.ShapeDtypeStruct((b, groups, hp, sq), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((hp * sk, lanes), v.dtype),
                pltpu.VMEM((hp * block_q, w), jnp.float32),
                pltpu.VMEM((hp * block_q, w), jnp.float32),
                pltpu.VMEM((block_q, lanes), jnp.float32),
            ],
            compiler_params=_RESIDENT_PARAMS,
            interpret=_interpret(),
        )(q, k, v)


def _fa_call_bwd_resident(q, k, v, o, lse, do, scale, causal, block_q,
                          block_k, d):
    b, sq, width = q.shape
    sk = k.shape[1]
    lanes = _step_lanes(width, d)
    groups, hp = width // lanes, lanes // d
    # delta from the very bf16 o and dO the kernel reads: unfenced, XLA
    # fuses this product into the matmul that makes dO and takes its
    # value BEFORE the rounding — rows of dS then sum to the rounding of
    # dO, not to zero (the key bias's zero gradient read a quarter more
    # residue on the chip, PERF.md 39.4)
    o, do = jax.lax.optimization_barrier((o, do))
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, sq, groups, hp, d), axis=-1)
    delta = delta.transpose(0, 2, 3, 1)             # [B, groups, hp, Sq]
    whole = pl.BlockSpec((1, sq, lanes), lambda b, g, j: (b, 0, g))
    block = pl.BlockSpec((1, block_k, lanes), lambda b, g, j: (b, j, g))
    rows = pl.BlockSpec((1, 1, hp, sq), lambda b, g, j: (b, g, 0, 0))
    with _x64_off():
        return pl.pallas_call(
            functools.partial(_fa_bwd_kernel_resident, scale=scale,
                              causal=causal, block_q=block_q, d=d),
            name="flash_attention_bwd",
            grid=(b, groups, sk // block_k),
            in_specs=[whole, block, block, whole, rows, rows],
            out_specs=[whole, block, block],
            out_shape=[
                jax.ShapeDtypeStruct((b, sq, width), q.dtype),
                jax.ShapeDtypeStruct((b, sk, width), k.dtype),
                jax.ShapeDtypeStruct((b, sk, width), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((sq, lanes), jnp.float32),
                            pltpu.VMEM((block_k, lanes), jnp.float32),
                            pltpu.VMEM((block_k, lanes), jnp.float32)],
            compiler_params=_RESIDENT_PARAMS,
            interpret=_interpret(),
        )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# What ONE grid step holds, decided in one place from what can be seen of
# the call.  The RESIDENT family needs (sq + sk) * d inside its budget;
# past it the STREAMING kernels above block K/V through a 3-D grid with
# scratch carries, one head a step on [B*H, S, D], and have no sequence
# cap (32k tested on hardware).  At [16, 1024, 12, 64] bf16 causal, both
# at their best tiles, the streamed family takes 4.80 ms forward +
# backward where the resident one takes 1.87 (v5e, PR 39): both stay.
# ---------------------------------------------------------------------------

_RESIDENT_VMEM_ELEMS = 1_500_000  # (sq + sk) * d
_MIN_BLOCK = 128


def _use_resident(sq, sk, d):
    return (sq + sk) * d <= _RESIDENT_VMEM_ELEMS


def _step_lanes(width, d):
    """Lanes of one grid step out of ``width = heads * d``: 128 // d
    heads side by side where they fill a 128-lane block, else one head."""
    return 128 if d < 128 and 128 % d == 0 and width % 128 == 0 else d


def _wide_block(seq, widest):
    """The widest power-of-two tile that divides ``seq`` and leaves it at
    least two blocks (a causal walk still skips, the pipeline still has a
    next step); a short or odd length keeps the 128 every shape had."""
    block = widest
    while block > _MIN_BLOCK and (seq % block or block * 2 > seq):
        block //= 2
    return min(block, seq)


def flash_attention_plan(sq, sk, d, heads, causal=False, dtype="bfloat16"):
    """The plan of one flash-attention call, a plain dict: tile sizes of
    the forward and the backward, heads a grid step, whether K/V stay
    resident, and whether the call works on [B, S, H*D] as it stands
    (``packed``) or on a transposed [B*H, S, D]."""
    del causal, dtype         # seen, and so far not what decides
    resident = _use_resident(sq, sk, d)
    lanes = _step_lanes(heads * d, d) if resident else d
    packed = resident and (lanes % 128 == 0 or heads == 1)
    wide_q, wide_k = _WIDE_FWD if resident else _WIDE_STREAMED
    bwd_q, bwd_k = _WIDE_BWD if resident else _WIDE_STREAMED
    return {
        "block_q": _wide_block(sq, wide_q), "block_k": _wide_block(sk, wide_k),
        "bwd_block_q": _wide_block(sq, bwd_q),
        "bwd_block_k": _wide_block(sk, bwd_k),
        "heads_per_step": lanes // d if packed else 1,
        "resident": resident, "packed": packed}


# The widest tiles the plan hands out, (q, k), each the measured winner of
# one layer's call on a v5e (PR 39, PERF.md §6; ms forward | forward +
# backward).  [16, 1024, 12, 64] bf16 causal, forward of 256x256 0.93,
# 256x512 0.79, 512x256 0.84, 512x512 0.73; with it the one backward
# kernel at 128x256 2.59, 256x128 2.36, 256x512 2.04, 512x256 2.05,
# 512x512 2.02, 256x256 1.93.  The streamed family at [1, 16384, 12, 64]:
# 128x128 75 | 230, 256x256 31 | 95, 512x512 13.5 | 42.4.
_WIDE_FWD = (512, 512)
_WIDE_BWD = (256, 256)
_WIDE_STREAMED = (512, 512)


@functools.lru_cache(maxsize=None)
def _log_plan(sq, sk, d, heads, causal, dtype, blocks):
    """Once a shape, where its kernel is first built."""
    logger.info("flash_attention sq=%d sk=%d d=%d heads=%d causal=%s %s: "
                "blocks fwd/bwd %s of plan %s", sq, sk, d, heads, causal,
                dtype, blocks,
                flash_attention_plan(sq, sk, d, heads, causal, dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_core(q, k, v, scale, causal, blocks, d, resident):
    """q, k, v: [B', S, H' * d].  ``blocks`` = forward (q, k) + backward
    (q, k) tile sizes."""
    return _flash_fwd_rule(q, k, v, scale, causal, blocks, d, resident)[0]


def _flash_fwd_rule(q, k, v, scale, causal, blocks, d, resident):
    call = functools.partial(_fa_call_fwd_resident, d=d) if resident \
        else _fa_call_fwd
    o, lse = call(q, k, v, scale, causal, blocks[0], blocks[1])
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, blocks, d, resident, res, do):
    call = functools.partial(_fa_call_bwd_resident, d=d) if resident \
        else _fa_call_bwd
    return call(*res, do, scale, causal, blocks[2], blocks[3])


_flash_attention_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, is_causal=False, scale=None,
                    block_q=None, block_k=None):
    """Flash attention on [B, S, H, D] inputs (the framework's attention
    layout). Differentiable via the Pallas backward kernels.  The tiles
    are ``flash_attention_plan``'s unless ``block_q``/``block_k`` name
    them (the autotuner's candidates, a user's cache entry)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    plan = flash_attention_plan(sq, sk, d, h, is_causal, q.dtype)
    blocks = (plan["block_q"], plan["block_k"],
              plan["bwd_block_q"], plan["bwd_block_k"])
    named = (min(block_q or blocks[0], sq), min(block_k or blocks[1], sk))
    if named != blocks[:2]:
        blocks = named + named
    if any(sq % blk for blk in blocks[0::2]) or \
            any(sk % blk for blk in blocks[1::2]):
        raise ValueError(
            f"flash_attention needs seq lengths divisible by the block "
            f"sizes: sq={sq}, sk={sk}, blocks (q, k, bwd q, bwd k)={blocks}")
    _log_plan(sq, sk, d, h, bool(is_causal), str(q.dtype), blocks)
    args = (float(s), bool(is_causal), tuple(int(x) for x in blocks), d,
            plan["resident"])
    if plan["packed"]:
        # [B, S, H, D] -> [B, S, H*D]: a view, no copy
        o = _flash_attention_core(
            q.reshape(b, sq, h * d), k.reshape(b, sk, h * d),
            v.reshape(b, sk, h * d), *args)
        return o.reshape(b, sq, h, d)
    # heads that do not fill a lane block: one head a step on [B*H, S, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    o = _flash_attention_core(qt, kt, vt, *args)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


# Below this length the DEFAULT tier is XLA's own attention.  One layer's
# call, causal bf16, 12 heads of 64, forward + backward on a v5e (PR 39,
# PERF.md §6; ms flash | lax): [16, 1024] 1.87 | 9.06, [32, 512] 1.43 |
# 4.70, [64, 256] 1.73 | 2.32 (forward alone 0.80 | 0.74), [128, 128]
# 1.07 | 1.06 — a tie at 128, where the full S^2 matrix is tiny.  This
# heuristic is only the DEFAULT: the shape-class autotune cache
# (ops/autotune_cache.py) overrides it wherever a measured winner is
# recorded, and tune_attention() records winners per device kind.
FLASH_MIN_SEQ = 512


def _sdpa_key(b, h, sq, sk, d, dtype, is_causal):
    from . import autotune_cache as _at
    # tune=bwd2: key-format version. Pre-r5 entries were measured
    # fwd-only at default blocks; the r5 tuner measures fwd+bwd across
    # block configs — stale entries must miss, not veto the new search.
    return _at.shape_class(b * h, sq, sk, d, dtype=str(dtype),
                           causal=bool(is_causal), tune="bwd2")


def _fa_supported(q, k, v, mask, dropout_key, dropout_p, is_causal):
    qs, ks = _shape_of(q), _shape_of(k)
    if len(qs) != 4 or mask is not None or (dropout_p or 0.0) > 0.0:
        return False
    b, sq, h, d = qs
    sk = ks[1]
    if is_causal and sq != sk:
        return False
    # structural requirements first — an unlowrable shape never dispatches
    # to Pallas regardless of what the cache says.  There is no seq cap:
    # past the resident budget the streaming kernels hold only
    # (block_q + 2*block_k) x d tiles plus scratch, and long context is
    # bounded by HBM for Q/K/V themselves (e.g. 128k x 128 bf16 = 32MB
    # per head-batch).
    if not (sq % min(_MIN_BLOCK, sq) == 0 and sk % min(_MIN_BLOCK, sk) == 0
            and d <= 256 and sq >= 8 and sk >= 8):
        return False
    if flag_value("FLAGS_pallas_force"):
        return True
    from . import autotune_cache as _at
    default = "pallas" if max(sq, sk) >= FLASH_MIN_SEQ else "lax"
    choice = _at.choose("scaled_dot_product_attention",
                        _sdpa_key(b, h, sq, sk, d, _dtype_of(q),
                                  is_causal),
                        default=default)
    return choice.startswith("pallas")   # incl. "pallas:BQxBK" configs


# block-size search space for tune_attention beside the plan's own tiles
# (which keep the plain name ``pallas``). Unlowerable or non-dividing
# combos simply fail their measurement and never win.
_TUNE_BLOCKS = [(128, 128), (256, 256), (256, 512), (512, 512), (512, 1024)]


def tune_attention(q, a_k, v, is_causal=False, persist=True,
                   include_bwd=True, skip_if_cached=False):
    """Measure lax vs pallas (the plan's tiles and other block-size
    configs) for this shape class on CONCRETE arrays and record the
    winner in the autotune cache (the reference's warmup-step
    measurement, made explicit). With ``include_bwd`` the timed quantity
    is a full fwd+bwd — the training crossover, which is what the benches
    dispatch on. Returns the winning tier name (``lax``, ``pallas``, or
    ``pallas:BQxBK``)."""
    import jax.numpy as jnp

    from . import autotune_cache as _at
    from .registry import get_op

    q = jnp.asarray(q._data if hasattr(q, "_data") else q)
    a_k = jnp.asarray(a_k._data if hasattr(a_k, "_data") else a_k)
    v = jnp.asarray(v._data if hasattr(v, "_data") else v)
    b, sq, h, d = q.shape
    sk = a_k.shape[1]
    key = _sdpa_key(b, h, sq, sk, d, q.dtype, is_causal)
    if skip_if_cached:
        got = _at.choose("scaled_dot_product_attention", key, default="")
        if got:
            return got    # measured in an earlier run; cache persists
    lax_fn = get_op("scaled_dot_product_attention").fn

    def thunk(f):
        if not include_bwd:
            jf = jax.jit(f)
            return lambda: jf(q, a_k, v)
        jg = jax.jit(jax.grad(
            lambda q_, k_, v_: f(q_, k_, v_).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        return lambda: jg(q, a_k, v)

    candidates = {
        "lax": thunk(functools.partial(lax_fn, is_causal=is_causal)),
        "pallas": thunk(functools.partial(flash_attention,
                                          is_causal=is_causal))}
    plan = flash_attention_plan(sq, sk, d, h, is_causal, q.dtype)
    # dedup on the CLAMPED blocks: at short seq several configs collapse
    # to the same kernel — measuring it repeatedly under different names
    # is pure tuning-budget waste (and blocks that name the plan's own
    # forward tiles ARE the plan)
    seen_effective = {(plan["block_q"], plan["block_k"])}
    for bq, bk in _TUNE_BLOCKS:
        eff = (min(bq, sq), min(bk, sk))
        if eff in seen_effective:
            continue
        seen_effective.add(eff)
        candidates[f"pallas:{bq}x{bk}"] = thunk(functools.partial(
            flash_attention, is_causal=is_causal, block_q=bq, block_k=bk))
    return _at.measure("scaled_dot_product_attention", key, candidates,
                       persist=persist)


def _tuned_blocks(q, k, is_causal):
    """Dispatch-time lookup of the measured block config (host-side dict
    read; shapes are static under trace). Falls back to the plan's tiles
    when the tuned blocks do not divide THIS shape — the pow2-bucketed
    shape class can contain members the winning config cannot tile."""
    from . import autotune_cache as _at
    b, sq, h, d = _shape_of(q)
    sk = _shape_of(k)[1]
    plan = flash_attention_plan(sq, sk, d, h, is_causal, _dtype_of(q))
    choice = _at.choose(
        "scaled_dot_product_attention",
        _sdpa_key(b, h, sq, sk, d, _dtype_of(q), is_causal),
        default="pallas")
    if choice.startswith("pallas:"):
        try:
            bq, bk = (int(x) for x in choice.split(":", 1)[1].split("x"))
        except ValueError:
            import warnings
            warnings.warn(f"malformed autotune entry {choice!r}; using "
                          f"the plan's flash blocks", RuntimeWarning)
        else:
            if sq % min(bq, sq) == 0 and sk % min(bk, sk) == 0:
                return bq, bk
    return plan["block_q"], plan["block_k"]


def _sdpa_pallas(q, k, v, mask=None, dropout_key=None, dropout_p=0.0,
                 is_causal=False, scale=None):
    bq, bk = _tuned_blocks(q, k, is_causal)
    return flash_attention(q, k, v, is_causal=is_causal, scale=scale,
                           block_q=bq, block_k=bk)


register_override(
    "scaled_dot_product_attention",
    lambda args, attrs: _pallas_enabled() and _fa_supported(
        args[0], args[1], args[2],
        args[3] if len(args) > 3 else attrs.get("mask"),
        args[4] if len(args) > 4 else attrs.get("dropout_key"),
        attrs.get("dropout_p", 0.0), attrs.get("is_causal", False)),
)(_sdpa_pallas)


# ===========================================================================
# Fused LayerNorm (last axis, affine) — fwd kernel + recompute bwd kernel
# ===========================================================================

LN_BLOCK_ROWS = 128


def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    # w_ref/b_ref are [1, D]: rank-1 blocks have no legal TPU layout for
    # arbitrary D, and [1, D] broadcasts against [rows, D] for free
    x = x_ref[...].astype(jnp.float32)            # [rows, D]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * w_ref[...].astype(jnp.float32) + \
        b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _ln_bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dwp_ref, dbp_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)            # [1, D]
    d = x.shape[-1]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    gw = g * w
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (gw - m1 - xhat * m2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # per-row-block partial reductions for dw/db. The partials carry an
    # 8-sublane middle dim ([nb, 8, D] overall) because a (1, D) block
    # over an [nb, D] array is tiling-illegal on TPU; each partial is
    # spread evenly over its 8 sublanes so the caller's plain sum over
    # (nb, 8) recovers the exact total.
    dwp_ref[0] = jnp.broadcast_to(
        jnp.sum(g * xhat, axis=0, keepdims=True) / 8.0, (8, x.shape[-1]))
    dbp_ref[0] = jnp.broadcast_to(
        jnp.sum(g, axis=0, keepdims=True) / 8.0, (8, x.shape[-1]))


def _ln_reshape(x):
    d = x.shape[-1]
    rows = x.size // d
    return x.reshape(rows, d), rows, d


def _ln_block_rows(rows, d):
    """Row-block size bounded by a ~4MB-per-buffer VMEM budget (the bwd
    kernel holds three row blocks at fp32)."""
    budget_rows = max(8, (4 * 2 ** 20) // (d * 4))
    return min(LN_BLOCK_ROWS, rows, budget_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_layer_norm_2d(x2, w, b, eps):
    """x2: [rows, D]; w, b: [1, D]."""
    rows, d = x2.shape
    br = _ln_block_rows(rows, d)
    with _x64_off():
        return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        name="fused_layer_norm_fwd",
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            # w/b are [1, D] arrays: block == full array dim on both
            # axes, the legal-by-equality case of the tiling rule
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # lint: ok
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # lint: ok
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
            interpret=_interpret(),
        )(x2, w, b)


def _ln_fwd_rule(x2, w, b, eps):
    return _fused_layer_norm_2d(x2, w, b, eps), (x2, w, b)


def _ln_bwd_rule(eps, res, g):
    x2, w, b = res
    b_dtype = b.dtype
    rows, d = x2.shape
    br = _ln_block_rows(rows, d)
    nb = rows // br
    with _x64_off():
        dx, dwp, dbp = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        name="fused_layer_norm_bwd",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            # w is a [1, D] array: block == full array (legal equality)
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # lint: ok
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 8, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x2.dtype),
            jax.ShapeDtypeStruct((nb, 8, d), jnp.float32),
            jax.ShapeDtypeStruct((nb, 8, d), jnp.float32),
        ],
            interpret=_interpret(),
        )(x2, w, g)
    return (dx, dwp.sum((0, 1), keepdims=False)[None, :].astype(w.dtype),
            dbp.sum((0, 1), keepdims=False)[None, :].astype(b_dtype))


_fused_layer_norm_2d.defvjp(_ln_fwd_rule, _ln_bwd_rule)


def fused_layer_norm(x, weight, bias, epsilon=1e-5):
    """LayerNorm over the last axis with affine params, as one Pallas
    kernel per row-block (reference: fused LN in fused_dropout_helper.h)."""
    x2, rows, d = _ln_reshape(x)
    br = _ln_block_rows(rows, d)
    if rows % br:
        raise ValueError(
            f"fused_layer_norm needs total rows ({rows}) divisible by the "
            f"row block ({br})")
    b = bias if bias is not None else jnp.zeros((d,), x.dtype)
    out = _fused_layer_norm_2d(x2, weight.reshape(1, d), b.reshape(1, d),
                               float(epsilon))
    return out.reshape(x.shape)


def _ln_supported(x, weight, bias, begin_norm_axis):
    xs = _shape_of(x)
    if not xs or weight is None:
        return False
    if begin_norm_axis is not None and begin_norm_axis != len(xs) - 1:
        return False
    d = xs[-1]
    rows = 1
    for s in xs[:-1]:
        rows *= s
    if rows == 0 or d < 8 or d > 16384:
        return False
    return rows % _ln_block_rows(rows, d) == 0


register_override(
    "layer_norm",
    lambda args, attrs: _pallas_enabled() and _ln_supported(
        args[0],
        args[1] if len(args) > 1 else attrs.get("weight"),
        args[2] if len(args) > 2 else attrs.get("bias"),
        attrs.get("begin_norm_axis")),
)(lambda x, weight=None, bias=None, epsilon=1e-5, begin_norm_axis=None:
  fused_layer_norm(x, weight, bias, epsilon))


# ===========================================================================
# Fused AdamW update — one elementwise kernel for (p, m, v) (reference:
# operators/optimizers/adam_op.cu / merged_adam)
# ===========================================================================

def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  new_p_ref, new_m_ref, new_v_ref):
    lr, b1, b2, eps, wd, bc1, bc2 = (sc_ref[0], sc_ref[1], sc_ref[2],
                                     sc_ref[3], sc_ref[4], sc_ref[5],
                                     sc_ref[6])
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    pf = p_ref[...].astype(jnp.float32)
    mhat = m / bc1
    vhat = v / bc2
    new_p = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
    new_p_ref[...] = new_p.astype(new_p_ref.dtype)
    new_m_ref[...] = m
    new_v_ref[...] = v


# rows of the flattened (rows, 128) parameter per grid step: 7 f32
# operands x 2 pipeline buffers x 512 x 128 x 4 B = 3.5 MB of VMEM, far
# inside v5e's 16 MB scoped limit — the un-gridded kernel mapped the
# whole parameter as one block and was refused at (768, 3072)
_ADAMW_BLOCK_ROWS = 512


@functools.lru_cache(maxsize=1024)
def _fused_adamw_callable(shape, dtype_name, interpret):
    """One jitted (pad → kernel → unpad) callable per param shape/dtype —
    the eager step hits this cache instead of re-tracing every call."""
    dtype = jnp.dtype(dtype_name)
    n = 1
    for s in shape:
        n *= s
    lanes = 128
    rows = max(1, (n + lanes - 1) // lanes)
    pad = rows * lanes - n
    # a parameter shorter than one block is its own (full-dim) block;
    # the ragged last block of a longer one is masked by Pallas
    block_rows = min(rows, _ADAMW_BLOCK_ROWS)
    blk = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))

    def run(p, g, m, v, scalars):
        def flat(a, dt):
            a = a.reshape(-1).astype(dt)
            if pad:
                a = jnp.pad(a, (0, pad))
            return a.reshape(rows, lanes)

        with _x64_off():
            new_p, new_m, new_v = pl.pallas_call(
                _adamw_kernel,
                name="fused_adamw",
                grid=(pl.cdiv(rows, block_rows),),
                in_specs=[blk, blk, blk, blk,
                          pl.BlockSpec(memory_space=pltpu.SMEM)],
                out_specs=[blk, blk, blk],
                out_shape=[jax.ShapeDtypeStruct((rows, lanes), dtype),
                           jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
                           jax.ShapeDtypeStruct((rows, lanes), jnp.float32)],
                interpret=interpret,
            )(flat(p, dtype), flat(g, jnp.float32), flat(m, jnp.float32),
              flat(v, jnp.float32), scalars)

        def unflat(a, dt):
            return a.reshape(-1)[:n].reshape(shape).astype(dt)

        return (unflat(new_p, dtype), unflat(new_m, jnp.float32),
                unflat(new_v, jnp.float32))

    return jax.jit(run)


def fused_adamw(p, g, m, v, lr, beta1, beta2, eps, weight_decay, step):
    """Fused AdamW on a flattened parameter. Returns (new_p, new_m, new_v)."""
    bc1 = 1.0 - beta1 ** step
    bc2 = 1.0 - beta2 ** step
    scalars = jnp.asarray([lr, beta1, beta2, eps, weight_decay, bc1, bc2],
                          jnp.float32)
    fn = _fused_adamw_callable(tuple(p.shape), jnp.dtype(p.dtype).name,
                               _interpret())
    return fn(p, g, m, v, scalars)


def fused_adamw_available() -> bool:
    return _pallas_enabled()
