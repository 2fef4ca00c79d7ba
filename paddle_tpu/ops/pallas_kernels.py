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

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


# LSE (and the bwd delta) travel between kernels as [BH, S, LSE_LANES]
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


def _fa_fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                   block_q, block_k, seq_k):
    # i32-typed block-size constants: bare python ints in fori_loop bodies
    # get materialized as i64 by Mosaic, producing malformed mixed-type
    # index arithmetic on TPU
    _I32_BQ = jnp.int32(block_q)
    _I32_BK = jnp.int32(block_k)
    qi = pl.program_id(1)
    q = q_ref[0]                                  # [bq, D] (native dtype)
    bq, d = q.shape
    nk_full = seq_k // block_k
    if causal:
        # kv blocks beyond the diagonal contribute nothing
        nk = jnp.minimum(nk_full, ((qi + 1) * block_q + block_k - 1)
                         // block_k)
    else:
        nk = nk_full

    def body(j, carry):
        # running softmax stats stay 2D [bq, 1] (sublane-oriented);
        # rank-1 carries would force lane<->sublane relayouts in Mosaic
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.ds(j * _I32_BK, block_k), :]
        v_blk = v_ref[0, pl.ds(j * _I32_BK, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        if causal:
            rows = qi * _I32_BQ + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            cols = j * _I32_BK + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)         # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l_safe), (bq, LSE_LANES))


def _fa_dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  *, scale, causal, block_q, block_k, seq_k):
    _I32_BQ = jnp.int32(block_q)
    _I32_BK = jnp.int32(block_k)
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, :1]                        # [bq, 1] of [bq, 8]
    delta = delta_ref[0][:, :1]
    bq, d = q.shape
    nk_full = seq_k // block_k
    nk = jnp.minimum(nk_full, ((qi + 1) * block_q + block_k - 1) //
                     block_k) if causal else nk_full

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * _I32_BK, block_k), :]
        v_blk = v_ref[0, pl.ds(j * _I32_BK, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * _I32_BQ + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            cols = j * _I32_BK + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _fa_dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                   seq_q):
    _I32_BQ = jnp.int32(block_q)
    _I32_BK = jnp.int32(block_k)
    ki = pl.program_id(1)
    k = k_ref[0]                                  # [bk, D] (native dtype)
    v = v_ref[0]
    bk, d = k.shape
    nq_full = seq_q // block_q
    start_q = (ki * block_k) // block_q if causal else 0

    def body(j, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(j * _I32_BQ, block_q), :]
        do_blk = do_ref[0, pl.ds(j * _I32_BQ, block_q), :]
        lse_blk = lse_ref[0, pl.ds(j * _I32_BQ, block_q), :1]   # [bq, 1]
        delta_blk = delta_ref[0, pl.ds(j * _I32_BQ, block_q), :1]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            rows = j * _I32_BQ + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            cols = ki * _I32_BK + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_blk)
        dv_new = dv + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk) * scale
        dk_new = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, nq_full, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _fa_call_fwd_resident(q, k, v, scale, causal, block_q, block_k):
    """q,k,v: [BH, S, D] -> (o [BH, Sq, D], lse [BH, Sq, LSE_LANES])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    kernel = functools.partial(
        _fa_fwd_kernel_resident, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_k=sk)
    with _x64_off():
        return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, LSE_LANES), jnp.float32),
        ],
            interpret=_interpret(),
        )(q, k, v)


def _fa_call_bwd_resident(q, k, v, o, lse, do, scale, causal, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [BH, Sq, 1]
    delta = jnp.broadcast_to(delta, (bh, sq, LSE_LANES))
    with _x64_off():
        dq = pl.pallas_call(
        functools.partial(_fa_dq_kernel_resident, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk),
        name="flash_attention_dq",
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LSE_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            interpret=_interpret(),
        )(q, k, v, do, lse, delta)
        dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel_resident, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_q=sq),
        name="flash_attention_dkv",
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sq, LSE_LANES), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sq, LSE_LANES), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
            interpret=_interpret(),
        )(q, k, v, do, lse, delta)
    return dq, dk, dv



# ---------------------------------------------------------------------------
# kernel variant dispatch: the RESIDENT kernels map full K/V into VMEM
# (fastest: one kernel invocation per q block, measured 1.4x the
# streaming variant at s=1024) but cap the sequence at VMEM; the
# STREAMING kernels above block K/V through a 3D grid with scratch
# carries and have no sequence cap (32k+ tested on hardware). Pick per
# shape.
# ---------------------------------------------------------------------------

_RESIDENT_VMEM_ELEMS = 1_500_000  # (sq + sk) * d fp32 budget, ~6MB x2


def _use_resident(sq, sk, d):
    return (sq + sk) * d <= _RESIDENT_VMEM_ELEMS


def _fa_dispatch_fwd(q, k, v, scale, causal, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    if _use_resident(sq, sk, d):
        return _fa_call_fwd_resident(q, k, v, scale, causal, block_q,
                                     block_k)
    return _fa_call_fwd(q, k, v, scale, causal, block_q, block_k)


def _fa_dispatch_bwd(q, k, v, o, lse, do, scale, causal, block_q,
                     block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    if _use_resident(sq, sk, d):
        return _fa_call_bwd_resident(q, k, v, o, lse, do, scale, causal,
                                     block_q, block_k)
    return _fa_call_bwd(q, k, v, o, lse, do, scale, causal, block_q,
                        block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_bhsd(q, k, v, scale, causal, block_q, block_k):
    o, _ = _fa_dispatch_fwd(q, k, v, scale, causal, block_q, block_k)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k):
    o, lse = _fa_dispatch_fwd(q, k, v, scale, causal, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, res, do):
    q, k, v, o, lse = res
    return _fa_dispatch_bwd(q, k, v, o, lse, do, scale, causal, block_q,
                            block_k)


_flash_attention_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, is_causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Flash attention on [B, S, H, D] inputs (the framework's attention
    layout). Differentiable via the Pallas backward kernels."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash_attention needs seq lengths divisible by the block "
            f"sizes: sq={sq} %% {block_q}, sk={sk} %% {block_k}")
    # [B,S,H,D] -> [B*H, S, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    o = _flash_attention_bhsd(qt, kt, vt, float(s), bool(is_causal),
                              int(block_q), int(block_k))
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


# Measured crossover on v5e (BENCH r3): at seq 128 XLA's native fused
# attention beats the flash kernel (BERT 47.6 vs 35.9 steps/s — the full
# S^2 matrix is tiny and XLA's bf16 fusion wins), while at seq 1024 the
# flash kernel wins 1.16x (GPT-2). This heuristic is only the DEFAULT:
# the shape-class autotune cache (ops/autotune_cache.py, r3 verdict
# item 9) overrides it wherever a measured winner is recorded, and
# tune_attention() records winners per device kind.
FLASH_MIN_SEQ = 512


def _sdpa_key(b, h, sq, sk, d, dtype, is_causal):
    from . import autotune_cache as _at
    # tune=bwd2: key-format version. Pre-r5 entries were measured
    # fwd-only at default blocks; the r5 tuner measures fwd+bwd across
    # block configs — stale entries must miss, not veto the new search.
    return _at.shape_class(b * h, sq, sk, d, dtype=str(dtype),
                           causal=bool(is_causal), tune="bwd2")


def _fa_supported(q, k, v, mask, dropout_key, dropout_p, is_causal,
                  block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    qs, ks = _shape_of(q), _shape_of(k)
    if len(qs) != 4 or mask is not None or (dropout_p or 0.0) > 0.0:
        return False
    b, sq, h, d = qs
    sk = ks[1]
    if is_causal and sq != sk:
        return False
    bq, bk = min(block_q, sq), min(block_k, sk)
    # structural requirements first — an unlowrable shape never dispatches
    # to Pallas regardless of what the cache says.
    # streaming kernels: VMEM holds only (block_q + 2*block_k) x d tiles
    # plus scratch regardless of sequence length, so there is no seq cap —
    # long context is bounded by HBM for Q/K/V themselves (e.g. 128k x 128
    # bf16 = 32MB per head-batch).
    if not (sq % bq == 0 and sk % bk == 0 and d <= 256 and
            sq >= 8 and sk >= 8):
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


# block-size search space for tune_attention (r4 verdict item 3: the
# flash bwd was undertuned at the default 128x128). Unlowerable or
# non-dividing combos simply fail their measurement and never win.
_TUNE_BLOCKS = [(128, 128), (256, 128), (128, 256), (256, 256)]


def tune_attention(q, a_k, v, is_causal=False, persist=True,
                   include_bwd=True, skip_if_cached=False):
    """Measure lax vs pallas (across block-size configs) for this shape
    class on CONCRETE arrays and record the winner in the autotune cache
    (the reference's warmup-step measurement, made explicit). With
    ``include_bwd`` the timed quantity is a full fwd+bwd — the training
    crossover, which is what the benches dispatch on. Returns the
    winning tier name (``lax``, ``pallas``, or ``pallas:BQxBK``)."""
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
        "lax": thunk(functools.partial(lax_fn, is_causal=is_causal))}
    seen_effective = set()
    for bq, bk in _TUNE_BLOCKS:
        # dedup on the CLAMPED blocks: at short seq several configs
        # collapse to the same kernel — measuring it repeatedly under
        # different names is pure tuning-budget waste
        eff = (min(bq, sq), min(bk, sk))
        if eff in seen_effective:
            continue
        seen_effective.add(eff)
        if eff == (min(DEFAULT_BLOCK_Q, sq), min(DEFAULT_BLOCK_K, sk)):
            name = "pallas"       # default blocks keep the plain name
        else:
            name = f"pallas:{bq}x{bk}"
        candidates[name] = thunk(functools.partial(
            flash_attention, is_causal=is_causal, block_q=bq, block_k=bk))
    return _at.measure("scaled_dot_product_attention", key, candidates,
                       persist=persist)


def _tuned_blocks(q, k, is_causal):
    """Dispatch-time lookup of the measured block config (host-side dict
    read; shapes are static under trace). Falls back to the defaults
    when the tuned blocks do not divide THIS shape — the pow2-bucketed
    shape class can contain members the winning config cannot tile."""
    from . import autotune_cache as _at
    b, sq, h, d = _shape_of(q)
    sk = _shape_of(k)[1]
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
                          f"default flash blocks", RuntimeWarning)
            return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
        if sq % min(bq, sq) == 0 and sk % min(bk, sk) == 0:
            return bq, bk
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K


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
