"""The selective state-space recurrence (Mamba-2) of a RAGGED serving
launch, over a state array a slot — the mixer's counterpart of
``ops/ragged_paged_attention.py`` + ``ops/kv_append.py``.

(The file's name says less than it holds since ``models/lfm2.py``:
:func:`seq_layout` and :func:`conv_rows` serve ANY mixer whose state is a
row a slot — LFM2's gated short convolution has no recurrence and uses
those two alone, at ``K`` 3 without a bias.)

What a sequence leaves behind in a state-space layer does not grow with
its context: the last ``d_conv - 1`` inputs of the depthwise convolution
(the TAIL) and the recurrent state ``H [heads, P, N]``. The serving pool
holds one row of each a slot (``serving/paging.py``: ``[layers with
state, slots + 1, ...]``, float32), and a launch's ragged batch — per
sequence contiguous rows, on the step's TOWER rows: back to back where
the tower has an axis of its own, padded to whole q blocks where it
shares the attention kernel's (``models/generation.py:_row_axes``,
``ops.ragged_paged_attention.ragged_layout``) — mixes sequences of ONE
row (decode) with a few of up to a thousand (prompt chunks). Each starts
from ITS slot's state and leaves the state after its last real row
there. Everything here is float32, and ``jax.numpy`` but for the step of
the one-row sequences, which is a Pallas kernel (:func:`state_step`).

The recurrence, a head ``h`` (``A_h < 0``, ``dt_t > 0``)::

    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t (x) B_t       H [P, N]
    y_t = H_t C_t + D_h x_t

in its two forms (:func:`ssm_scan`):

* **one step a sequence**, for every slot at once, on the slot's first
  row: read ``H``, write ``H``, ``2 x heads x P x N x 4`` bytes a
  sequence a layer and nothing else to speak of — memory-bound. It is
  the whole of a plain launch's scan, and ONE kernel
  (:func:`state_step`; :func:`ssm_step` is its plain statement, which
  the tests hold it to): written with ``jax.numpy``, XLA made two
  fusions of it that passed over the state three times (PERF.md, PR 51).
* **the chunked scan** for a sequence of more than one row: chunks of
  ``chunk`` rows (the published ``mamba_chunk_size``), inside a chunk
  the quadratic form on the MXU (``y = (L * C B^T) (dt x)`` with ``L[t,
  u] = exp(sum_{u < v <= t} dt_v A)``), between chunks the state
  carried, the first chunk starting from the slot's state. A ``while``
  loop over the launch's chunks (none in a plain launch): a sequence of
  1,024 rows moves its state 8 times, not 1,024.

A sequence whose first row is at position 0 (``seq_fresh``) starts from
ZERO, here, in the program: its slot may have served another request
whose last launch is still in flight, so the host cannot clear it
(``serving/scheduler.py``: two launches in flight). A slot with no rows
in the launch keeps its state; pad rows belong to no sequence and touch
none. The state arrays' last row (``slots``) is owned by no sequence:
the chunked scan parks a sequence's running state there between chunks'
writes so that every write of the loop has one shape.

:func:`conv_rows` is the causal depthwise convolution over the same
layout with the tail carried, :func:`seq_layout` what both read of the
launch's metadata.

The step kernel
---------------
Grid ``(slot, head block)``; a grid step brings ``[hb, P, N]`` of
``state[layer, s]`` into VMEM through the ``BlockSpec`` pipeline (the
next block's read and the last one's write ride under this one's
arithmetic: no hand-issued DMA for a stream this regular), computes a
head at a time ::

    h' = exp(dt a) h + (dt x) (x) B        (h = 0 where the slot is fresh)
    y  = h' C

and writes ``h'`` back to the SAME place: the whole state array is the
call's operand AND its result (``input_output_aliases``), so the other
layers and row ``S``, which no grid step visits, keep their bytes
unmoved, and ``layer`` rides the scalar-prefetch path (one trace a step
program, not one a layer — ``ops/kv_append.py``'s "Operands"). Beside
``layer`` in SMEM: what each slot does (0: it has no single row this
launch — absent, or a longer sequence whose OLD state the chunked scan
reads afterwards — and its block goes back as it came; 1: a step from
its state; 2: a step from zero) and ``exp(dt a)``, a scalar a head.
``dt x`` arrives ``[hb, P]`` with ``P`` on lanes and is turned once a
block, so that a head's column of it broadcasts over the state's lanes
as ``B``'s row does over its sublanes. The contraction with ``C`` is on
the MXU (``h' @ C`` at ``Precision.HIGHEST``, ``C``'s row as 128 equal
rows): as a product on the VPU and a reduction over lanes it PACED the
kernel at Nemotron-3's ``N`` 128 — a cross-lane reduction for every
register of state, 525 GB/s where the kernel without it streams 649 —
and on the MXU it hides behind the stream at both cells' widths (PERF.md,
PR 51). ``y``'s columns are gathered and turned back. ``D x``, the gathers of
the slots' rows and ``exp`` stay ``jax.numpy`` on ``[S, H, P]``. ``hb``
follows from the shapes (:func:`step_head_block`): no caller sizes it.
Off the TPU the kernel runs interpreted (``pallas_kernels._interpret``),
so the tests run the same code; the program has no other path.

How often it engages is in the launch record already
(``serving/engine.py``): ``ssm_rows - ssm_chunk_rows`` IS the number of
one-row sequences — the slots a layer the kernel steps — and
``state_slots`` the sequences whose state moves at all.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret, _x64_off

__all__ = ["SeqLayout", "seq_layout", "conv_rows", "ssm_scan",
           "ssm_step", "ssm_chunk_scan", "state_step", "step_head_block",
           "step_head_blocks", "STEP_VMEM_BUDGET"]


class SeqLayout(NamedTuple):
    """A launch's rows by sequence. ``S`` slots, ``Q`` rows of the
    step's tower (``Q`` throughout this module: whichever axis the
    caller's rows are on)."""
    row_seq: object      # [Q] int32: the row's slot; S for a row of none
    row_off: object      # [Q] int32: the row's place in its sequence's rows
    seq_qstart: object   # [S] int32: the sequence's first row
    seq_len: object      # [S] int32: its real rows this launch (0: absent)
    seq_fresh: object    # [S] bool: its first row is position 0


def seq_layout(blk_seq, seq_qstart, seq_pos0, kv_len, row_valid,
               block_q: int) -> SeqLayout:
    """The layout from the attention kernel's scalar metadata (a launch
    of one token a row: ``kv_len - seq_pos0`` rows a sequence):
    ``blk_seq`` names the sequence of each run of ``block_q`` rows (-1 or
    ``S``: none) and ``seq_qstart`` each sequence's first row. Rows
    padded to the kernel's q blocks are described by the kernel's own
    ``blk_seq`` and 8; rows laid back to back, without that padding, by a
    sequence a ROW (``block_q`` 1) and the sequences' compact starts —
    :func:`conv_rows` and :func:`ssm_scan` read either."""
    S = seq_qstart.shape[0]
    row_seq = jnp.repeat(blk_seq.astype(jnp.int32), block_q)
    row_seq = jnp.where(row_valid & (row_seq >= 0), row_seq, S)
    rows = jnp.arange(row_seq.shape[0], dtype=jnp.int32)
    qs = jnp.concatenate([seq_qstart.astype(jnp.int32),
                          jnp.zeros(1, jnp.int32)])
    seq_len = (kv_len - seq_pos0).astype(jnp.int32)
    return SeqLayout(row_seq, rows - qs[row_seq], seq_qstart.astype(jnp.int32),
                     seq_len, (seq_pos0 == 0) & (seq_len > 0))


def conv_rows(x, weight, bias, tail, layer: int, lay: SeqLayout):
    """Causal depthwise convolution over a ragged launch with the tail
    carried. ``x [Q, C]`` float32 inputs, ``weight [K, C]`` (tap ``j``
    reads the input ``K - 1 - j`` rows back), ``bias [C]`` or ``None``; ``tail
    [layers, S + 1, K - 1, C]`` the slots' last ``K - 1`` inputs (a fresh
    sequence reads zeros). Returns ``(out [Q, C]`` before the activation,
    ``tail)`` with each present sequence's row replaced by the inputs of
    its last ``K - 1`` positions."""
    Q, C = x.shape
    K = weight.shape[0]
    S = lay.seq_len.shape[0]
    w = weight.astype(jnp.float32)
    old = tail[layer, :S]                                   # [S, K-1, C]
    present = lay.seq_len > 0
    # what a sequence's first rows see before them
    prev = jnp.where(lay.seq_fresh[:, None, None], 0.0, old)
    out = x * w[K - 1]
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    for back in range(1, K):
        # the input `back` rows up, where it is the same sequence's
        shifted = jnp.concatenate(
            [jnp.zeros((back, C), x.dtype), x[:Q - back]])
        out = out + jnp.where((lay.row_off >= back)[:, None],
                              shifted, 0.0) * w[K - 1 - back]
    # rows 0 .. K-2 of a sequence read the tail: row i, `back` rows up,
    # is tail entry K - 1 + i - back
    head = jnp.stack([sum(prev[:, K - 1 + i - back] * w[K - 1 - back]
                          for back in range(i + 1, K))
                      for i in range(K - 1)], axis=1)
    head = jnp.concatenate([head, jnp.zeros((1, K - 1, C), jnp.float32)])
    first = jnp.where(lay.row_off < K - 1, lay.row_seq, S)
    out = out + head[first, jnp.clip(lay.row_off, 0, K - 2)]
    # the new tail: the inputs of the sequence's last K-1 positions, from
    # its rows where it has them and from the old tail before that
    k = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    off = lay.seq_len[:, None] - (K - 1) + k                # [S, K-1]
    from_rows = x[jnp.clip(lay.seq_qstart[:, None] + off, 0, Q - 1)]
    from_tail = jnp.take_along_axis(
        prev, jnp.clip(off + K - 1, 0, K - 2)[:, :, None], axis=1)
    new = jnp.where((off >= 0)[:, :, None], from_rows, from_tail)
    new = jnp.where(present[:, None, None], new, old)
    return out, tail.at[layer, :S].set(new.astype(tail.dtype))


def _heads(v, heads: int):
    """``[..., G, N]`` of the groups -> ``[..., heads, N]``: head ``h``
    reads group ``h // (heads / G)``."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssm_step(h, x, dt, a, b, c, d):
    """ONE step of the recurrence for a batch: ``h [S, H, P, N]``, ``x
    [S, H, P]``, ``dt [S, H]`` (after the softplus), ``a``/``d [H]``,
    ``b``/``c [S, G, N]`` -> ``(y [S, H, P], h)``."""
    H = x.shape[1]
    bh, ch = _heads(b, H), _heads(c, H)
    h = jnp.exp(dt * a)[..., None, None] * h \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    return jnp.sum(h * ch[:, :, None, :], axis=-1) + d[:, None] * x, h


# A grid step of the step kernel holds one block of a slot's state four
# times over in VMEM (the pipeline's two buffers each way); the head block
# is the largest the shapes allow inside this.
STEP_VMEM_BUDGET = 4 << 20


def step_head_blocks(heads: int, groups: int) -> list:
    """The head blocks :func:`state_step` can run at: divisors of
    ``heads`` that Mosaic can tile (whole sublane tiles of 8 heads in the
    ``[hb, P]`` blocks of ``x`` and ``y``, or every head) and that lie
    whole inside a group's heads or hold whole groups (a block then reads
    its ``B`` and ``C`` rows at static places)."""
    per_group = heads // groups
    return [hb for hb in range(1, heads + 1)
            if heads % hb == 0 and (hb % 8 == 0 or hb == heads)
            and (hb % per_group == 0 or per_group % hb == 0)]


def step_head_block(heads: int, head_dim: int, d_state: int,
                    groups: int) -> int:
    """Heads a grid step of :func:`state_step` — read from the state's
    shape and from nothing else: the largest of :func:`step_head_blocks`
    whose block ``[hb, P, N]`` float32 fits ``STEP_VMEM_BUDGET`` four
    times; where none fits, the smallest of them."""
    legal = step_head_blocks(heads, groups)
    fits = [hb for hb in legal
            if 4 * hb * head_dim * d_state * 4 <= STEP_VMEM_BUDGET]
    return max(fits) if fits else min(legal)


def _step_kernel(layer_ref, how_ref, decay_ref, dtx_ref, b_ref, c_ref, h_in,
                 y_ref, h_out, *, heads, per_group):
    """One grid step: slot ``s``, head block ``j``. ``how_ref[s]`` is 0
    for a slot this launch does not step (its block goes back as it
    came), 1 for a step from the slot's state, 2 for a step from zero.
    ``h_in`` and ``h_out`` are the same block of the same array.

    A head at a time, so that what is live is one ``[P, N]`` of vector
    registers: ``dtx`` comes with ``P`` on lanes and is turned ONCE a
    block, a head's column of it then broadcasts over the state's lanes
    as ``B``'s row does over its sublanes. ``y`` is a product on the MXU
    against ``C``'s row as 128 equal rows (every column of it is ``y``);
    its columns are gathered a head a lane and turned back."""
    del layer_ref
    s, j = pl.program_id(0), pl.program_id(1)
    hb, P = dtx_ref.shape[1:]
    N = b_ref.shape[2]
    how = how_ref[s]

    @pl.when(how == 0)
    def _keep():
        h_out[...] = h_in[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(how != 0)
    def _step():
        fresh = how == 2
        dtx = dtx_ref[0].T                                  # [P, hb]
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, hb), 1)
        y = jnp.zeros((P, hb), jnp.float32)
        for h in range(hb):
            if h % per_group == 0:
                g = (j * hb + h) // per_group
                b_row = b_ref[0, pl.ds(g, 1), :]            # [1, N]
                c_rows = jnp.broadcast_to(c_ref[0, pl.ds(g, 1), :], (128, N))
            old = jnp.where(fresh, 0.0, h_in[0, 0, h])      # [P, N]
            new = decay_ref[s * heads + j * hb + h] * old \
                + dtx[:, h:h + 1] * b_row
            h_out[0, 0, h] = new
            y_h = jax.lax.dot_general(
                new, c_rows, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)         # [P, 128]
            y = jnp.where(lane == h, y_h[:, :1], y)
        y_ref[0] = y.T


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(layer, how, decay, dtx, b, c, state, *, interpret):
    """The Pallas call, a jitted function of its own with ``layer`` an
    operand, so a step program traces and lowers the kernel once
    (``kv_append._append_call``'s reason)."""
    S, H, P = dtx.shape
    G, N = b.shape[1:]
    hb = step_head_block(H, P, N, G)
    rows = lambda s, j, *_: (s, j, 0)
    group_rows = lambda s, j, *_: (s, 0, 0)
    block = lambda s, j, layer, *_: (layer[0], s, j, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, H // hb),
        in_specs=[
            pl.BlockSpec((1, hb, P), rows),
            pl.BlockSpec((1, G, N), group_rows),
            pl.BlockSpec((1, G, N), group_rows),
            pl.BlockSpec((1, 1, hb, P, N), block),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, P), rows),
            pl.BlockSpec((1, 1, hb, P, N), block),
        ],
    )
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=H, per_group=H // G),
        name="ssm_step",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch three: the state is the seventh
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer, how, decay, dtx, b, c, state)


def state_step(state, layer: int, how, x, dt, a, b, c, d):
    """ONE step of the recurrence for every slot at once, the state read
    and written once and IN PLACE (where ``state`` is donated): the
    Pallas kernel of :func:`ssm_step`. ``state [layers, S + 1, H, P,
    N]`` float32; ``how [S]`` int32 says what a slot does — 0 keeps its
    state (bit for bit; its ``y`` reads 0), 1 steps from it, 2 steps from
    zero whatever it holds; ``x [S, H, P]``, ``dt [S, H]``, ``b``/``c
    [S, G, N]`` the slots' rows (a kept slot's may hold anything),
    ``a``/``d [H]``. Returns ``(y [S, H, P], state)``; the other layers
    and row ``S`` are not moved."""
    L, S1, H, P, N = state.shape
    S = x.shape[0]
    if state.dtype != jnp.float32:
        raise ValueError(f"the recurrent state is float32, not "
                         f"{state.dtype.name} (PERF.md 40.6)")
    if S1 != S + 1 or x.shape != (S, H, P) or b.shape != c.shape \
            or b.shape[0] != S or b.shape[2] != N or H % b.shape[1]:
        raise ValueError(
            f"state {state.shape} does not go with x {x.shape}, "
            f"b {b.shape}, c {c.shape}")
    if not 0 <= int(layer) < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    how = jnp.asarray(how, jnp.int32)
    with _x64_off():
        y, state = _step_call(
            jnp.asarray([layer], jnp.int32), how,
            jnp.exp(f32(dt) * f32(a)).reshape(S * H),
            f32(dt)[..., None] * f32(x), f32(b), f32(c), state,
            interpret=_interpret())
    return jnp.where((how != 0)[:, None, None], y + d[:, None] * x, 0.0), \
        state


def ssm_chunk_scan(h, x, dt, a, b, c, d):
    """One chunk of ONE sequence with an initial state, in the quadratic
    form: ``h [H, P, N]``, ``x [T, H, P]``, ``dt [T, H]`` (0 at a row
    that is not the sequence's: it neither decays nor adds), ``b``/``c
    [T, G, N]`` -> ``(y [T, H, P], h`` after the last row``)``. float32,
    every product at the highest precision (the recurrence sums its
    rounding)."""
    T, H, _ = x.shape
    hi = jax.lax.Precision.HIGHEST
    cs = jnp.cumsum(dt * a, axis=0)                         # [T, H] <= 0
    seen = jnp.tril(jnp.ones((T, T), bool))
    # L[t, u] = exp(cs_t - cs_u) for u <= t: u's input as row t sees it
    L = jnp.exp(jnp.where(seen[:, :, None],
                          cs[:, None, :] - cs[None, :, :], -jnp.inf))
    cb = jnp.einsum("tgn,ugn->tug", c, b, precision=hi)     # [T, T, G]
    w = _heads(cb[..., None], H)[..., 0] * L * dt[None]     # [T, T, H]
    y = jnp.einsum("tuh,uhp->thp", w, x, precision=hi)
    ch, bh = _heads(c, H), _heads(b, H)
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "thn,hpn->thp", ch, h, precision=hi)
    to_end = jnp.exp(cs[-1][None] - cs) * dt                # [T, H]
    h = jnp.exp(cs[-1])[:, None, None] * h + jnp.einsum(
        "thp,thn->hpn", to_end[..., None] * x, bh, precision=hi)
    return y + d[None, :, None] * x, h


def ssm_scan(x, dt, a, b, c, d, state, layer: int, lay: SeqLayout,
             chunk: int = 128):
    """The recurrence over a ragged launch (module doc). ``x [Q, H, P]``,
    ``dt [Q, H]`` (after the softplus), ``b``/``c [Q, G, N]``, ``a``/``d
    [H]``, all float32; ``state [layers, S + 1, H, P, N]`` the slots'
    recurrent state, ``layer`` the layer's place in it. Returns ``(y [Q,
    H, P], state)``: a row of no sequence reads 0."""
    Q, H, P = x.shape
    S = lay.seq_len.shape[0]
    chunk = min(int(chunk), Q)
    i32 = jnp.int32

    # -- one step a sequence, every slot at once, on its first row --------
    # (a longer sequence keeps its OLD state here: `one_chunk` starts
    # from it)
    r0 = lay.seq_qstart
    y0, state = state_step(
        state, layer,
        jnp.where(lay.seq_len == 1, 1 + lay.seq_fresh.astype(i32), 0),
        x[r0], dt[r0], a, b[r0], c[r0], d)
    y0 = jnp.concatenate([y0, jnp.zeros((1, H, P), jnp.float32)])
    y = jnp.where((lay.row_off == 0)[:, None, None], y0[lay.row_seq], 0.0)

    # -- the chunked scan of every longer sequence -------------------------
    n_chunks = jnp.where(lay.seq_len > 1, -(-lay.seq_len // chunk), 0)
    ends = jnp.cumsum(n_chunks).astype(i32)
    rows = jnp.arange(chunk, dtype=i32)

    def one_chunk(carry):
        i, h_run, y, state = carry
        s = jnp.sum(ends <= i).astype(i32)          # the chunk's sequence
        k = i - (ends[s] - n_chunks[s])             # its place in it
        start = lay.seq_qstart[s] + k * chunk
        # a slice may not run past the rows: it starts earlier then, and
        # the rows before the chunk's first are masked out
        at = jnp.minimum(start, Q - chunk)
        off = k * chunk + rows - (start - at)       # place in the sequence
        mine = (rows >= start - at) & (off < lay.seq_len[s])
        cut = lambda v: jax.lax.dynamic_slice_in_dim(v, at, chunk, 0)
        h_in = jnp.where(
            k > 0, h_run,
            jnp.where(lay.seq_fresh[s], 0.0, jax.lax.dynamic_slice(
                state, (i32(layer), s, i32(0), i32(0), i32(0)),
                (1, 1) + state.shape[2:])[0, 0]))
        y_c, h_out = ssm_chunk_scan(
            h_in, jnp.where(mine[:, None, None], cut(x), 0.0),
            jnp.where(mine[:, None], cut(dt), 0.0), a, cut(b), cut(c), d)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(mine[:, None, None], y_c, cut(y)), at, 0)
        # the sequence's state lands in its slot with its last chunk;
        # until then the write goes to the row no sequence owns
        last = k == n_chunks[s] - 1
        state = jax.lax.dynamic_update_slice(
            state, h_out[None, None],
            (i32(layer), jnp.where(last, s, S).astype(i32), i32(0), i32(0),
             i32(0)))
        return i + 1, h_out, y, state

    _, _, y, state = jax.lax.while_loop(
        lambda carry: carry[0] < ends[-1], one_chunk,
        (i32(0), jnp.zeros(state.shape[2:], jnp.float32), y, state))
    return y, state
