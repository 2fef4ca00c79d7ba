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
there. Everything here is ``jax.numpy`` in float32.

The recurrence, a head ``h`` (``A_h < 0``, ``dt_t > 0``)::

    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t (x) B_t       H [P, N]
    y_t = H_t C_t + D_h x_t

in its two forms (:func:`ssm_scan`):

* **one step a sequence**, for every slot at once, on the slot's first
  row: read ``H``, write ``H``, ``2 x heads x P x N x 4`` bytes a
  sequence a layer and nothing else to speak of — memory-bound. It is
  the whole of a plain launch's scan.
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
"""
from __future__ import annotations

from typing import NamedTuple

__all__ = ["SeqLayout", "seq_layout", "conv_rows", "ssm_scan",
           "ssm_step", "ssm_chunk_scan"]


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
    import jax.numpy as jnp
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
    import jax.numpy as jnp
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
    import jax.numpy as jnp
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssm_step(h, x, dt, a, b, c, d):
    """ONE step of the recurrence for a batch: ``h [S, H, P, N]``, ``x
    [S, H, P]``, ``dt [S, H]`` (after the softplus), ``a``/``d [H]``,
    ``b``/``c [S, G, N]`` -> ``(y [S, H, P], h)``."""
    import jax.numpy as jnp
    H = x.shape[1]
    bh, ch = _heads(b, H), _heads(c, H)
    h = jnp.exp(dt * a)[..., None, None] * h \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    return jnp.sum(h * ch[:, :, None, :], axis=-1) + d[:, None] * x, h


def ssm_chunk_scan(h, x, dt, a, b, c, d):
    """One chunk of ONE sequence with an initial state, in the quadratic
    form: ``h [H, P, N]``, ``x [T, H, P]``, ``dt [T, H]`` (0 at a row
    that is not the sequence's: it neither decays nor adds), ``b``/``c
    [T, G, N]`` -> ``(y [T, H, P], h`` after the last row``)``. float32,
    every product at the highest precision (the recurrence sums its
    rounding)."""
    import jax
    import jax.numpy as jnp
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
    import jax
    import jax.numpy as jnp
    Q, H, P = x.shape
    S = lay.seq_len.shape[0]
    chunk = min(int(chunk), Q)
    i32 = jnp.int32

    # -- one step a sequence, every slot at once, on its first row --------
    r0 = lay.seq_qstart
    old = state[layer, :S]
    y0, stepped = ssm_step(
        jnp.where(lay.seq_fresh[:, None, None, None], 0.0, old),
        x[r0], dt[r0], a, b[r0], c[r0], d)
    single = lay.seq_len == 1
    state = state.at[layer, :S].set(
        jnp.where(single[:, None, None, None], stepped, old))
    y0 = jnp.concatenate([jnp.where(single[:, None, None], y0, 0.0),
                          jnp.zeros((1, H, P), jnp.float32)])
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
