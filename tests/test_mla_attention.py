"""The latent (MLA) paged attention kernel in interpret mode against a
numpy oracle, at the least ragged shapes that cover its branches: decode
rows (the one-row body) beside a chunk (the ``BLOCK_Q``-row body),
contexts that end mid-block, one group of blocks and several, a pad q
block. float32, so the only difference is the order of the online
softmax's sums: 2e-5 on outputs of size ~1."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import mla_paged_attention as M
from paddle_tpu.ops.ragged_paged_attention import (BLOCK_Q, check_kv_tile,
                                                   kv_group_blocks,
                                                   ragged_layout)

H, LANES, V, BS = 4, 128, 32, 8


def _walk_case(blk_seq, q_lens, pos0s, seed, nan_rows=False):
    """A launch whose q blocks are ``blk_seq`` (sequence ids, -1 a pad
    block: laid out by hand, a pad block may come FIRST): sequence ``s``
    feeds ``q_lens[s]`` rows (0: absent) from position ``pos0s[s]``.
    Block ids are 0..NB-1, so the pool's last block ``NB`` is the scratch
    block no table names; ``nan_rows`` plants NaNs there and in every row
    of a sequence's last block past its ``kv_len``."""
    rng = np.random.default_rng(seed)
    S = len(q_lens)
    kv_len = np.asarray([p + n for p, n in zip(pos0s, q_lens)], np.int32)
    T = int(-(-kv_len.max() // BS))
    NB = S * T
    pool = rng.standard_normal((2, NB + 1, 1, BS, LANES)).astype(np.float32)
    pool[..., 48:] = 0.0
    tables = np.zeros((S, T), np.int32)
    ids = rng.permutation(NB)
    for s in range(S):
        n = -(-int(kv_len[s]) // BS)
        tables[s, :n] = ids[s * T:s * T + n]
        if nan_rows and kv_len[s] % BS:
            pool[:, tables[s, n - 1], 0, kv_len[s] % BS:] = np.nan
    if nan_rows:
        pool[:, NB] = np.nan
    blk_seq = np.asarray(blk_seq, np.int32)
    qstart = np.zeros(S, np.int32)
    for s in np.flatnonzero(np.asarray(q_lens) > 0):
        qstart[s] = int(np.flatnonzero(blk_seq == s)[0]) * BLOCK_Q
    q = rng.standard_normal((blk_seq.size * BLOCK_Q, H, LANES)).astype(
        np.float32)
    q[..., 48:] = 0.0
    rows = [(int(qstart[s]) + i, s, pos0s[s] + i)
            for s in range(S) for i in range(q_lens[s])]
    return (q, pool, blk_seq, qstart, np.asarray(pos0s, np.int32), tables,
            kv_len, rows)


def _case(q_lens, pos0s, q_bucket, seed):
    """The same with the q blocks ``ragged_layout`` lays out."""
    blk_seq = ragged_layout(q_lens, pos0s, q_bucket=q_bucket)[0]
    return _walk_case(blk_seq, q_lens, pos0s, seed)


@pytest.mark.parametrize("q_lens,pos0s,q_bucket", [
    # three decode rows (contexts ending mid-block) beside a 13-row chunk
    ([1, 13, 1, 1], [20, 6, 0, 43], 48),
    # a chunk whose last q block holds ONE real row, and an absent slot
    ([9, 0, 1], [3, 0, 15], 32),
])
def test_ragged_rows_against_the_oracle(q_lens, pos0s, q_bucket):
    q, pool, blk_seq, qstart, pos0, tables, kv_len, rows = _case(
        q_lens, pos0s, q_bucket, seed=sum(q_lens))
    lo = np.zeros(len(q_lens), np.int32)
    out = np.asarray(M.mla_paged_attention(
        jnp.asarray(q), jnp.asarray(pool), 1, blk_seq, qstart, pos0, tables,
        lo, kv_len, v_lanes=V, scale=0.2))
    assert out.shape == (q_bucket, H, V)
    at, seq, pos = (np.asarray(c) for c in zip(*rows))
    want = M.reference_mla_attention(q[at], pool, 1, seq, pos, tables, lo,
                                     v_lanes=V, scale=0.2)
    np.testing.assert_allclose(out[at], want, atol=2e-5)
    pad_blocks = np.flatnonzero(blk_seq < 0)
    assert pad_blocks.size and not out[pad_blocks[0] * BLOCK_Q:].any()


def test_a_context_of_several_groups(monkeypatch):
    """Groups of 4 blocks (32 columns) in place of 64: a 75-token context
    walks three groups, the last one partly filled and the buffers
    alternating."""
    monkeypatch.setattr(M, "LATENT_COLUMNS", 32)
    assert M.latent_group_blocks(BS, LANES, jnp.float32) == 4
    q_lens, pos0s = [1, 10], [74, 60]
    q, pool, blk_seq, qstart, pos0, tables, kv_len, rows = _case(
        q_lens, pos0s, 24, seed=11)
    lo = np.zeros(2, np.int32)
    # shapes no other case of this file traces: the group is read at trace time
    out = np.asarray(M.mla_paged_attention(
        jnp.asarray(q), jnp.asarray(pool), 0, blk_seq, qstart, pos0, tables,
        lo, kv_len, v_lanes=V, scale=0.3))
    at, seq, pos = (np.asarray(c) for c in zip(*rows))
    want = M.reference_mla_attention(q[at], pool, 0, seq, pos, tables, lo,
                                     v_lanes=V, scale=0.3)
    np.testing.assert_allclose(out[at], want, atol=2e-5)


def test_the_tile_law_admits_a_latent_row_by_its_lanes(monkeypatch):
    """``check_kv_tile`` extended, not bypassed: the same (sublane, 128)
    law, asked with the row's own width. 576 lanes are refused on a TPU,
    640 admitted; the group is sized from the bytes of a latent block."""
    from paddle_tpu.ops import ragged_paged_attention as rpa
    check_kv_tile("bfloat16", 16, 64)                    # as before
    check_kv_tile("bfloat16", 16, lanes=640)
    with pytest.raises(ValueError, match="sublane"):
        check_kv_tile("int8", 16, lanes=640)
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    check_kv_tile("bfloat16", 16, lanes=640)
    with pytest.raises(ValueError, match="576 lanes"):
        check_kv_tile("bfloat16", 16, lanes=576)
    with pytest.raises(ValueError, match="128-lane"):
        check_kv_tile("bfloat16", 16, 48)
    assert kv_group_blocks(20, 16, 64, "bfloat16") == 8  # GPT-2 large: as before
    assert M.latent_group_blocks(16, 640, "bfloat16") == 32
    with pytest.raises(ValueError, match="one row"):
        M.mla_paged_attention(jnp.zeros((8, H, LANES)),
                              jnp.zeros((1, 3, 2, BS, LANES)), 0, [0], [0],
                              [0], [[1]], [0], [1], v_lanes=V, scale=1.0)


def test_the_self_lint_reads_the_new_kernel_file():
    """``pallas-block-tiling`` walks every file of ``ops/``: the new file
    passes as it stands, and a literal block dim that breaks the law,
    planted in its text, is flagged."""
    import os
    from paddle_tpu.analysis import selflint
    path = os.path.join(os.path.dirname(M.__file__), "mla_paged_attention.py")
    src = open(path).read()
    rel = "ops/mla_paged_attention.py"
    assert not [f for f in selflint.lint_source(path, src, rel)
                if f.rule == "pallas-block-tiling"]
    planted = src.replace("pl.BlockSpec((m_blk, lanes),",
                          "pl.BlockSpec((4, 576),")
    assert planted != src
    found = [f.rule for f in selflint.lint_source(path, planted, rel)]
    assert found.count("pallas-block-tiling") == 2


# -- the pipelined walk (PR 47): what one wait a group, an unrolled issue
# and a first group started a grid step ahead can get wrong ---------------

@pytest.fixture
def groups_of_four(monkeypatch):
    """Groups of 4 blocks = 32 cache columns, so a context of a few dozen
    tokens walks several; the group is read when ``_mla_call`` is traced,
    so its jit cache is dropped on both sides."""
    monkeypatch.setattr(M, "LATENT_COLUMNS", 32)
    M._mla_call.clear_cache()
    yield
    M._mla_call.clear_cache()


@pytest.mark.parametrize("blk_seq,q_lens,pos0s,nan_rows", [
    # a context that ends exactly on a group border (64 = two groups of 32)
    ([0, 1], [1, 1], [63, 31], False),
    # ... and one token past it: a third group of one block, one row real
    ([0, 1], [1, 1], [64, 32], False),
    # consecutive q blocks of different sequences whose walks are 1, 2, 3,
    # 2, 1, 4 groups long: the slot a step begins on follows the last one
    ([0, 1, 2, 3, 4, 5], [1] * 6, [19, 49, 89, 63, 32, 100], False),
    # a real block followed by pad blocks: nothing is started for them
    ([0, 1, -1, -1], [1, 1], [40, 70], False),
    # the only real block is the call's last, after pad blocks
    ([-1, -1, 0], [1], [77], False),
    # real blocks on both sides of a pad block: the walk starts again
    ([0, -1, 1], [1, 1], [33, 95], False),
    # a chunk of three q blocks (20 rows) after decode rows, and one after
    ([0, 1, 2, 2, 2, 3], [1, 1, 20, 1], [45, 64, 50, 10], False),
    # NaNs in the pool's scratch block and past kv_len in every last block
    ([0, 1, 2, 2, 3], [1, 1, 11, 1], [36, 63, 27, 2], True),
], ids=["group-border", "one-past-the-border", "odd-and-even-walks",
        "pads-after-real", "only-the-last-is-real", "pad-between-real",
        "chunk-after-decode", "nans-where-no-token-is"])
def test_the_pipelined_walk_against_the_oracle(groups_of_four, blk_seq,
                                               q_lens, pos0s, nan_rows):
    """Each case twice: the second call runs on what the first left
    behind (nothing may be: no copy outstanding, no semaphore signalled)
    and has to give the same bits."""
    q, pool, blk_seq, qstart, pos0, tables, kv_len, rows = _walk_case(
        blk_seq, q_lens, pos0s, seed=len(blk_seq) + sum(pos0s),
        nan_rows=nan_rows)
    lo = np.zeros(len(q_lens), np.int32)
    call = lambda: np.asarray(M.mla_paged_attention(
        jnp.asarray(q), jnp.asarray(pool), 1, blk_seq, qstart, pos0, tables,
        lo, kv_len, v_lanes=V, scale=0.25))
    out = call()
    assert np.isfinite(out).all()
    at, seq, pos = (np.asarray(c) for c in zip(*rows))
    clean = np.nan_to_num(pool)              # the oracle reads whole rows
    want = M.reference_mla_attention(q[at], clean, 1, seq, pos, tables, lo,
                                     v_lanes=V, scale=0.25)
    np.testing.assert_allclose(out[at], want, atol=2e-5)
    for b in np.flatnonzero(blk_seq < 0):
        assert not out[b * BLOCK_Q:(b + 1) * BLOCK_Q].any()
    np.testing.assert_array_equal(call(), out)
