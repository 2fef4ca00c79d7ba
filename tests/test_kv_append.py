"""``ops/kv_append.py`` against the XLA scatter it replaces in the fused
step (``models/generation.py:_write_rows``), kernel interpreted on the
CPU: the pool bit for bit outside the scratch block, the scratch block
and every other layer untouched."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.generation import _kv_lanes, _write_rows
from paddle_tpu.ops import kv_append as KA
from paddle_tpu.ops.ragged_paged_attention import BLOCK_Q, ragged_layout

DH, BS, NB, L = 64, 16, 72, 2


def _launch(seqs, q_bucket):
    """``write_block`` / ``write_off`` as ``engine._ragged_operands``
    builds them: ``seqs`` is ``(real rows, first cache position)`` a
    sequence, each given its own blocks 1, 2, ... in order."""
    _, qstart, _, _, _ = ragged_layout([n for n, _ in seqs],
                                       [p for _, p in seqs],
                                       q_bucket=q_bucket)
    wb = np.zeros(q_bucket, np.int32)
    off = np.zeros(q_bucket, np.int32)
    free = 1
    for s, (n, p0) in enumerate(seqs):
        table = np.arange(free, free + (p0 + n + BS - 1) // BS)
        free = int(table[-1]) + 1
        for i in range(n):
            wb[qstart[s] + i] = table[(p0 + i) // BS]
            off[qstart[s] + i] = (p0 + i) % BS
    assert free <= NB + 1
    return wb, off


CASES = {
    # name: (sequences (rows, first position), q bucket)
    "decode-rows": ([(1, 20), (1, 0), (1, 15), (1, 16), (1, 47)], 64),
    # 40 rows from position 5: blocks 0|1|2 of the sequence, the first
    # entered mid-block, q blocks and KV blocks out of step
    "chunk-mid-block": ([(40, 5)], 64),
    # rows 0-7 and 8-15 are two q blocks and one KV block
    "two-q-blocks-one-kv-block": ([(16, 0)], 32),
    "pad-rows-only": ([], 32),
    # more rewrites than the ring holds, a chunk among decode rows, pad
    # q blocks behind them
    "mixed-past-the-ring": ([(1, 3 * i) for i in range(20)]
                            + [(45, 11)] + [(1, 31)] * 3, 256),
    # a q bucket the grid step's q blocks do not divide
    "odd-q-blocks": ([(1, 7), (9, 14), (1, 0)], 40),
}


def _check(heads, dtype, seqs, q_bucket, layer=1):
    wb, off = _launch(seqs, q_bucket)
    rng = np.random.RandomState(len(seqs) + heads)
    pool = jnp.asarray(rng.randn(L, NB + 1, heads, BS, 2 * DH), dtype)
    k = jnp.asarray(rng.randn(q_bucket, heads, DH), jnp.float32)
    v = jnp.asarray(rng.randn(q_bucket, heads, DH), jnp.float32)
    want = np.asarray(_write_rows(pool, layer, wb, off, k, v))
    got = np.asarray(jax.jit(KA.kv_append, static_argnums=1)(
        pool, layer, wb, off, _kv_lanes(k, v)))
    bits = {2: np.uint16, 4: np.uint32}[want.dtype.itemsize]
    np.testing.assert_array_equal(got[:, 1:].view(bits),
                                  want[:, 1:].view(bits))
    # the scratch block is no longer written, nor any other layer
    before = np.asarray(pool)
    np.testing.assert_array_equal(got[:, 0].view(bits),
                                  before[:, 0].view(bits))
    np.testing.assert_array_equal(got[1 - layer].view(bits),
                                  before[1 - layer].view(bits))
    real = int((wb > 0).sum())
    assert real == sum(n for n, _ in seqs)
    changed = (got.view(bits) != before.view(bits)).any(axis=(2, 4))
    assert changed[layer].sum() == real     # one row a real token, no more


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("heads,dtype", [
    (12, "bfloat16"), (20, "bfloat16"), (20, "float32"),
    (3, "bfloat16"),        # a TP shard's H / mp: 12 heads over 4 devices
    (5, "float32"),         # 20 heads over 4
], ids=["h12-bf16", "h20-bf16", "h20-f32", "tp-h3-bf16", "tp-h5-f32"])
def test_kv_append_equals_write_rows(case, heads, dtype):
    _check(heads, dtype, *CASES[case])


@pytest.mark.parametrize("blocks", [3, 4, 5, 7])
def test_kv_append_with_a_short_ring(monkeypatch, blocks):
    """A pool whose blocks are fat against the budget keeps few rewrites
    in VMEM: 3 is no read ahead at all, 5 one q block. (Head counts no
    other case has: the call is jitted by shape.)"""
    heads = 5 + blocks
    monkeypatch.setattr(KA, "APPEND_VMEM_BUDGET",
                        blocks * heads * BS * 2 * DH * 4 + 1)
    assert KA.append_ring_blocks(heads, BS, DH, "float32") == blocks
    _check(heads, "float32", *CASES["mixed-past-the-ring"])


def test_append_ring_blocks_reads_the_pool_shape():
    # gpt2-large: 80 KB a block, the cap; a fat block, what the budget
    # holds; never under a q block's three
    assert KA.append_ring_blocks(20, 16, 64, "bfloat16") == \
        KA.APPEND_RING_MAX
    assert KA.append_ring_blocks(64, 32, 128, "bfloat16") == 4
    assert KA.append_ring_blocks(128, 64, 128, "float32") == 3


@pytest.mark.parametrize("bad,match", [
    (dict(dtype="int8"), "quantized"),
    (dict(rows_heads=11), "rows shape"),
    (dict(q=12), "multiple of"),
    (dict(layer=2), "out of range"),
], ids=["int8-pool", "rows-heads", "q-not-padded", "layer"])
def test_kv_append_refuses(bad, match):
    q = bad.get("q", 16)
    pool = jnp.zeros((L, NB + 1, 12, 32, 2 * DH),
                     bad.get("dtype", "bfloat16"))
    rows = jnp.zeros((q, bad.get("rows_heads", 12), 2 * DH), jnp.float32)
    with pytest.raises(ValueError, match=match):
        KA.kv_append(pool, bad.get("layer", 0), np.zeros(q, np.int32),
                     np.zeros(q, np.int32), rows)


def test_block_q_is_the_layouts():
    # at most two blocks a q block rests on block_size >= BLOCK_Q
    from paddle_tpu.ops.ragged_paged_attention import MIN_KV_BLOCK
    assert MIN_KV_BLOCK >= BLOCK_Q
