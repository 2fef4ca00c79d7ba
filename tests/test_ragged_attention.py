"""Fused ragged paged attention (ops/ragged_paged_attention.py) and the
chunked-prefill serving path over it (``GenerationEngine``, at the
kernel's smallest block size here).

Four layers of guarantees:

* **kernel parity** — the Pallas kernel (interpret mode on CPU, so the
  kernel BODY executes under tier-1) matches a full-precision numpy
  oracle on ragged mixed prefill+decode batches over randomized page
  tables, including multi-block chunks and bf16 storage;
* **engine parity** — greedy engine output is token-identical to
  per-request ``models.generate``
  under mixed concurrent churn, prefix-cache adoption, COW and
  block-pressure preemption — with ZERO retraces during the storm and a
  clean ``analyze()`` bill on the fused step (donation-safe,
  host-sync-free);
* **chunked prefill** — long prompts feed in ``prefill_budget``-token
  chunks mixed into decode launches: output stays exact, the chunk
  counters are observable in ``stats()``/the flight recorder, and the
  policy test shows decode rows advancing in the SAME cycles that chunk
  a long prompt (no cycle spends its whole budget on one prompt);
* **validation** — a Mosaic-tileable block size, fail-fast at
  construction; the removed options refused by name.
"""

import numpy as np
import pytest

from paddle_tpu.framework import monitor, trace_probe
from paddle_tpu.models import generate
from paddle_tpu.ops.ragged_paged_attention import (
    q_step_blocks, ragged_layout, ragged_paged_attention,
    reference_ragged_attention)
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.scheduler import GenerationRequest

import _toys
from _mock_serving import MockDevice, mock_pool

VOCAB = _toys.VOCAB

# the two engines the tests that only serve requests share (``engines``
# hands each out drained, its pool and trie as new): the kernel's smallest
# block, and four slots fed in chunks of at most 8 tokens
PLAIN = dict(num_slots=2, max_len=48, block_size=8)
CHUNKS = dict(num_slots=4, max_len=64, block_size=8, prefill_budget=8)


def _prompt(rng, n):
    return rng.randint(1, VOCAB, n).astype(np.int32)


# ---------------------------------------------------------------------------
# kernel-level parity (interpret mode: the kernel body runs on CPU)
# ---------------------------------------------------------------------------

def _random_ragged_case(rng, *, dtype="float32"):
    """A randomized ragged batch over a randomized page table: returns
    everything the kernel needs plus the flat oracle rows."""
    import jax.numpy as jnp

    L, H, BS, DH, S, T = 2, 3, 8, 16, 4, 4
    NB = 24
    pool = rng.randn(L, NB + 1, H, BS, 2 * DH).astype(np.float32)
    # per-seq: present?, kv_len, q_len (decode=1 or a chunk tail)
    tables = np.zeros((S, T), np.int32)
    q_lens, pos0s, kv_lens = [], [], []
    free = list(range(1, NB + 1))
    rng.shuffle(free)
    for s in range(S):
        if s == 3:                      # one absent sequence
            q_lens.append(0), pos0s.append(0), kv_lens.append(0)
            continue
        kv = int(rng.randint(1, T * BS + 1))
        q = 1 if s == 0 else int(rng.randint(1, kv + 1))  # s0 = decode
        nblk = -(-kv // BS)
        blocks = [free.pop() for _ in range(nblk)]
        tables[s, :nblk] = blocks
        q_lens.append(q)
        pos0s.append(kv - q)            # the q rows are the kv tail
        kv_lens.append(kv)
    layer = int(rng.randint(0, L))
    blk_seq, qstart, pos0, last_row, total = ragged_layout(q_lens, pos0s)
    Qp = len(blk_seq) * 8
    q = rng.randn(H, Qp, DH).astype(np.float32)
    lo = np.zeros(S, np.int32)
    out = ragged_paged_attention(
        jnp.asarray(q, dtype), jnp.asarray(pool, dtype), layer,
        blk_seq, qstart, pos0, tables, lo, np.asarray(kv_lens, np.int32))
    rows, row_seq, row_pos = [], [], []
    for s in range(S):
        for i in range(q_lens[s]):
            rows.append(q[:, qstart[s] + i, :])        # [H, Dh]
            row_seq.append(s)
            row_pos.append(pos0s[s] + i)
    q_rows = np.stack(rows)                            # [N, H, Dh]
    ref = reference_ragged_attention(
        q_rows, pool, layer, row_seq, row_pos,
        [list(t) for t in tables], lo)
    got = np.stack([np.asarray(out, np.float32)[:, qstart[s] + i, :]
                    for s in range(S) for i in range(q_lens[s])])
    return got, ref


def _walk_case(seqs, *, heads=3, bs=32, dh=16, t_len=None,
               dtype="float32", nan_at=(), seed=0, q_heads=None,
               mask_block=1, window=0, sinks=False, dv=0, lanes=0,
               free_behind=False, q_bucket=0):
    """A hand-built launch for the grouped KV walk: ``seqs`` is a list of
    ``(q_len, kv_len)`` (the q rows are the context's tail), each
    sequence's blocks drawn from a shuffled pool, its table padded with
    block 0 up to ``t_len``. ``dtype`` "int8" quantizes the pool with
    per-(block, head) scales that all differ. ``nan_at`` fills pool
    blocks with NaN after the oracle's copy is taken: ``(s, j)`` is
    sequence ``s``'s ``j``-th block, ``"scratch"`` block 0. Returns ``(got, ref, out)``: real rows
    ``[N, H, Dh]`` of the kernel and of the oracle, and the kernel's
    whole output. ``heads`` are the pool's KV heads; ``q_heads`` (a
    multiple of them: grouped-query attention) defaults to the same;
    ``mask_block`` B lets a row see to the end of its block of B;
    ``window`` W lets it see its last W columns only, and with
    ``free_behind`` every block wholly behind a sequence's first row's
    window is FREED as the pool frees it (its table entry names the
    scratch block, which is NaN, and ``lo`` is the first position still
    held); ``sinks`` draws a logit a query head; ``dv`` makes V the last
    ``dv`` lanes of a row of ``lanes`` (default ``dh + dv``) and K the
    first ``dh``; ``q_bucket`` pads the launch to that many q rows (0: to
    its content, which decides how many q blocks a grid step covers)."""
    import jax.numpy as jnp
    q_heads = q_heads or heads
    lanes = lanes or (dh + dv if dv else 2 * dh)

    rng = np.random.RandomState(seed)
    S = len(seqs)
    need = [-(-kv // bs) for _, kv in seqs]
    t_len = t_len or max(need)
    nb = sum(need) + 2
    quant = dtype == "int8"
    if quant:
        pool = rng.randint(-127, 128, (2, nb + 1, heads, bs, 2 * dh)) \
            .astype(np.int8)
        scales = (0.2 + rng.rand(2, 2, nb + 1, heads)).astype(np.float32) / 64
    else:
        pool = rng.randn(2, nb + 1, heads, bs, lanes).astype(np.float32)
        scales = None
    free = list(range(1, nb + 1))
    rng.shuffle(free)
    tables = np.zeros((S, t_len), np.int32)
    for s, n in enumerate(need):
        tables[s, :n] = [free.pop() for _ in range(n)]
    q_lens = [q for q, _ in seqs]
    pos0s = [kv - q for q, kv in seqs]
    kv_len = np.asarray([kv for _, kv in seqs], np.int32)
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, pos0s,
                                                q_bucket=q_bucket)
    q = rng.randn(q_heads, len(blk_seq) * 8, dh).astype(np.float32)
    lo = np.zeros(S, np.int32)
    if free_behind:
        for s in range(S):
            gone = max(0, pos0s[s] - window + 1) // bs
            tables[s, :gone] = 0
            lo[s] = gone * bs
        nan_at = tuple(nan_at) + ("scratch",)
    sink = rng.randn(q_heads).astype(np.float32) if sinks else None
    rows = [(s, i) for s in range(S) for i in range(q_lens[s])]
    ref = reference_ragged_attention(
        np.stack([q[:, qstart[s] + i] for s, i in rows]), pool, 1,
        [s for s, _ in rows], [pos0s[s] + i for s, i in rows],
        [list(t) for t in tables], lo, scales=scales,
        mask_block=mask_block, kv_len=kv_len, window=window, sinks=sink,
        v_lanes=dv)
    for at in nan_at:
        pool[:, 0 if at == "scratch" else tables[at]] = np.nan
    qdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    out = np.asarray(ragged_paged_attention(
        jnp.asarray(q, qdt), jnp.asarray(pool, dtype), 1, blk_seq, qstart,
        pos0, tables, lo, kv_len,
        scales=None if scales is None else jnp.asarray(scales),
        mask_block=mask_block, window=window, sinks=sink,
        v_lanes=dv).astype(jnp.float32))
    got = np.stack([out[:, qstart[s] + i] for s, i in rows])
    return got, ref, out


# what a grid step of M q blocks meets: q blocks of ONE sequence (a wide
# step), a sequence's first and last q blocks beside other rows, decode
# rows only, pad blocks
_STEP_CASES = {
    "whole-steps": [(64, 200)],
    "starts-and-ends-inside-a-step": [(1, 50), (70, 300), (1, 20)],
    "decode-rows-only": [(1, 135), (1, 40), (1, 300), (1, 77), (1, 129)],
    "pad-blocks": [(40, 170)],
}
_STEP_FORMS = {
    "g1": {"bs": 16},
    "g8-mask4-ride": {"heads": 2, "q_heads": 16, "bs": 16, "mask_block": 4,
                      "more": [(8, 40)]},
    "g16": {"heads": 1, "q_heads": 16, "bs": 16},
    "window128-sinks-lanes-freed": {
        "heads": 2, "q_heads": 16, "bs": 16, "dh": 192, "dv": 128,
        "lanes": 384, "window": 128, "sinks": True, "free_behind": True},
    "int8": {"dtype": "int8"},
}


class TestKernelParity:
    # block 32 in float32: a group is G = 4 blocks, 128 columns
    @pytest.mark.parametrize("seqs,kw", [
        ([(1, 70)], {}),                         # 3 blocks: under one group
        ([(1, 128)], {}),                        # exactly one group
        ([(1, 129)], {}),                        # one block past a group
        ([(1, 135), (9, 263)], {}),              # kv_len ends mid-block
        ([(1, 160), (1, 33)], {"t_len": 5}),     # the table fills all of T
        ([(1, 300), (1, 7), (44, 190)], {}),     # decode rows + a chunk
        ([(1, 300), (1, 7), (44, 190)], {"dtype": "bfloat16"}),
        ([(1, 200), (12, 140)], {"dtype": "int8"}),   # per-head scales
        ([(1, 135), (9, 263)], {"heads": 20, "bs": 16}),  # gpt2-large
        ([(1, 135), (9, 263)], {"heads": 5, "bs": 16}),   # its mp=4 shard
        # grouped-query heads (8 query heads a KV head, folded into the
        # rows) under the block mask of 4: a denoising block, a commit
        # beside the next block's rows, a block that kv_len cuts short,
        # a prefill chunk of whole blocks
        ([(4, 68), (8, 40), (1, 33), (44, 192)],
         {"heads": 2, "q_heads": 16, "bs": 16, "mask_block": 4}),
        ([(4, 68), (44, 192)],
         {"heads": 2, "q_heads": 16, "bs": 16, "mask_block": 4,
          "dtype": "bfloat16"}),
        # grouped heads, causal mask; one head a group, block mask
        ([(1, 135), (9, 263)], {"heads": 3, "q_heads": 6, "bs": 16}),
        ([(4, 136), (12, 264)], {"bs": 16, "mask_block": 4}),
        # Dh 128: K and V lanes are whole tiles, the products take each
        # apart (sdar-30b-a3b's shape: 4 KV heads, 8 query heads each)
        ([(4, 36), (1, 17)], {"heads": 4, "q_heads": 32, "bs": 16,
                              "dh": 128, "mask_block": 4}),
        ([(1, 40), (9, 30)], {"heads": 2, "bs": 16, "dh": 128}),
    ], ids=["under-one-group", "one-group", "one-past-a-group",
            "kv-len-mid-block", "table-fills-T", "decode-and-chunk",
            "decode-and-chunk-bf16", "int8-per-head-scales", "H20", "H5",
            "gqa8-mask4", "gqa8-mask4-bf16", "gqa2-causal", "mha-mask4",
            "gqa8-mask4-dh128", "mha-causal-dh128"])
    def test_grouped_walk_matches_oracle(self, seqs, kw):
        got, ref, _ = _walk_case(seqs, **kw)
        tol = {"bfloat16": 0.08, "int8": 2e-4}.get(kw.get("dtype"), 2e-5)
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)

    # window x sinks x lanes x group: a decode row far past its window,
    # a row whose window is not full yet, chunks that cross blocks and
    # windows, freed blocks behind the window (NaN scratch), K and V
    # lanes apart (equal-sized tiles: the zero-extended form; whole
    # 128-lane tiles with padding between: the split form at the
    # published 192 | 128 in 384)
    @pytest.mark.parametrize("seqs,kw", [
        ([(1, 300), (1, 20), (44, 190)], {"window": 32, "bs": 16}),
        ([(1, 300), (1, 20), (44, 190)],
         {"window": 32, "bs": 16, "free_behind": True}),
        ([(1, 300), (9, 130)], {"window": 33, "bs": 16, "sinks": True}),
        ([(1, 300), (9, 130)], {"window": 31, "bs": 16, "sinks": True,
                                "free_behind": True}),
        ([(1, 135), (9, 263)], {"sinks": True}),          # sinks, no window
        ([(1, 135), (9, 263)], {"bs": 16, "dh": 24, "dv": 16}),
        ([(1, 135), (9, 263)], {"bs": 16, "dh": 24, "dv": 16, "lanes": 48,
                                "window": 40, "sinks": True,
                                "free_behind": True}),
        ([(1, 200), (20, 150)],
         {"heads": 2, "q_heads": 16, "bs": 16, "window": 48,
          "sinks": True, "free_behind": True}),           # group of 8
        ([(1, 200), (20, 150)],
         {"heads": 1, "q_heads": 16, "bs": 16}),          # group of 16
        ([(1, 300), (20, 200)],
         {"heads": 2, "q_heads": 16, "bs": 16, "dh": 192, "dv": 128,
          "lanes": 384, "window": 128, "sinks": True,
          "free_behind": True}),                          # a window layer
        ([(1, 300), (20, 200)],
         {"heads": 1, "q_heads": 16, "bs": 16, "dh": 192, "dv": 128,
          "lanes": 384}),                                 # a global layer
        ([(1, 300), (20, 200)],
         {"heads": 2, "q_heads": 16, "bs": 16, "dh": 192, "dv": 128,
          "lanes": 384, "window": 128, "sinks": True,
          "free_behind": True, "dtype": "bfloat16"}),
    ], ids=["window", "window-freed", "window-sinks", "window-sinks-freed",
            "sinks", "lanes24-16", "lanes24-16-in-48-window-sinks-freed",
            "gqa8-window-sinks-freed", "gqa16", "window-layer-192-128",
            "global-layer-192-128", "window-layer-192-128-bf16"])
    def test_window_sinks_lanes_groups_match_oracle(self, seqs, kw):
        got, ref, out = _walk_case(seqs, **kw)
        tol = {"bfloat16": 0.08}.get(kw.get("dtype"), 2e-5)
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
        if kw.get("free_behind"):
            assert np.isfinite(out).all()    # a freed block is never read

    # a grid step covers M = 4 q blocks (the launches here are padded to
    # whole steps of 32 rows): the step's cases x the forms the cells use
    @pytest.mark.parametrize("form,kw", _STEP_FORMS.items(),
                             ids=list(_STEP_FORMS))
    @pytest.mark.parametrize("case,seqs", _STEP_CASES.items(),
                             ids=list(_STEP_CASES))
    def test_wide_and_mixed_steps_match_oracle(self, case, seqs, form, kw):
        kw = dict(kw)
        # the block form's launches carry a ride: a commit's rows and the
        # next block's, one q block
        seqs = seqs + kw.pop("more", [])
        rows = sum(-(-q // 8) * 8 for q, _ in seqs)
        bucket = max(-(-rows // 32) * 32, 128 if case == "pad-blocks" else 0)
        heads = kw.get("heads", 3)
        assert q_step_blocks(
            heads, kw.get("q_heads", heads) // heads, kw.get("bs", 32),
            kw.get("lanes", 0) or 2 * kw.get("dh", 16),
            kw.get("dtype", "float32"), v_lanes=kw.get("dv", 0),
            q_blocks=bucket // 8) == 4
        got, ref, out = _walk_case(seqs, q_bucket=bucket, **kw)
        tol = {"int8": 2e-4}.get(kw.get("dtype"), 2e-5)
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
        assert np.isfinite(out).all()            # pad blocks and pad rows

    @pytest.mark.parametrize("seqs,rows,past", [
        # a wide step: the chunk's rows 0..31 sit at 336..367, block 22
        # is their last; the chunk's own later blocks are poison
        ([(64, 400)], range(0, 32), 23),
        # q blocks walked one by one beside a decode row: rows 0..15 of
        # the chunk end in block 21
        ([(1, 50), (64, 400)], range(1, 17), 22),
    ], ids=["wide-step", "q-blocks-alone"])
    @pytest.mark.parametrize("kw", [
        {}, {"heads": 2, "q_heads": 16, "mask_block": 4}],
        ids=["causal", "gqa8-mask4"])
    def test_walk_stops_at_the_steps_last_row(self, seqs, rows, past, kw):
        """Blocks past the last row of a step (a wide one, or a q block
        on its own) are never fetched, whatever ``kv_len`` is: NaN there
        leaves those rows finite and equal."""
        s = len(seqs) - 1
        got, ref, _ = _walk_case(
            seqs, bs=16, nan_at=[(s, j) for j in range(past, 25)],
            q_bucket=96, **kw)
        rows = list(rows)
        assert np.isfinite(got[rows]).all()
        np.testing.assert_allclose(got[rows], ref[rows], rtol=2e-5,
                                   atol=2e-5)
        assert np.isnan(got[-1]).any()      # the last rows do see them

    @pytest.mark.parametrize("seqs,nan_at,clean", [
        # the scratch block the tables pad with is never fetched
        ([(1, 70), (9, 200)], ["scratch"], (0, 1)),
        # sequence 0's middle blocks are NaN and stay behind in both
        # group buffers: the partial groups of 1 and 2 must not see them
        ([(1, 256), (1, 40), (3, 130)], [(0, j) for j in (2, 3, 4, 5)],
         (1, 2)),
    ], ids=["nan-scratch-block", "nan-left-in-the-buffers"])
    def test_nan_outside_a_sequence_never_reaches_it(self, seqs, nan_at,
                                                     clean):
        got, ref, out = _walk_case(seqs, nan_at=nan_at)
        rows = np.concatenate([[s] * q for s, (q, _) in enumerate(seqs)])
        keep = np.isin(rows, clean)
        assert np.isfinite(got[keep]).all()
        np.testing.assert_allclose(got[keep], ref[keep], rtol=2e-5,
                                   atol=2e-5)
        if "scratch" in nan_at:
            assert np.isfinite(out).all()        # pad rows too

    def test_ragged_mixed_batches_match_oracle(self):
        rng = np.random.RandomState(3)
        for _ in range(4):
            got, ref = _random_ragged_case(rng)
            np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    def test_bf16_storage_stays_close(self):
        got, ref = _random_ragged_case(np.random.RandomState(5),
                                       dtype="bfloat16")
        np.testing.assert_allclose(got, ref, rtol=0.08, atol=0.08)

    def test_multi_block_chunk_is_causal(self):
        """A 20-row chunk spans 3 q blocks; every row must see exactly
        its own prefix — the causal-within-chunk contract chunked
        prefill relies on."""
        import jax.numpy as jnp
        rng = np.random.RandomState(7)
        H, BS, DH = 2, 8, 16
        pool = rng.randn(1, 5, H, BS, 2 * DH).astype(np.float32)
        tables = np.array([[1, 2, 3, 4]], np.int32)
        blk_seq, qstart, pos0, last_row, total = ragged_layout([20], [0])
        q = rng.randn(H, len(blk_seq) * 8, DH).astype(np.float32)
        out = ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(pool), 0, blk_seq, qstart, pos0,
            tables, np.zeros(1, np.int32), np.asarray([20], np.int32))
        q_rows = q[:, :20, :].transpose(1, 0, 2)
        ref = reference_ragged_attention(
            q_rows, pool, 0, [0] * 20, list(range(20)),
            [list(tables[0])], np.zeros(1, np.int32))
        np.testing.assert_allclose(np.asarray(out)[:, :20, :],
                                   ref.transpose(1, 0, 2),
                                   rtol=2e-5, atol=2e-5)

    def test_layout_and_validation(self):
        blk_seq, qstart, pos0, last_row, total = ragged_layout(
            [1, 0, 9], [4, 0, 2], q_bucket=32)
        np.testing.assert_array_equal(blk_seq, [0, 2, 2, -1])
        assert (qstart[0], qstart[2]) == (0, 8)
        assert (last_row[0], last_row[2]) == (0, 16)
        assert total == 10
        with pytest.raises(ValueError, match="multiple of block_q"):
            ragged_layout([1], [0], q_bucket=12)
        with pytest.raises(ValueError, match="cannot hold"):
            ragged_layout([9, 9], [0, 0], q_bucket=16)
        import jax.numpy as jnp
        pool = jnp.zeros((1, 3, 2, 4, 32))      # block_size 4 < 8
        with pytest.raises(ValueError, match="legal"):
            ragged_paged_attention(
                jnp.zeros((2, 8, 16)), pool, 0, np.zeros(1, np.int32),
                np.zeros(1, np.int32), np.zeros(1, np.int32),
                np.zeros((1, 1), np.int32), np.zeros(1, np.int32),
                np.zeros(1, np.int32))


# ---------------------------------------------------------------------------
# the hand-over (PR 49): a walk's first group is started from the last
# trip of the walk before it
# ---------------------------------------------------------------------------

# a launch of 16 q blocks laid out by hand, an entry a q block: None is a
# pad block, (s, kv) a DECODE row of sequence s whose context is kv
# tokens, (s, kv, k, n) the k-th of the n q blocks of sequence s's chunk
# (the chunk's last row is the context's last). Blocks of 32 tokens in
# groups of G = 4: a group is 128 tokens
def _chunk(s, kv, n):
    return [(s, kv, k, n) for k in range(n)]


_HANDOVER_LAYOUTS = {
    # contexts that end on a group border, and one token past it
    "group-border": [(0, 128), (1, 129), (2, 256), (3, 257)] + [None] * 12,
    # consecutive walks of 1, 2, 3, 2, 1, 4 groups: the buffer a walk
    # begins on follows from the walk before it
    "odd-and-even-walks": [(0, 100), (1, 200), (2, 300), (3, 250), (4, 30),
                           (5, 500)] + [None] * 10,
    # real blocks followed by pads: nothing is started for them
    "pads-after-real": [(0, 140), (1, 270)] + [None] * 14,
    # the only real block is the call's last
    "only-the-last-is-real": [None] * 15 + [(0, 300)],
    # a pad block between real ones (inside a step, and a whole pad step)
    "pad-between-real": [(0, 140), None, (1, 300), (2, 60)] + [None] * 4
    + [(3, 129), (4, 257)] + [None] * 6,
    # decode rows -> a chunk whose middle q blocks are two WIDE steps ->
    # decode rows: from _each into _wide and back, across grid steps
    "each-wide-each": [(0, 200), (1, 129)] + _chunk(2, 470, 11)
    + [(3, 300), (4, 100), None],
    # a wide step first, and a wide step last
    "wide-first-and-last": _chunk(0, 380, 4) + [(1, 257), (2, 90), None,
                                               None] + _chunk(3, 512, 8),
}
_HANDOVER_FORMS = {
    "plain": {},
    "window": {"window": 150, "free_behind": True},
    "mask4": {"mask_block": 4},
    "gqa4": {"heads": 2, "q_heads": 8},
    "gqa5": {"heads": 1, "q_heads": 5},
    "sinks": {"sinks": True},
    "int8": {"dtype": "int8"},
    # NaN in the scratch block and in every row past a context's end
    "nans": {"nan_rows": True},
}


def _handover_case(layout, *, heads=3, q_heads=None, dtype="float32",
                   window=0, free_behind=False, mask_block=1, sinks=False,
                   nan_rows=False, seed=0):
    """The kernel's operands for a hand-laid ``layout`` (see
    ``_HANDOVER_LAYOUTS``) over a pool of 96 blocks of 32 tokens, a
    sequence's table 16 entries wide: ``(call, check)`` — ``call()`` runs
    the kernel and returns its whole output, ``check(out)`` holds the
    real rows to the oracle, the pad blocks to zeros and everything to
    finite."""
    import jax.numpy as jnp
    bs, dh, t_len, nb, S = 32, 16, 16, 96, 6
    q_heads = q_heads or heads
    rng = np.random.RandomState(seed)
    quant = dtype == "int8"
    if quant:
        pool = rng.randint(-127, 128, (2, nb + 1, heads, bs, 2 * dh)) \
            .astype(np.int8)
        scales = (0.2 + rng.rand(2, 2, nb + 1, heads)).astype(np.float32) / 64
    else:
        pool = rng.randn(2, nb + 1, heads, bs, 2 * dh).astype(np.float32)
        scales = None
    blk_seq = np.full(len(layout), -1, np.int32)
    qstart, pos0 = np.zeros(S, np.int32), np.zeros(S, np.int32)
    kv_len, lo = np.zeros(S, np.int32), np.zeros(S, np.int32)
    rows = []                               # (q row, sequence, position)
    for b, entry in enumerate(layout):
        if entry is None:
            continue
        s, kv, k, n = entry + (0, 1)[len(entry) - 2:]
        blk_seq[b] = s
        kv_len[s] = kv
        # a decode row is its q block's only real row; a chunk of n q
        # blocks is 8 n - 3 rows, so its last q block ends in 3 pad rows
        q_len = 1 if len(entry) == 2 else 8 * n - 3
        if k == 0:
            qstart[s], pos0[s] = 8 * b, kv - q_len
        for r in range(8):
            if 8 * k + r < q_len:
                rows.append((8 * b + r, s, pos0[s] + 8 * k + r))
    free = list(range(1, nb + 1))
    rng.shuffle(free)
    tables = np.zeros((S, t_len), np.int32)
    for s in range(S):
        need = -(-int(kv_len[s]) // bs)
        tables[s, :need] = [free.pop() for _ in range(need)]
        if free_behind:
            gone = max(0, int(pos0[s]) - window + 1) // bs
            tables[s, :gone] = 0
            lo[s] = gone * bs
    q = rng.randn(q_heads, 8 * len(layout), dh).astype(np.float32)
    sink = rng.randn(q_heads).astype(np.float32) if sinks else None
    at, seq, pos = (np.asarray(c) for c in zip(*rows))
    ref = reference_ragged_attention(
        np.stack([q[:, r] for r in at]), pool, 1, seq, pos,
        [list(t) for t in tables], lo, scales=scales, mask_block=mask_block,
        kv_len=kv_len, window=window, sinks=sink)
    if nan_rows or free_behind:
        pool[:, 0] = np.nan                 # the scratch block
    if nan_rows:
        for s in range(S):
            end = int(kv_len[s])
            if end % bs:
                pool[:, tables[s, end // bs], :, end % bs:] = np.nan
    operands = (jnp.asarray(q), jnp.asarray(pool, dtype), 1, blk_seq, qstart,
                pos0, tables, lo, kv_len)
    kw = dict(scales=None if scales is None else jnp.asarray(scales),
              mask_block=mask_block, window=window, sinks=sink)

    def check(out):
        assert np.isfinite(out).all()
        tol = 2e-4 if quant else 2e-5
        np.testing.assert_allclose(out[:, at].transpose(1, 0, 2), ref,
                                   rtol=tol, atol=tol)
        for b in np.flatnonzero(blk_seq < 0):
            assert not out[:, 8 * b:8 * b + 8].any()

    counts = dict(t_len=t_len, block_size=bs, mask_block=mask_block,
                  window=window)
    return (lambda: np.asarray(ragged_paged_attention(*operands, **kw))), \
        check, (blk_seq, qstart, pos0, lo, kv_len), counts


def _logging_tpu(real_tpu, events):
    """A stand-in for the kernel module's ``pltpu`` whose async copies
    append ``(what, buffer, block of the group)`` to ``events`` when they
    are started and waited for — at run time, in the order the
    interpreted kernel does it: every event sits in a trip of a loop
    that carries the semaphores' state."""
    import jax

    class Logged:
        def __init__(self, copy, at):
            self.copy, self.at = copy, at

        def _note(self, what):
            jax.debug.callback(
                lambda slot, g: events.append((what, int(slot), int(g))),
                *self.at)

        def start(self):
            self._note("start")
            self.copy.start()

        def wait(self):
            self._note("wait")
            self.copy.wait()

    class LoggingTpu:
        def __getattr__(self, name):
            return getattr(real_tpu, name)

    tpu = LoggingTpu()
    tpu.make_async_copy = lambda src, dst, sem: Logged(
        real_tpu.make_async_copy(src, dst, sem), sem.transforms[-1].indices)
    return tpu


@pytest.fixture(scope="class")
def dma_log():
    """Every DMA the interpreted kernel starts and waits for, in order:
    the kernel module's ``pltpu`` is ``_logging_tpu`` for the class's
    tests, and the jitted call's cache is dropped on both sides (traces
    made with the proxy are no other test's)."""
    from paddle_tpu.ops import ragged_paged_attention as rpa
    events = []
    patch = pytest.MonkeyPatch()
    patch.setattr(rpa, "pltpu", _logging_tpu(rpa.pltpu, events))
    rpa._rpa_call.clear_cache()
    yield events
    patch.undo()
    rpa._rpa_call.clear_cache()


class TestHandOver:
    @pytest.mark.parametrize("form", _HANDOVER_FORMS)
    @pytest.mark.parametrize("layout", _HANDOVER_LAYOUTS)
    def test_the_handed_walk_against_the_oracle(self, dma_log, layout, form):
        """Each case twice: the second call runs on what the first left
        behind (nothing may be: no copy outstanding, no semaphore
        signalled) and has to give the same bits. And the DMAs of the
        first call, in order: every wait finds its copy started, nothing
        is started twice, nothing is left; the blocks fetched and the
        groups waited for are ``ragged_walk_counts``'s — what the parent
        fetched — and so are the groups that were NOT in flight before
        the group before them was waited for: a walk's own first group,
        ``kv_walks - kv_walks_handed`` of them."""
        import jax

        from paddle_tpu.ops.ragged_paged_attention import (
            kv_group_blocks, ragged_walk_counts)
        kw = _HANDOVER_FORMS[form]
        call, check, meta, counts = _handover_case(
            _HANDOVER_LAYOUTS[layout], seed=len(layout) + len(form), **kw)
        del dma_log[:]
        out = call()
        jax.effects_barrier()
        events = list(dma_log)
        check(out)
        np.testing.assert_array_equal(call(), out)
        jax.effects_barrier()
        assert dma_log[len(events):] == events
        heads = kw.get("heads", 3)
        group = kv_group_blocks(heads, 32, 16, kw.get("dtype", "float32"))
        assert group == 4
        want = ragged_walk_counts(
            *meta[:3], meta[3], meta[4], step_blocks=4, group=group,
            **counts)
        in_flight, waited, exposed = {}, 0, 0
        for what, slot, g in events:
            if what == "start":
                assert (slot, g) not in in_flight, "started twice"
                # a group is known by its first block: the groups waited
                # for before it was started
                in_flight[slot, g] = waited if g == 0 \
                    else in_flight[slot, 0]
            else:
                assert (slot, g) in in_flight, "waited for, never started"
                began = in_flight.pop((slot, g))
                if g == 0:
                    # started after the group before it was waited for:
                    # nothing hid its latency
                    exposed += began == waited
                    waited += 1
        assert not in_flight, "a copy is outstanding when the call returns"
        assert sum(w == "start" for w, _, _ in events) == want["kv_steps"]
        assert waited == want["kv_fetches"]
        assert exposed == want["kv_walks"] - want["kv_walks_handed"]

    @pytest.mark.parametrize("layout,walks,handed", [
        ("group-border", 4, 3), ("odd-and-even-walks", 6, 5),
        ("pads-after-real", 2, 1), ("only-the-last-is-real", 1, 0),
        ("pad-between-real", 5, 2), ("each-wide-each", 9, 8),
        ("wide-first-and-last", 5, 3),
    ])
    def test_the_counter_follows_the_kernels_rule(self, layout, walks,
                                                  handed):
        """``kv_walks`` / ``kv_walks_handed`` on the layouts above, by
        hand: every walk but the call's first and one after a pad block."""
        from paddle_tpu.ops.ragged_paged_attention import ragged_walk_counts
        _, _, meta, counts = _handover_case(_HANDOVER_LAYOUTS[layout])
        got = ragged_walk_counts(*meta[:3], meta[3], meta[4], step_blocks=4,
                                 group=4, **counts)
        assert (got["kv_walks"], got["kv_walks_handed"]) == (walks, handed)


def _equations(jaxpr):
    """Equations of a jaxpr, those of every jaxpr nested in them too."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _equations(sub)
    return n


@pytest.mark.parametrize("q_heads,kv_heads,q_rows,window,at_most", [
    (20, 20, 512, 0, 510),         # gpt2-large's plain launch: 378 at PR 47
    (32, 8, 1024, 0, 540),         # 32 heads on 8 KV heads of 64: 400
    (32, 8, 1024, 128, 556),       # the same under a window of 128: 434
], ids=["gpt2-large-q512", "gqa4-q1024", "gqa4-q1024-window128"])
def test_the_kernels_text_stays_small(q_heads, kv_heads, q_rows, window,
                                      at_most):
    """What this protects is ``setup_s``: every ``(Q, T)`` step program of
    every warm-up traces this kernel and lowers its text to Mosaic, cache
    warm or not — PR 48's unrolled DMA starts cost +4-6 s of every warm-up
    of ``gpt2-large.decode`` and the PR with it. The equations of
    ``jax.make_jaxpr(ragged_paged_attention)``, counted through every
    nested jaxpr at three of the cells' shapes, stay under 1.35 x what
    PR 47's kernel counted (378 | 400 | 412 by the issue's count; 452 |
    474 | 496 with the hand-over of PR 49)."""
    import jax
    import jax.numpy as jnp
    S, T = 64, 64
    meta = (np.zeros(q_rows // 8, np.int32), np.zeros(S, np.int32),
            np.zeros(S, np.int32), np.zeros((S, T), np.int32),
            np.zeros(S, np.int32), np.ones(S, np.int32))
    jaxpr = jax.make_jaxpr(lambda q, pool: ragged_paged_attention(
        q, pool, 0, *meta, window=window))(
            jax.ShapeDtypeStruct((q_heads, q_rows, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 9, kv_heads, 16, 128), jnp.bfloat16))
    assert 300 < _equations(jaxpr.jaxpr) <= at_most


# ---------------------------------------------------------------------------
# fused engine parity: engine == generate, one trace a bucket, clean
# analysis — the acceptance criterion
# ---------------------------------------------------------------------------

class TestFusedEngineParity:
    def test_single_request_matches_generate(self, served_model, engines):
        p = _prompt(np.random.RandomState(1), 7)
        out = engines(served_model, **PLAIN) \
            .submit(p, max_new_tokens=8).result(timeout=300)
        ref = generate(served_model, p[None, :], max_new_tokens=8)
        np.testing.assert_array_equal(out, ref.numpy()[0])

    def test_eos_early_stop_matches_generate(self, served_model, engines):
        p = _prompt(np.random.RandomState(3), 6)
        ref8 = generate(served_model, p[None, :], max_new_tokens=8)
        eos = int(ref8.numpy()[0, 6 + 2])
        ref = generate(served_model, p[None, :], max_new_tokens=8,
                       eos_token_id=eos, pad_token_id=0)
        out = engines(served_model, **PLAIN) \
            .submit(p, max_new_tokens=8, eos_token_id=eos) \
            .result(timeout=300)
        np.testing.assert_array_equal(out, ref.numpy()[0])

    def test_prefix_hit_cow_and_preemption_interleavings(
            self, served_model):
        """Shared system prompt + block pressure: later requests adopt
        the cached prefix blocks (fused takes the hit at ANY tail
        length — chunks drain long tails, no replay cliff), growth under
        a halved block budget preempts the youngest, and every output
        stays token-exact."""
        eng = GenerationEngine(served_model, num_slots=4, max_len=32,
                               block_size=8,
                               num_blocks=8)
        rng = np.random.RandomState(5)
        system = _prompt(rng, 16)        # two full cacheable blocks
        tails = [_prompt(rng, n) for n in (3, 1, 6, 10)]
        prompts = [np.concatenate([system, t]) for t in tails]
        first = eng.submit(prompts[0], max_new_tokens=6).result(timeout=300)
        assert eng._pool.prefix_hits == 0
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        outs = [h.result(timeout=600) for h in handles]
        stats = eng.stats()
        eng.close()
        # every hit is adopted, the 10-token tail's too
        assert eng._pool.prefix_hits >= 3
        assert stats["prefill_tokens_saved"] >= 3 * 16
        for p, out in zip(prompts, [first] + outs):
            ref = generate(served_model, p[None, :], max_new_tokens=6)
            np.testing.assert_array_equal(out, ref.numpy()[0])

    def test_block_pressure_preempts_and_stays_exact(self, served_model):
        eng = GenerationEngine(served_model, num_slots=2, max_len=32,
                               block_size=8,
                               num_blocks=4)
        pa = _prompt(np.random.RandomState(6), 4)
        pb = _prompt(np.random.RandomState(7), 4)
        ha = eng.submit(pa, max_new_tokens=24)
        hb = eng.submit(pb, max_new_tokens=24)
        oa, ob = ha.result(timeout=600), hb.result(timeout=600)
        stats = eng.stats()
        eng.close()
        assert stats["preempts"] >= 1
        np.testing.assert_array_equal(
            oa, generate(served_model, pa[None, :],
                         max_new_tokens=24).numpy()[0])
        np.testing.assert_array_equal(
            ob, generate(served_model, pb[None, :],
                         max_new_tokens=24).numpy()[0])
        assert eng._pool.blocks_in_use == 0

    def test_warm_buckets_serve_with_zero_retraces(self, served_model,
                                                   engines):
        """The deterministic zero-retrace assertion: a request identical
        in shape class to one already served reuses every fused (q,
        table) bucket program — no new trace anywhere, and the
        dispatch/retrace_cause counters stay untouched."""
        eng = engines(served_model, **PLAIN)
        rng = np.random.RandomState(4)
        eng.submit(_prompt(rng, 7), max_new_tokens=8).result(timeout=300)
        retrace0 = monitor.stat_get("dispatch/retrace_cause")
        sites0 = {k: v["traces"]
                  for k, v in trace_probe.snapshot().items()
                  if k.startswith("serving/fused") and f"#{eng._eid}" in k}
        assert sites0
        out = eng.submit(_prompt(rng, 7), max_new_tokens=8) \
                 .result(timeout=300)
        assert out.shape == (15,)
        assert monitor.stat_get("dispatch/retrace_cause") == retrace0
        sites1 = {k: v["traces"]
                  for k, v in trace_probe.snapshot().items()
                  if k.startswith("serving/fused") and f"#{eng._eid}" in k}
        assert sites1 == sites0

    def test_sampled_and_greedy_share_one_bucket_trace(self, served_model):
        eng = GenerationEngine(served_model, num_slots=4, max_len=48,
                               block_size=8)
        rng = np.random.RandomState(8)
        g = eng.submit(_prompt(rng, 6), max_new_tokens=5)
        s = eng.submit(_prompt(rng, 6), max_new_tokens=5, do_sample=True,
                       temperature=0.7)
        o1, o2 = g.result(timeout=300), s.result(timeout=300)
        eng.close()
        assert o1.shape == o2.shape == (11,)
        assert ((0 <= o2) & (o2 < VOCAB)).all()
        sites = {k: v for k, v in trace_probe.snapshot().items()
                 if k.startswith("serving/fused") and f"#{eng._eid}" in k}
        assert sites
        for name, rec in sites.items():
            assert rec["traces"] == 1, (name, rec)


# ---------------------------------------------------------------------------
# chunked prefill: budget-bounded feeding, observable, non-starving
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_long_prompt_chunks_within_budget_and_stays_exact(
            self, served_model, engines):
        eng = engines(served_model, **CHUNKS)
        since, before = eng._sched._cycle, eng.stats()
        p = _prompt(np.random.RandomState(9), 40)
        h = eng.submit(p, max_new_tokens=4)
        out = h.result(timeout=600)
        _toys.settle(eng)
        stats = eng.stats()
        rec = eng.dump_flight_recorder()
        ref = generate(served_model, p[None, :], max_new_tokens=4)
        np.testing.assert_array_equal(out, ref.numpy()[0])
        # 40 feed tokens at an 8-token budget: >= 5 chunk launches,
        # visible in stats() and in the flight recorder's cycle ring
        assert stats["prefill_chunks"] - before["prefill_chunks"] >= 5
        assert stats["chunked_prefill_tokens"] \
            - before["chunked_prefill_tokens"] == 40
        assert stats.get("chunked_prefill_tokens_per_sec", 0) > 0
        chunk_cycles = [c for c in rec["cycles"]
                        if c.get("chunk_tokens", 0) > 0
                        and c["cycle"] > since]
        assert chunk_cycles
        assert max(c["chunk_tokens"] for c in chunk_cycles) <= 8
        # the request trace carries the per-chunk marks and the
        # completion mark that separates feeding from decoding
        assert h.trace.count("prefill_chunk") >= 5
        assert h.trace.t("chunked_prefill_done") is not None

    def test_long_prompt_does_not_starve_decode(self, served_model,
                                                engines):
        """The anti-starvation policy: while a 40-token prompt is being
        chunk-fed at an 8-token budget, the already-decoding request
        keeps emitting IN THE SAME cycles — no cycle spends its whole
        budget on the prompt alone (the prompt-burst monopoly a
        whole-prompt prefill at admission could not avoid)."""
        eng = engines(served_model, **CHUNKS)
        since = eng._sched._cycle
        short = eng.submit(_prompt(np.random.RandomState(10), 4),
                           max_new_tokens=40)
        it = short.stream()
        next(it)                        # short is decoding now
        long_h = eng.submit(_prompt(np.random.RandomState(11), 40),
                            max_new_tokens=2)
        long_h.result(timeout=600)
        short.cancel()
        with pytest.raises(Exception):
            for _ in it:
                pass
        _toys.settle(eng)
        rec = eng.dump_flight_recorder()
        chunk_cycles = [c for c in rec["cycles"]
                        if c.get("chunk_tokens", 0) > 0
                        and c["cycle"] > since]
        assert len(chunk_cycles) >= 5
        # every chunk cycle also advanced decode: emitted >= 1
        assert all(c["emitted"] >= 1 for c in chunk_cycles), chunk_cycles
        assert max(c["chunk_tokens"] for c in chunk_cycles) <= 8

    def test_launch_counters_equal_the_kernels_dmas(self, served_model,
                                                    monkeypatch):
        """``kv_steps`` / ``kv_fetches`` / ``q_blocks_wide`` of the launch
        records are what the kernel does: every block DMA the
        interpret-mode kernel starts on the engine's own layouts, and
        every group it waits for (a group's first block is semaphore 0
        of its buffer), are counted at run time and compared, and the
        wide steps are read off each launch's ``blk_seq``."""
        import jax

        from paddle_tpu.ops import ragged_paged_attention as rpa
        events, launches = [], []
        # the kernel module alone: kv_append's copies are not the walk's
        monkeypatch.setattr(rpa, "pltpu", _logging_tpu(rpa.pltpu, events))
        # the kernel's call is a jitted function of its own: traces made
        # before this test do not count, and no later test may find these
        rpa._rpa_call.clear_cache()
        eng = GenerationEngine(served_model, num_slots=4, max_len=64,
                               block_size=8, prefill_budget=56)
        note = eng._sched.note_launch
        real_ops = eng._ragged_operands

        def ragged_operands(*a, **kw):
            out = real_ops(*a, **kw)
            launches[-1]["blk_seq"] = np.asarray(out[2][4])
            return out

        monkeypatch.setattr(
            eng._sched, "note_launch",
            lambda **kw: (launches.append(dict(kw)), note(**kw))[1])
        monkeypatch.setattr(eng, "_ragged_operands", ragged_operands)
        short = eng.submit(_prompt(np.random.RandomState(10), 4),
                           max_new_tokens=12)
        it = short.stream()
        next(it)                        # a decode row beside the chunks
        long_h = eng.submit(_prompt(np.random.RandomState(11), 60),
                            max_new_tokens=3)
        long_h.result(timeout=600)
        short.result(timeout=600)
        eng.close()
        jax.effects_barrier()
        rpa._rpa_call.clear_cache()
        layers = 2
        starts = sum(what == "start" for what, _, _ in events)
        assert starts == len(events) - starts \
            == layers * sum(r["kv_steps"] for r in launches)
        assert sum(what == "wait" and g == 0 for what, _, g in events) \
            == layers * sum(r["kv_fetches"] for r in launches)
        wide = 0
        for r in launches:
            blocks = r["blk_seq"]
            assert r["q_blocks"] == (blocks >= 0).sum()
            m = min(4, len(blocks))
            steps = blocks.reshape(-1, m)
            one = (steps[:, :1] >= 0) & (steps == steps[:, :1])
            n = m * int(one.all(axis=1).sum()) if m > 1 else 0
            assert r["q_blocks_wide"] == n
            wide += n
        # the 56-row chunk is q blocks 1..7 of its launch: 4..7 a step
        assert wide == 4

    def test_chunk_plan_policy_mock_scheduler(self):
        """Deterministic mock-device policy check (no model): the chunk
        plan gives every decode slot its row unconditionally and splits
        the token budget FCFS among feeding slots."""
        dev = MockDevice(mock_pool(slots=4), token=7)
        sched = dev.scheduler(prefill_budget=6)
        a = sched.submit(GenerationRequest(np.ones(4, np.int32), 8))
        a.result(timeout=60)
        b = sched.submit(GenerationRequest(np.ones(20, np.int32), 1))
        c = sched.submit(GenerationRequest(np.ones(20, np.int32), 1))
        b.result(timeout=60)
        c.result(timeout=60)
        sched.close()
        assert sched.prefill_chunks >= 7     # 4 + 20 + 20 tokens / 6
        assert sched.chunk_tokens == 44
        # no launch ever fed more than the budget, and whenever a
        # decode row existed it was in the launch too
        for plan in dev.launches:
            fed = sum(n for n in plan.values() if n > 1)
            assert fed <= 6
        # FCFS: b (older) finished its feed no later than c
        tb = b.trace.t("chunked_prefill_done")
        tc = c.trace.t("chunked_prefill_done")
        assert tb is not None and tc is not None and tb <= tc


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

class TestFusedValidation:
    def test_fused_requires_paged_layout(self, served_model):
        with pytest.raises(ValueError, match="paged"):
            GenerationEngine(served_model, num_slots=2, max_len=32,
                             kv_layout="dense")

    def test_fused_requires_tileable_block_size(self, served_model):
        with pytest.raises(ValueError, match="block_size"):
            GenerationEngine(served_model, num_slots=2, max_len=32,
                             block_size=4)

    def test_unknown_attention_rejected(self, served_model):
        with pytest.raises(ValueError, match="attention"):
            GenerationEngine(served_model, num_slots=2, max_len=32,
                             block_size=8,
                             attention="flash")

    def test_fused_admits_prompts_the_bucket_ladder_rejects(
            self, served_model, engines):
        """No prefill buckets: a feed whose pow2 bucket would overshoot
        a non-pow2 max_len chunks through the ragged step like any
        other; ``prompt + max_new <= max_len`` is the only bound."""
        out = engines(served_model, **PLAIN) \
            .submit(np.ones(33, np.int32), max_new_tokens=1) \
            .result(timeout=300)
        assert out.shape == (34,)
