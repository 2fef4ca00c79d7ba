"""The tower on the launch's real rows (PR 41): a step program's per-row
work runs on ``R <= Q`` TOWER rows that hold the slots' real rows back to
back, and the attention kernel's ``Q`` rows — each slot's padded to whole
q blocks — exist only around the kernel call
(``models/generation.py:_row_axes``, ``ops/ragged_paged_attention.py``
Layout contract, ``serving/engine.py:_tower_rows``).

* the rule ``R(Q)``: its values at the benchmark's configurations, what it
  promises for every ``Q``, and that every plan ``_chunk_plan`` can make
  fits a bucket the engine had before;
* a launch that mixes prompt chunks, decode rows and an absent slot gives,
  through the compact step, the tokens, the pool and the state the padded
  layout gives — the padded twin is the SAME engine code with ``R == Q``
  forced (a family at a time in ``tests/test_compact_tower_twins.py``;
  the verify step, int8 blocks and block generation here);
* where ``R(Q) == Q`` nothing moves: block generation of 8 rows a slot,
  a ``mesh=`` engine.

The toy engines hold a few slots, so the rule's row multiple (128: whole
MXU passes) is set to 8 here; nothing else differs from a served engine.
"""
import types

import numpy as np
import pytest

import paddle_tpu.ops.ragged_paged_attention as rpa
from paddle_tpu.ops.ragged_paged_attention import BLOCK_Q, tower_rows
import _toys

# (slots, chunk budget, most real rows a decode slot holds) of the
# configurations of BENCHMARK.json (configs/*.json `serving`, the traffic
# files' `slots`; sdar's block of 4 rides two at a time)
CONFIGS = {
    "gpt2-large": (64, 512, 1),
    "gpt2-124m": (64, 512, 1),
    "axk1-ep16": (128, 1024, 1),
    "sdar-30b-a3b-pp8": (128, 1024, 8),
    "mimo-v2-flash-ep16": (128, 1024, 1),
    "falcon-h1-34b-pp12": (64, 1024, 1),
}


def _pow2(rows):
    b = BLOCK_Q
    while b < rows:
        b *= 2
    return b


def _padded(n):
    return -(-n // BLOCK_Q) * BLOCK_Q


# -- the rule ------------------------------------------------------------------

@pytest.mark.parametrize("q,slots,budget,rows,want", [
    (512, 64, 1024, 1, 128),        # falcon-h1 / gpt2-large: a plain launch
    (2048, 64, 1024, 1, 1152),      # falcon-h1: decode rows beside a chunk
    (2048, 128, 1024, 1, 1152),     # axk1 / mimo: the same at 128 slots
    (1024, 128, 1024, 1, 128),      # axk1: a plain launch
    (1024, 64, 512, 1, 640),        # gpt2-large: decode rows beside a chunk
    (1024, 64, 1024, 1, 1024),      # falcon-h1: between the two, one axis
    (1024, 128, 1024, 8, 1024),     # sdar: blocks of 8 rows fill the kernel's
    (2048, 128, 1024, 8, 2048),
], ids=lambda v: str(v))
def test_tower_rows_at_the_benchmarks_configurations(q, slots, budget, rows,
                                                     want):
    assert tower_rows(q, slots, budget, rows) == want


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tower_rows_never_pass_the_kernels_and_grow_with_them(name):
    S, C, d = CONFIGS[name]
    last = 0
    for k in range(3, 14):
        Q = 1 << k
        R = tower_rows(Q, S, C, d)
        assert BLOCK_Q <= R <= Q
        assert R == Q or R % rpa.TOWER_ROW_MULTIPLE == 0
        assert R >= last                      # monotone in Q
        last = R
    # far enough up, every slot's decode rows and a whole chunk fit
    assert last >= S * d + C


def _stub_engine(S, C, d):
    """What ``GenerationEngine._tower_rows`` / ``_launch_bucket`` read of
    an engine, and nothing else: the real methods on a namespace."""
    from paddle_tpu.serving import GenerationEngine
    B = d // 2 if d > 1 else 1
    stub = types.SimpleNamespace(
        _mesh=None, _chunk_budget=C, _spec=False, _spec_k=0,
        _pool=types.SimpleNamespace(num_slots=S),
        _decoder_spec=types.SimpleNamespace(
            generation=types.SimpleNamespace(block_length=B)))
    for name in ("_q_bucket", "_decode_rows", "_tower_rows",
                 "_launch_bucket"):
        setattr(stub, name, types.MethodType(
            getattr(GenerationEngine, name), stub))
    return stub


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_plan_fits_a_bucket_the_engine_had(name):
    """``_chunk_plan`` hands every decode slot its rows and shares at most
    the budget out between the feeding slots: whatever it makes, the
    bucket the launch gets holds its padded AND its real rows, is never
    smaller than the parent's and never larger than the parent's largest
    (no program is added)."""
    S, C, d = CONFIGS[name]
    eng = _stub_engine(S, C, d)
    rng = np.random.default_rng(41)
    # the parent's largest bucket: S - 1 decode slots beside a budget of
    # chunk rows in one slot, or one row more in each of several
    largest = _pow2((S - 1) * _padded(d) + _padded(C))
    plans = [([d] * S, []), ([], [C]), ([d] * (S - 1), [C]),
             ([], [1] * min(S, C)), ([d], [C - 1, 1] if C > 1 else [C])]
    for _ in range(400):
        n_dec = int(rng.integers(0, S + 1))
        n_feed = int(rng.integers(0, S - n_dec + 1))
        left, chunks = C, []
        for _ in range(n_feed):
            if left < 1:
                break
            n = int(rng.integers(1, left + 1))
            chunks.append(n)
            left -= n
        # under block generation a decode slot holds B or 2 B rows
        dec = [int(rng.choice([d, max(1, d // 2)])) for _ in range(n_dec)]
        plans.append((dec, chunks))
    for dec, chunks in plans:
        rows = dec + chunks
        if not rows:
            continue
        padded, real = sum(_padded(n) for n in rows), sum(rows)
        Q = eng._launch_bucket(padded, real)
        assert Q >= _pow2(padded) and Q >= padded
        assert eng._tower_rows(Q) >= real
        assert Q <= max(largest, _pow2(padded))
        # only a launch whose real rows pass R of its own bucket moves up
        if eng._tower_rows(_pow2(padded)) >= real:
            assert Q == _pow2(padded)


def test_a_plan_no_program_holds_is_refused():
    eng = _stub_engine(4, 16, 1)
    with pytest.raises(ValueError, match="fits no program"):
        eng._launch_bucket(4096, 4096)


# -- the compact step against its padded twin ----------------------------------

@pytest.fixture
def small_multiple(monkeypatch):
    monkeypatch.setattr(rpa, "TOWER_ROW_MULTIPLE", 8)


def test_the_row_axes_of_a_launch(small_multiple):
    """``_row_axes`` from the kernel's metadata alone: slots' real rows
    back to back, pad kernel rows read past the end, pad tower rows are
    nobody's."""
    import jax.numpy as jnp

    from paddle_tpu.models.generation import _row_axes
    q_lens, pos0s = [5, 0, 1, 9], [3, 0, 7, 0]
    blk_seq, qstart, pos0, _, _ = rpa.ragged_layout(q_lens, pos0s,
                                                    q_bucket=32)
    kv_len = np.asarray(pos0s) + np.asarray(q_lens)
    args = [jnp.asarray(v, jnp.int32) for v in (blk_seq, qstart, pos0,
                                                kv_len)]
    assert _row_axes(32, *args) is None          # one axis: nothing traced
    ax = _row_axes(16, *args)
    assert np.asarray(ax.start).tolist() == [0, 5, 5, 6]
    assert np.asarray(ax.row_seq).tolist() == [0] * 5 + [2] + [3] * 9 + [4]
    assert np.asarray(ax.from_kernel).tolist() == \
        [0, 1, 2, 3, 4, 8] + list(range(16, 25)) + [0]
    want = np.full(32, 16)
    want[0:5], want[8], want[16:25] = np.arange(5), 5, np.arange(6, 15)
    assert np.asarray(ax.to_kernel).tolist() == want.tolist()
    with pytest.raises(ValueError, match="at most the kernel's rows"):
        _row_axes(40, *args)


# -- where R == Q nothing moves ------------------------------------------------

def test_blocks_of_eight_rows_fill_the_kernels_rows(small_multiple):
    """sdar's toy: a decode slot holds B = 4 or 2 B = 8 rows, so two
    slots' blocks are the 16 kernel rows and a chunk the rest — every
    bucket the engine launches traces the one-axis program."""
    from paddle_tpu.serving import GenerationEngine
    eng = GenerationEngine(_toys.default("sdar"), num_slots=2, max_len=32,
                           block_size=8)
    try:
        assert eng._decoder_spec.generation.block_length == 4
        for Q in (8, 16, 32):
            assert eng._tower_rows(Q) == Q
            ops = eng._null_step_operands(Q, 4)
            assert ops[0].shape == (Q,)                   # token_ids
            assert ops[4].shape == (Q // BLOCK_Q,)        # blk_seq
    finally:
        eng.close()


def test_block_generation_on_fewer_tower_rows_is_the_padded_one(
        small_multiple):
    """Where blocks do NOT fill the kernel's rows (a few active slots of
    many) the block step runs on its own axis too: same tokens, fixed in
    the same order, as the padded twin."""
    from paddle_tpu.serving import GenerationEngine
    net = _toys.default("sdar")
    outs = []
    for twin in (False, True):
        eng = GenerationEngine(net, num_slots=4, max_len=64, block_size=16,
                               prefill_budget=16)
        try:
            if twin:
                eng._tower_rows = lambda Q: int(Q)
            else:
                assert eng._tower_rows(32) == 32 and \
                    eng._tower_rows(64) == 48
            # three short prompts and a long one behind them: its chunks
            # ride beside three slots' blocks (24 + 16 rows: Q 64, R 48);
            # no context passes two cache blocks of 16 (tables of 1 and 2)
            hs = [eng.submit((np.arange(n) * 5 + 1) % 40 + 2, 8)
                  for n in (9, 6, 11, 24)]
            outs.append([[int(t) for t in h.stream()] for h in hs])
            eng.close()
            recs = eng.flight_recorder.snapshot()["cycles"]
            rows = {(r["launch_q"], r["launch_tower_rows"]) for r in recs
                    if r.get("launch_q")}
            assert all(q == r for q, r in rows) == twin, rows
        finally:
            eng.close()
    assert outs[0] == outs[1]


@pytest.mark.parametrize("kw", [
    dict(spec_draft="auto", spec_k=4, block_size=8),
    dict(kv_dtype="int8", block_size=32),
], ids=["spec-verify", "int8-blocks"])
def test_the_verify_step_and_int8_blocks_on_the_towers_own_axis(
        kw, small_multiple):
    """The two other users of ``_fused_tower``: the speculative verify
    launch (a slot's candidate rows, the draft's tokens laid over the
    slots' first TOWER rows on the device) and a quantized pool (its
    append is an XLA scatter with a target a row: no K|V gather). Greedy
    tokens through the compact programs are the padded twin's."""
    from paddle_tpu.serving import GenerationEngine
    net = _toys.default("gpt2")
    # four requests on four slots, one of them in two chunks (21 tokens at
    # a budget of 16), six tokens each: chunk rows, decode rows and the
    # verify launch's candidate rows all ride in launches of both kinds
    prompts = [(np.arange(n) * 5 + 3 * n) % 40 + 2 for n in (13, 4, 21, 7)]
    outs = []
    for twin in (False, True):
        eng = GenerationEngine(net, num_slots=4, max_len=64,
                               prefill_budget=16, **kw)
        try:
            if twin:
                eng._tower_rows = lambda Q: int(Q)
            hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            outs.append([h.result(timeout=600).tolist() for h in hs])
            eng.close()
            rows = {(r["launch_q"], r["launch_tower_rows"])
                    for r in eng.flight_recorder.snapshot()["cycles"]
                    if r.get("launch_q")}
            assert all(q == r for q, r in rows) == twin, rows
        finally:
            eng.close()
    assert outs[0] == outs[1]


def test_a_mesh_engine_keeps_one_axis():
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.serving import GenerationEngine
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    eng = GenerationEngine(_toys.new_default("gpt2"), num_slots=2,
                           max_len=32, block_size=8, mesh=mesh)
    try:
        for Q in (8, 32, 256, 1024):
            assert eng._tower_rows(Q) == Q
        assert eng._null_step_operands(256, 4)[0].shape == (256,)
    finally:
        eng.close()


def test_a_launch_counts_its_tower_rows(small_multiple):
    """``note_launch`` puts ``launch_tower_rows`` beside ``launch_rows``
    and ``launch_q``, and the two monitors sum them."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import GenerationEngine
    eng = GenerationEngine(_toys.default("gpt2"), num_slots=4, max_len=64,
                           block_size=8, prefill_budget=16)
    try:
        monitor.stat_reset("serving/launch_rows")
        monitor.stat_reset("serving/tower_rows")
        out = [int(t) for t in eng.submit(np.arange(2, 12), 4).stream()]
        assert len(out) == 4
        # the last launch's record enters the ring at the end of the turn
        # that woke the client: read it once the scheduler has stopped
        eng.close()
        recs = [r for r in eng.flight_recorder.snapshot()["cycles"]
                if r.get("launch_q")]
    finally:
        eng.close()
    assert recs and all(
        r["launch_rows"] <= r["launch_tower_rows"] <= r["launch_q"]
        for r in recs)
    # a decode row: one real row of 8 tower rows; the kernel's are 8 too
    assert (recs[-1]["launch_rows"], recs[-1]["launch_tower_rows"]) == (1, 8)
    assert monitor.stat_get("serving/launch_rows") == sum(
        r["launch_rows"] for r in recs)
    assert monitor.stat_get("serving/tower_rows") == sum(
        r["launch_tower_rows"] for r in recs)
