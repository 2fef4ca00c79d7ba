"""Falcon-H1 (a Mamba-2 mixer beside grouped-query attention in every
layer, its recurrent state a row a slot of the paged pool) against its
plain reference (``benchmark/lib/reference_falcon_h1.py``: float32,
``highest``, the recurrence a sequential scan, no cache, no chunks, no
kernels), at toy widths (``tiny-falcon-h1-config.json``: 10 query heads on
2 KV heads, so ``q_group`` 5; a mixer of 4 heads of 16 with state 32, chunk
16) with the benchmark's seeded weights, on the CPU in float32. Logits and
states are compared, never sampled tokens. Tolerances: float32 sums in
another order differ by ~1e-6 of a unit-RMS value, so 1e-4 on logits of
spread 1 and on states is two orders of room and still two under what
bfloat16 anywhere would give.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import reference_falcon_h1 as R
from paddle_tpu.models import decoder_spec as DS
from paddle_tpu.models.falcon_h1 import FalconH1Config
from paddle_tpu.ops import ssm as SSM
from paddle_tpu.serving import GenerationEngine

import _toys

ORDER_OF_SUM = 1e-4        # see the module doc
TOY = _toys.config("falcon_h1")


@pytest.fixture(scope="module")
def net():
    return _toys.seeded("falcon_h1")


@pytest.fixture(scope="module")
def make():
    return _toys.weights("falcon_h1")


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, size=n).astype(
        np.int32)


WIDTH, SERVED = 64, 36     # one compiled shape of the reference


def _padded(text):
    ids = np.zeros(WIDTH, np.int32)
    ids[:len(text)] = text
    return ids


def _gaps(make, prompt, tokens):
    """Normalised reference gap of each served token (0 = the reference's
    own first choice)."""
    n = len(tokens)
    pos = np.zeros((1, SERVED), np.int32)
    pos[0, :n] = len(prompt) - 1 + np.arange(n)
    served = np.zeros((1, SERVED), np.int32)
    served[0, :n] = tokens
    out = R.served_margins(make, TOY, _padded(list(prompt) + list(tokens))[
        None], pos, served, rows_per_call=1)
    return (out["gap"][0] / out["std"][0])[:n]


@pytest.fixture(scope="module")
def engine(net):
    """Two slots, chunks of at most 24 tokens over blocks of 8: shared by
    the tests that only need an engine, so that its step programs are
    built once."""
    eng = GenerationEngine(net, num_slots=2, max_len=64, block_size=8,
                           prefill_budget=24)
    yield eng
    eng.close()


# -- 1. the plain forward pass, the spec ----------------------------------------

def test_the_programs_forward_is_the_references(net, make):
    ids = np.stack([_ids(50, 1), _ids(50, 2)])     # three chunks of 16 and 2
    want = R.logits(make, TOY, ids)
    program = np.asarray(net(jnp.asarray(ids))._data)
    assert float(want.std()) > 0.5                 # logits of spread ~1
    np.testing.assert_allclose(program, want, atol=ORDER_OF_SUM)


def test_every_layer_has_a_state_beside_its_cache(net):
    spec = DS.serving_decoder(net).spec
    assert spec.state_layers == (0, 1)
    assert spec.state.parts == (("conv", (3, 192), "float32"),
                                ("ssm", (4, 16, 32), "float32"))
    assert spec.state.nbytes == (3 * 192 + 4 * 16 * 32) * 4
    assert len(spec.cache_groups) == 1
    assert spec.cache_groups[0].q_group == 5
    # the published widths: 4.19 MB of state and 61 KB of tail a layer
    big = FalconH1Config().state_spec
    assert dict((n, s) for n, s, _ in big.parts) == {
        "conv": (3, 5120), "ssm": (32, 128, 256)}
    assert big.nbytes == 4_194_304 + 61_440


def test_the_spec_refuses_a_state_it_cannot_serve():
    full = DS.CacheSpec(rows=2, lanes=32)
    lat = DS.CacheSpec(rows=1, lanes=128, v_aliases_k=True, v_lanes=32)
    st = DS.StateSpec((("ssm", (2, 4, 8), "float32"),))
    with pytest.raises(ValueError, match="beside full attention"):
        DS.LayerSpec(DS.LATENT, lat, DS.DENSE, state=st)
    with pytest.raises(ValueError, match="beside full attention"):
        DS.LayerSpec(DS.FULL, full, DS.DENSE, window=8, state=st)
    with pytest.raises(ValueError, match="differ in its descriptor"):
        DS.DecoderSpec(
            (DS.LayerSpec(DS.FULL, full, DS.DENSE, state=st),
             DS.LayerSpec(DS.FULL, full, DS.DENSE, state=DS.StateSpec(
                 (("ssm", (2, 4, 16), "float32"),)))), 256, 64)
    with pytest.raises(ValueError, match="under block generation"):
        DS.DecoderSpec(
            (DS.LayerSpec(DS.FULL, full, DS.DENSE, state=st),), 256, 64,
            DS.GenerationRule(block_length=4, denoising_steps=4,
                              mask_token_id=255))
    with pytest.raises(ValueError, match="at least one part"):
        DS.StateSpec(())
    mixed = DS.DecoderSpec(
        (DS.LayerSpec(DS.FULL, full, DS.DENSE),
         DS.LayerSpec(DS.FULL, full, DS.DENSE, state=st)), 256, 64)
    assert mixed.state_layers == (1,) and mixed.state is st
    assert len(mixed.cache_groups) == 1      # the state forms no group


# -- 2. the recurrence's two forms and the convolution --------------------------

def _scan_inputs(T, seed=0, H=4, P=16, N=32, G=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    dt = jax.nn.softplus(f(T, H) - 2.0)
    a = -jnp.exp(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)))
    return f(T, H, P), dt, a, f(T, G, N), f(T, G, N), jnp.ones(H), f(H, P, N)


def _sequential(h, x, dt, a, b, c, d):
    """The recurrence of ONE sequence a row at a time: what both forms of
    ``ops/ssm.py`` are held to. ``h [H, P, N]``, ``x [T, H, P]``, ``dt [T,
    H]``, ``b``/``c [T, G, N]`` -> ``(y [T, H, P], h)``."""
    ys = []
    for t in range(x.shape[0]):
        y, h = SSM.ssm_step(h[None], x[t][None], dt[t][None], a,
                            b[t][None], c[t][None], d)
        h = h[0]
        ys.append(y[0])
    return jnp.stack(ys), h


@pytest.mark.parametrize("T", [1, 16, 21])
def test_the_chunk_form_with_an_initial_state_is_the_sequential_recurrence(T):
    x, dt, a, b, c, d, h0 = _scan_inputs(T, seed=T)
    want_y, want_h = _sequential(h0, x, dt, a, b, c, d)
    y, h = SSM.ssm_chunk_scan(h0, x, dt, a, b, c, d)
    np.testing.assert_allclose(y, want_y, atol=ORDER_OF_SUM)
    np.testing.assert_allclose(h, want_h, atol=ORDER_OF_SUM)
    assert float(jnp.abs(want_h - h0).max()) > 0.1     # the state moved


def test_the_decode_step_is_one_step_of_the_recurrence():
    x, dt, a, b, c, d, h0 = _scan_inputs(3, seed=9)
    want_y, want_h = _sequential(h0, x[:1], dt[:1], a, b[:1], c[:1], d)
    y, h = SSM.ssm_step(h0[None], x[:1], dt[:1], a, b[:1], c[:1], d)
    np.testing.assert_allclose(y, want_y, atol=1e-6)
    np.testing.assert_allclose(h[0], want_h, atol=1e-6)


def _ragged(q_lens, pos0s, S):
    """The layout of a launch as the engine builds it, and the flat rows'
    (sequence, offset) for the test's own bookkeeping."""
    from paddle_tpu.ops.ragged_paged_attention import BLOCK_Q, ragged_layout
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, pos0s)
    Q = len(blk_seq) * BLOCK_Q
    valid = np.zeros(Q, bool)
    for s, n in enumerate(q_lens):
        valid[qstart[s]:qstart[s] + n] = True
    kv_len = np.asarray([p + n for p, n in zip(pos0s, q_lens)], np.int32)
    lay = SSM.seq_layout(jnp.asarray(blk_seq), jnp.asarray(qstart),
                         jnp.asarray(pos0), jnp.asarray(kv_len),
                         jnp.asarray(valid), BLOCK_Q)
    return lay, qstart, Q


def test_a_ragged_launch_is_each_sequence_on_its_own():
    """Five slots: a decode row that continues, a chunk of 37 that
    continues at a boundary that is no multiple of the chunk (16), a fresh
    chunk of 9, a fresh single row, and a slot with no rows. Every
    sequence's rows and final state are its own sequential recurrence from
    its slot's state (zero where it is fresh, whatever the slot held);
    rows of no sequence read 0; the absent slot's state and the row no
    slot owns... only the chunked scan's parking touches the latter."""
    S, H, P, N, G = 5, 4, 16, 32, 2
    q_lens, pos0s = [1, 37, 9, 1, 0], [40, 23, 0, 0, 0]
    lay, qstart, Q = _ragged(q_lens, pos0s, S)
    x, dt, a, b, c, d, _ = _scan_inputs(Q, seed=3)
    rng = np.random.default_rng(4)
    state = jnp.asarray(rng.standard_normal((2, S + 1, H, P, N)), jnp.float32)
    y, new = SSM.ssm_scan(x, dt, a, b, c, d, state, 1, lay, chunk=16)
    np.testing.assert_array_equal(new[0], state[0])      # the other layer
    np.testing.assert_array_equal(new[1, 4], state[1, 4])  # the absent slot
    owned = np.zeros(Q, bool)
    for s, n in enumerate(q_lens):
        if not n:
            continue
        rows = slice(qstart[s], qstart[s] + n)
        owned[rows] = True
        h0 = jnp.zeros((H, P, N)) if pos0s[s] == 0 else state[1, s]
        want_y, want_h = _sequential(h0, x[rows], dt[rows], a,
                                            b[rows], c[rows], d)
        np.testing.assert_allclose(y[rows], want_y, atol=ORDER_OF_SUM)
        np.testing.assert_allclose(new[1, s], want_h, atol=ORDER_OF_SUM)
    assert float(jnp.abs(y[~owned]).max()) == 0.0


def test_pad_rows_and_absent_slots_change_no_state():
    """A launch of decode rows only: 7 of a q block's 8 rows are pad rows.
    Whatever they hold (here: NaN), the state of the slots with a row is
    what the one real row makes it, and the other slots' rows of both
    state arrays are bit for bit what they were."""
    S, C, K = 4, 192, 4
    lay, qstart, Q = _ragged([1, 0, 1, 0], [12, 0, 30, 0], S)
    x, dt, a, b, c, d, _ = _scan_inputs(Q, seed=5)
    real = np.zeros(Q, bool)
    real[[qstart[0], qstart[2]]] = True
    poison = lambda v: jnp.where(
        real.reshape((-1,) + (1,) * (v.ndim - 1)), v, jnp.nan)
    rng = np.random.default_rng(6)
    state = jnp.asarray(rng.standard_normal((1, S + 1, 4, 16, 32)),
                        jnp.float32)
    y, new = SSM.ssm_scan(poison(x), poison(dt), a, poison(b), poison(c), d,
                          state, 0, lay)
    assert bool(jnp.isfinite(new).all()) and bool(jnp.isfinite(y[real]).all())
    for s in (1, 3, 4):
        np.testing.assert_array_equal(new[0, s], state[0, s])
    for s in (0, 2):
        r = qstart[s]
        _, want = SSM.ssm_step(state[0, s][None], x[r][None], dt[r][None], a,
                               b[r][None], c[r][None], d)
        np.testing.assert_allclose(new[0, s], want[0], atol=1e-6)
    # the convolution and its tail
    xc = jnp.asarray(rng.standard_normal((Q, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, C)), jnp.float32)
    tail = jnp.asarray(rng.standard_normal((1, S + 1, K - 1, C)), jnp.float32)
    out, new_tail = SSM.conv_rows(poison(xc), w, jnp.zeros(C), tail, 0, lay)
    assert bool(jnp.isfinite(new_tail).all())
    for s in (1, 3, 4):
        np.testing.assert_array_equal(new_tail[0, s], tail[0, s])
    for s in (0, 2):
        r = qstart[s]
        want = (tail[0, s] * w[:K - 1]).sum(0) + xc[r] * w[K - 1]
        np.testing.assert_allclose(out[r], want, atol=1e-5)
        np.testing.assert_allclose(
            new_tail[0, s], jnp.concatenate([tail[0, s, 1:], xc[r][None]]),
            atol=0)


def test_the_convolution_carries_its_tail_across_a_chunk_boundary():
    """One sequence of 29 inputs in one launch, against the same sequence
    in launches of 13, 1, 2 and 13 rows with the tail carried: the same
    outputs, the same last three inputs left behind."""
    C, K = 24, 4
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((29, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, C)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(C), jnp.float32)
    want = bias + sum(w[j] * jnp.pad(x, ((K - 1 - j, 0), (0, 0)))[:29]
                      for j in range(K))
    tail = jnp.asarray(rng.standard_normal((1, 2, K - 1, C)), jnp.float32)
    got, at = [], 0
    for n in (13, 1, 2, 13):
        lay, qstart, Q = _ragged([n], [at], 1)
        rows = jnp.zeros((Q, C)).at[:n].set(x[at:at + n])
        out, tail = SSM.conv_rows(rows, w, bias, tail, 0, lay)
        got.append(out[:n])
        at += n
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-5)
    np.testing.assert_allclose(tail[0, 0], x[-3:], atol=0)


# -- 3. serving through the pool ------------------------------------------------

def test_chunked_prefill_then_decode_through_the_pool_agrees(
        engine, make):
    """Prompts whose lengths straddle the chunk (16) and a prefill budget
    (24) that splits them at boundaries that are no multiple of it (15 +
    9 of 17; 24 of 33 + 9), then decode steps: every served token is the
    reference's first choice by its own logits, and what each slot's state
    rows hold once its request is in is what the reference's full forward
    leaves behind (float32 against float32: prefill in chunks + decode
    through cache and state against one pass from zero)."""
    eng, pool = engine, engine._pool
    chunks0 = eng.stats()["prefill_chunks"]
    for pair in ((15, 17), (33, 5)):
        prompts = [_ids(n, seed=n).tolist() for n in pair]
        handles = [eng.submit(p, 10) for p in prompts]
        outs = [[int(t) for t in h.stream()] for h in handles]
        while pool.n_active:               # the last launch's landing
            pass
        state = [np.asarray(a) for a in pool.state_data]
        for slot, (p, o) in enumerate(zip(prompts, outs)):
            assert len(o) == 10
            assert float(_gaps(make, p, o).max()) < ORDER_OF_SUM
            # the state after every token but the last served one (which
            # was emitted and never fed)
            fed = p + o[:-1]
            left = R.final_states(make, TOY, _padded(fed), len(fed))
            for layer, (tail, h) in enumerate(left):
                np.testing.assert_allclose(state[0][layer, slot], tail,
                                           atol=ORDER_OF_SUM)
                np.testing.assert_allclose(state[1][layer, slot], h,
                                           atol=ORDER_OF_SUM)
    st = eng.stats()
    assert st["prefill_chunks"] - chunks0 >= 5 and st["preempts"] == 0
    # no block offered to or matched in the prefix cache
    assert st["prefix_hits"] == 0 and st["cached_blocks"] == 0
    assert pool.blocks_in_use == 0
    # the record's keys: the launch's slots and rows through the mixer,
    # the state the live slots hold
    rec = [c for c in eng.flight_recorder.snapshot()["cycles"]
           if c.get("launch_q")]
    assert all({"state_slots", "ssm_rows", "ssm_chunk_rows",
                "state_live_bytes", "kv_live_bytes",
                "kv_live_tokens"} <= set(c) for c in rec)
    assert sum(c["ssm_rows"] for c in rec) == sum(c["launch_rows"]
                                                  for c in rec)
    assert sum(c["ssm_chunk_rows"] for c in rec) >= 15 + 17 + 33 + 5
    slot_bytes = 2 * (3 * 192 + 4 * 16 * 32) * 4
    assert st["state"]["slot_bytes"] == slot_bytes
    assert max(c["state_live_bytes"] for c in rec) == 2 * slot_bytes
    assert all(c["state_slots"] <= 2 for c in rec)


def test_a_reused_slot_starts_from_zero_with_the_late_row_in_the_air(
        net, make):
    """ONE slot. Request A ends on an EOS the host learns one launch late
    (two launches in flight), so a launch that still carries A's row —
    and writes A's state — is in the air when B takes the slot. B's text
    is what the engine gave it while its state rows were still untouched
    (a fresh engine's), and the reference's."""
    pa, pb = _ids(21, seed=71).tolist(), _ids(19, seed=72).tolist()
    eng = GenerationEngine(net, num_slots=1, max_len=64, block_size=8,
                           prefill_budget=24)
    assert all(float(np.abs(np.asarray(a)).max()) == 0.0
               for a in eng._pool.state_data)
    want = [int(t) for t in eng.submit(pb, 10).stream()]      # fresh
    a_alone = [int(t) for t in eng.submit(pa, 8).stream()]
    eos = a_alone[3]
    n_a = a_alone.index(eos) + 1
    late0 = eng._sched.late_rows
    ha = eng.submit(pa, 8, eos_token_id=eos)
    hb = eng.submit(pb, 10)
    got_a = [int(t) for t in ha.stream()]
    got = [int(t) for t in hb.stream()]
    late = eng._sched.late_rows - late0
    eng.close()
    assert got_a == a_alone[:n_a]
    assert late >= 1                       # A's row rode one launch too far
    assert got == want
    assert float(_gaps(make, pb, got).max()) < ORDER_OF_SUM


def test_a_preempted_request_resumes_by_refeed_to_the_same_tokens(net, make):
    """Two requests that outgrow four blocks (contexts of 23 and 25
    tokens: three and four blocks of 8, tables of 1, 2 and 4): the younger
    is preempted — its state row is simply abandoned — re-admitted and
    re-fed from position 0 (prompt + what it had generated, in chunks);
    both stay the reference's own text."""
    pa, pb = _ids(9, seed=61).tolist(), _ids(11, seed=62).tolist()
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           num_blocks=4, prefill_budget=16)
    ha, hb = eng.submit(pa, 14), eng.submit(pb, 14)
    oa = [int(t) for t in ha.stream()]
    ob = [int(t) for t in hb.stream()]
    preempts = eng.stats()["preempts"]
    eng.close()
    assert preempts >= 1
    assert float(_gaps(make, pa, oa).max()) < ORDER_OF_SUM
    assert float(_gaps(make, pb, ob).max()) < ORDER_OF_SUM
    assert eng._pool.blocks_in_use == 0 and eng._pool.n_active == 0


def test_the_plan_and_the_analyzer_take_the_step_with_state(net):
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           hbm_budget_bytes=1 << 30)
    slot_bytes = 2 * (3 * 192 + 4 * 16 * 32) * 4
    assert eng._plan["fits"] and eng._plan["state_bytes"] == 3 * slot_bytes
    assert eng._plan["pool_bytes"] == eng._pool.capacity_bytes \
        + 3 * slot_bytes
    list(eng.submit(_ids(12, seed=5).tolist(), 3).stream())
    report = eng.analyze()
    eng.close()
    assert not [f for f in report.findings if f.severity == "error"]
    # a budget the blocks alone would fit, the state beside them does not
    with pytest.raises(Exception, match="does not fit"):
        GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                         hbm_budget_bytes=eng._plan["static_peak_bytes"]
                         - slot_bytes)


# -- 4. the refusals -------------------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(spec_draft="auto"), "spec_draft does not compose with a recurrent "
                              "state"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes does not compose with "
                                    "a recurrent state"),
    (dict(kv_dtype="int8", block_size=32), "int8/fp8 KV blocks do not "
                                           "compose with a recurrent state"),
    (dict(mesh="a mesh"), "does not compose with a recurrent state"),
], ids=["spec_draft", "host_tier", "int8-blocks", "mesh"])
def test_what_needs_a_state_snapshot_is_refused_by_name(net, kwargs, match):
    with pytest.raises(ValueError, match=match):
        GenerationEngine(net, num_slots=2, max_len=32, **kwargs)


def test_prefix_reuse_is_off_where_a_state_would_need_a_snapshot(engine):
    """Two requests that share their first 32 tokens: the second feeds
    all of them (nothing is offered to the trie, nothing matched)."""
    shared = _ids(32, seed=80).tolist()
    fed0 = engine.stats()["chunked_prefill_tokens"]
    first = [int(t) for t in engine.submit(shared + [7], 3).stream()]
    second = [int(t) for t in engine.submit(shared + [9], 3).stream()]
    st = engine.stats()
    assert len(first) == len(second) == 3
    assert st["chunked_prefill_tokens"] - fed0 == 2 * 33
    assert st["prefix_hits"] == 0 and st["cached_blocks"] == 0
    assert st["prefill_tokens_saved"] == 0


# -- 5. q_group 5 in the ragged kernel --------------------------------------------

def test_the_ragged_kernel_folds_a_group_of_five_query_heads():
    """10 query heads on 2 KV heads: a folded q block is 40 rows, the
    first group that is no power of two. Interpret mode against
    ``jax.numpy`` — a chunk and decode rows, over page tables."""
    from paddle_tpu.ops.ragged_paged_attention import (
        ragged_layout, ragged_paged_attention, reference_ragged_attention)
    H, Hkv, Dh, bs, NB, T = 10, 2, 16, 8, 12, 4
    q_lens, pos0s = [1, 19, 1], [9, 3, 30]
    S = len(q_lens)
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, pos0s)
    Q = len(blk_seq) * 8
    rng = np.random.default_rng(11)
    q = rng.standard_normal((H, Q, Dh)).astype(np.float32)
    pool = rng.standard_normal((1, NB + 1, Hkv, bs, 2 * Dh)).astype(np.float32)
    tables = (1 + np.arange(S * T).reshape(S, T) % NB).astype(np.int32)
    lo = np.zeros(S, np.int32)
    kv_len = np.asarray([p + n for p, n in zip(pos0s, q_lens)], np.int32)
    out = np.asarray(ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pool), 0, blk_seq, qstart, pos0, tables,
        lo, kv_len))
    row_seq = np.full(Q, -1, np.int32)
    row_pos = np.zeros(Q, np.int32)
    for s, n in enumerate(q_lens):
        row_seq[qstart[s]:qstart[s] + n] = s
        row_pos[qstart[s]:qstart[s] + n] = pos0s[s] + np.arange(n)
    want = reference_ragged_attention(
        np.swapaxes(q, 0, 1), pool, 0, row_seq, row_pos, tables, lo)
    real = row_seq >= 0
    np.testing.assert_allclose(np.swapaxes(out, 0, 1)[real], want[real],
                               atol=1e-5)
