"""LFM2-MoE (a gated short convolution as the whole mixer of three layers
in four — no KV cache there, its two-row tail a slot's state — beside
grouped-query attention and sigmoid-routed experts) against its plain
reference (``benchmark/lib/reference_lfm2.py``: float32, ``highest``, the
convolution as three shifted products, no cache, no chunks, no kernels,
no grouped products), at toy widths (``tiny-lfm2-config.json``: two
leading ``conv`` layers with a dense FFN, then a whole period (attention,
conv, conv, conv) with 8 experts top-2; 8 query heads on 2 KV heads, so
``q_group`` 4) with the benchmark's seeded weights, on the CPU in
float32. Logits and states are compared, never sampled tokens.
Tolerances: float32 sums in another order differ by ~1e-6 of a unit-RMS
value, so 1e-4 on logits of spread 1 and on states is two orders of room
and still two under what bfloat16 anywhere would give.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import reference_lfm2 as R
from paddle_tpu.models import decoder_spec as DS
from paddle_tpu.models.axk1 import route_top_k
from paddle_tpu.models.lfm2 import Lfm2MoeConfig
from paddle_tpu.ops import ssm as SSM
from paddle_tpu.serving import GenerationEngine

import _toys

ORDER_OF_SUM = 1e-4        # see the module doc
TOY = _toys.config("lfm2")
CONV_LAYERS = (0, 1, 3, 4, 5)


@pytest.fixture(scope="module")
def net():
    return _toys.seeded("lfm2")


@pytest.fixture(scope="module")
def make():
    return _toys.weights("lfm2")


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
    ids = np.stack([_ids(50, 1), _ids(50, 2)])
    want = R.logits(make, TOY, ids)
    program = np.asarray(net(jnp.asarray(ids))._data)
    assert float(want.std()) > 0.5                 # logits of spread ~1
    np.testing.assert_allclose(program, want, atol=ORDER_OF_SUM)


@pytest.mark.parametrize("depart", [
    dict(select_bias=False), dict(qk_norm=False), dict(gate_first=False),
], ids=lambda d: next(iter(d)))
def test_a_reference_that_departs_in_one_convention_is_far_away(
        make, depart):
    ids = _ids(50, 3)[None]
    want = R.logits(make, TOY, ids)
    other = R.logits(make, TOY, ids, depart=depart)
    assert float(np.abs(other - want).max()) > 0.1


def test_three_layers_in_four_hold_no_cache(net):
    spec = DS.serving_decoder(net).spec
    assert spec.cache_layers == (2,) and spec.state_layers == CONV_LAYERS
    assert spec.state.parts == (("conv", (2, 64), "float32"),)
    assert [ls.ffn for ls in spec.layers] == [DS.DENSE] * 2 + [DS.ROUTED] * 4
    (group,) = spec.cache_groups
    assert group.layers == (2,) and group.q_group == 4
    assert group.cache == DS.CacheSpec(rows=2, lanes=16)
    # the published widths: a tail of 2 x 2,048 float32 a conv layer, a
    # token of 8 KV heads x 128 lanes in each of the 10 attention layers
    big = Lfm2MoeConfig()
    assert big.head_dim == 64 and big.state_spec.nbytes == 2 * 2048 * 4
    assert big.layer_types.count("full_attention") == 10
    assert big.layer_types[:10] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2
    first = Lfm2MoeConfig(num_hidden_layers=10)      # the cell's stage
    assert first.layer_types.count("conv") == 8


@pytest.mark.parametrize("over,match", [
    (dict(conv_bias=True), "conv_bias true is not built"),
    (dict(use_expert_bias=False), "built with its expert_bias"),
    (dict(layer_types=["conv", "mamba"], num_hidden_layers=2),
     "one of 'conv' and 'full_attention'"),
    (dict(experts_held=(0, 9)), "is no range of the 8 experts"),
], ids=["conv-bias", "no-expert-bias", "layer-types", "experts-held"])
def test_the_config_refuses_what_is_not_built(over, match):
    with pytest.raises(ValueError, match=match):
        Lfm2MoeConfig.tiny(**over)


# -- 2. the convolution at K 3 without bias, the router ---------------------------

def _three_shifted_products(x, w):
    T = x.shape[0]
    return sum(w[j][None, :] * jnp.pad(x, ((2 - j, 0), (0, 0)))[:T]
               for j in range(3))


@pytest.mark.parametrize("cut", [1, 2, 7], ids=lambda c: f"tail-rows-{c}")
def test_conv_rows_at_three_taps_without_bias_is_three_shifted_products(cut):
    """One sequence fed in two launches of 24 rows (pad rows after the
    real ones), the second a CONTINUED chunk whose first rows read the
    tail: ``cut`` rows in the first launch — 1 (the tail then still holds
    a zero from before the sequence's start), 2 (exactly the tail) and
    more."""
    rng = np.random.default_rng(cut)
    T, C, Q = 19, 12, 24
    x = jnp.asarray(rng.standard_normal((T, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, C)), jnp.float32)
    want = _three_shifted_products(x, w)
    junk = rng.standard_normal((2, 3, 2, C)).astype(np.float32)
    tail = jnp.asarray(junk)
    got = []
    for at, n in ((0, cut), (cut, T - cut)):
        rows = jnp.arange(Q, dtype=jnp.int32)
        lay = SSM.SeqLayout(
            jnp.where(rows < n, 0, 2), rows,          # 2: a row of no slot
            jnp.zeros(2, jnp.int32), jnp.asarray([n, 0], jnp.int32),
            jnp.asarray([at == 0, False]))
        rows_in = jnp.concatenate(
            [x[at:at + n], jnp.asarray(rng.standard_normal((Q - n, C)),
                                       jnp.float32)])
        out, tail = SSM.conv_rows(rows_in, w, None, tail, 1, lay)
        got.append(out[:n])
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-5)
    np.testing.assert_allclose(tail[1, 0], x[-2:], atol=0)
    # the other layer's rows, the absent slot and the row no slot owns
    np.testing.assert_array_equal(tail[0], junk[0])
    np.testing.assert_array_equal(tail[1, 1:], junk[1, 1:])


def test_the_router_is_top4_of_s_plus_bias_with_weights_from_s():
    """At the published router width (64 experts of hidden 2,048, top-4)
    with the cell's weight scales: the choice is the top-4 of ``s +
    expert_bias``, the weights the chosen ``s`` over their sum + 1e-6 —
    the reference's, and hand arithmetic — and the bias moves the choice
    away from the plain top-4 on a real share of rows."""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2-24b-a2b-pp4.json")) as f:
        sc = json.load(f)["model"]["weight_scales"]
    rng = np.random.default_rng(5)
    N, E, n, k = 512, 2048, 64, 4
    v = jnp.asarray(rng.standard_normal((N, E)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((n, E)) * sc["router_gain"]
                         / E ** 0.5, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(n) * sc["expert_bias_std"],
                       jnp.float32)
    idx, w, scores = route_top_k(v, router, k, 1.0, True, scoring="sigmoid",
                                 select_bias=bias, eps=1e-6)
    d = R.Dims.of(dict(TOY, num_experts=n, num_experts_per_tok=k,
                       hidden_size=E, num_attention_heads=32))
    with jax.default_matmul_precision("highest"):
        ridx, rw, rs = R.route(d, {"router": router, "expert_bias": bias}, v)
    np.testing.assert_allclose(scores, rs, atol=1e-6)
    same = np.sort(np.asarray(idx), -1) == np.sort(np.asarray(ridx), -1)
    assert same.all(axis=-1).mean() > 0.99          # near-ties may flip
    s = np.asarray(scores)
    hand_idx = np.argsort(-(s + np.asarray(bias)[None]), axis=-1)[:, :k]
    top = np.take_along_axis(s, hand_idx, axis=-1)
    hand_w = top / (top.sum(-1, keepdims=True) + 1e-6)
    rows = (hand_idx == np.asarray(idx)).all(axis=-1)
    assert rows.mean() > 0.99
    np.testing.assert_allclose(np.asarray(w)[rows], hand_w[rows], atol=1e-7)
    # the epsilon joins the SUM (1e-6 is under a float32 weight's last
    # place, so the arithmetic is shown at 1e-2): w = s / (sum + eps)
    _, w2, _ = route_top_k(v, router, k, 1.0, True, scoring="sigmoid",
                           select_bias=bias, eps=1e-2)
    np.testing.assert_allclose(np.asarray(w2)[rows],
                               (top / (top.sum(-1, keepdims=True) + 1e-2)
                                )[rows], atol=1e-7)
    _, w0, _ = route_top_k(v, router, k, 1.0, True, scoring="sigmoid",
                           select_bias=bias)
    assert float(np.abs(np.asarray(w0).sum(-1) - 1.0).max()) < 3e-7
    # the bias decides: a real share of rows choose another set than the
    # plain top-4 of the scores
    plain = np.argsort(-s, axis=-1)[:, :k]
    moved = (np.sort(plain, -1) != np.sort(hand_idx, -1)).any(axis=-1)
    assert 0.2 < moved.mean() < 0.95


# -- 3. serving through the pool ------------------------------------------------

def test_chunked_prefill_then_decode_through_pool_and_state_agrees(
        engine, make):
    """Prompts that a prefill budget of 24 splits into a CONTINUED chunk
    at boundaries that leave 1 row (25 = 24 + 1) and 2 rows (26 = 24 + 2)
    for the second launch — the tail's two cases — and two more, then
    decode steps: every served token is the reference's first choice by
    its own logits, and what each slot's tail rows hold once its request
    is in is what the reference's full forward leaves behind."""
    eng, pool = engine, engine._pool
    assert pool.groups[0].shape[0] == 1              # blocks for ONE layer
    assert [a.shape for a in pool.state_data] == [(5, 3, 2, 64)]
    chunks0 = eng.stats()["prefill_chunks"]
    for pair in ((25, 26), (33, 5)):
        prompts = [_ids(n, seed=n).tolist() for n in pair]
        handles = [eng.submit(p, 10) for p in prompts]
        outs = [[int(t) for t in h.stream()] for h in handles]
        while pool.n_active:               # the last launch's landing
            pass
        state = np.asarray(pool.state_data[0])
        for slot, (p, o) in enumerate(zip(prompts, outs)):
            assert len(o) == 10
            assert float(_gaps(make, p, o).max()) < ORDER_OF_SUM
            fed = p + o[:-1]
            left = R.final_states(make, TOY, _padded(fed), len(fed))
            assert len(left) == len(CONV_LAYERS)
            for place, tail in enumerate(left):
                np.testing.assert_allclose(state[place, slot], tail,
                                           atol=ORDER_OF_SUM)
    st = eng.stats()
    assert st["prefill_chunks"] - chunks0 >= 5 and st["preempts"] == 0
    assert st["prefix_hits"] == 0 and st["cached_blocks"] == 0
    assert pool.blocks_in_use == 0
    assert st["state"]["layers"] == 5
    assert st["state"]["slot_bytes"] == 5 * 2 * 64 * 4
    # the record's keys: PR 40's, and the layers that read and write the
    # pool — ONE of six here
    rec = [c for c in eng.flight_recorder.snapshot()["cycles"]
           if c.get("launch_q")]
    assert all({"state_slots", "ssm_rows", "ssm_chunk_rows",
                "state_live_bytes", "kv_live_bytes", "kv_live_tokens",
                "cache_layers"} <= set(c) for c in rec)
    assert {c["cache_layers"] for c in rec} == {1}
    assert sum(c["ssm_rows"] for c in rec) == sum(c["launch_rows"]
                                                  for c in rec)
    # kv_live_bytes counts the one layer that holds a cache: whole blocks
    # of 8 tokens x 2 KV heads x 16 lanes x 4 B
    live = [c for c in rec if c["kv_live_tokens"]]
    assert live and all(c["kv_live_bytes"] % (8 * 2 * 16 * 4) == 0
                        and c["kv_live_bytes"] <= 2 * (
                            c["kv_live_tokens"] + 16) * 2 * 16 * 4
                        for c in live)


def test_a_conv_layer_has_no_cache_write_and_no_attention_section(engine):
    """The compiled step's text: layer 2 (attention) has ``qkv``,
    ``cache_write`` and ``attention`` ops; the five ``conv`` layers have
    ``ssm_proj`` and ``ssm_conv`` and none of those three."""
    import re
    Q, T = 32, 4
    step = engine._fused_step_fn(Q, T)
    text = step.jitted.lower(
        engine._params, engine._buffers, engine._pool_operand(),
        *engine._null_step_operands(Q, T)).as_text(debug_info=True)
    found = set(re.findall(r"/layer(\d+)/(\w+)/", text))
    by_layer = {}
    for layer, word in found:
        by_layer.setdefault(int(layer), set()).add(word)
    kernel_side = {DS.QKV, DS.CACHE_WRITE, DS.ATTENTION}
    assert kernel_side <= by_layer[2]
    assert not {DS.SSM_PROJ, DS.SSM_CONV} & by_layer[2]
    for layer in CONV_LAYERS:
        assert {DS.SSM_PROJ, DS.SSM_CONV, DS.O_PROJ} <= by_layer[layer]
        assert not kernel_side & by_layer[layer]
    assert text.count("ragged_paged_attention") >= 1
    assert DS.SSM_SCAN not in {w for ws in by_layer.values() for w in ws}


def test_pad_rows_and_absent_slots_change_no_state(engine):
    """Two slots, one request: the tails of the absent slot and of the row
    no slot owns stay exactly what they were (planted garbage), through a
    chunk launch with pad rows and decode launches."""
    pool = engine._pool
    rng = np.random.default_rng(3)
    junk = rng.standard_normal((5, 3, 2, 64)).astype(np.float32)
    pool.state_data = (jnp.asarray(junk),)          # donated to the step
    out = [int(t) for t in engine.submit(_ids(21, seed=9).tolist(),
                                         6).stream()]
    while pool.n_active:
        pass
    state = np.asarray(pool.state_data[0])
    assert len(out) == 6
    took = [s for s in range(3)
            if not np.array_equal(state[:, s], junk[:, s])]
    assert len(took) == 1 and took[0] < 2            # a slot, not row 2
    assert float(np.abs(state[:, took[0]] - junk[:, took[0]]).min()) > 0


def test_a_reused_slot_starts_from_zero_with_the_late_row_in_the_air(
        net, make):
    """ONE slot. Request A ends on an EOS the host learns one launch late
    (two launches in flight), so a launch that still carries A's row —
    and writes A's tail — is in the air when B takes the slot. B's text
    is a fresh engine's, and the reference's."""
    pa, pb = _ids(21, seed=71).tolist(), _ids(19, seed=72).tolist()
    eng = GenerationEngine(net, num_slots=1, max_len=64, block_size=8,
                           prefill_budget=24)
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
    is preempted — its tail rows are simply abandoned — re-admitted and
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


def test_the_planner_sizes_blocks_from_the_cache_bearing_layers(net):
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           hbm_budget_bytes=1 << 30)
    pool = eng._pool
    slot_bytes = 5 * 2 * 64 * 4
    # ONE layer of blocks (of six), five layers of tails
    assert pool.groups[0].shape == (1, pool.num_blocks + 1, 2, 8, 16)
    assert pool.block_bytes == 1 * 2 * 8 * 16 * 4
    assert eng._plan["fits"] and eng._plan["state_bytes"] == 3 * slot_bytes
    assert eng._plan["pool_bytes"] == pool.capacity_bytes + 3 * slot_bytes
    list(eng.submit(_ids(12, seed=5).tolist(), 3).stream())
    report = eng.analyze()
    eng.close()
    assert not [f for f in report.findings if f.severity == "error"]


# -- 4. the refusals -------------------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(spec_draft="auto"), "spec_draft does not compose with a recurrent "
                              "state"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes does not compose with "
                                    "a recurrent state"),
    (dict(kv_dtype="int8", block_size=32), "int8/fp8 KV blocks do not "
                                           "compose with a recurrent state"),
    (dict(mesh="a mesh"), "beside attention or in place of it"),
], ids=["spec_draft", "host_tier", "int8-blocks", "mesh"])
def test_what_needs_a_state_snapshot_is_refused_by_name(net, kwargs, match):
    with pytest.raises(ValueError, match=match):
        GenerationEngine(net, num_slots=2, max_len=32, **kwargs)


def test_prefix_reuse_is_off_where_a_tail_would_need_a_snapshot(engine):
    shared = _ids(32, seed=80).tolist()
    fed0 = engine.stats()["chunked_prefill_tokens"]
    first = [int(t) for t in engine.submit(shared + [7], 3).stream()]
    second = [int(t) for t in engine.submit(shared + [9], 3).stream()]
    st = engine.stats()
    assert len(first) == len(second) == 3
    assert st["chunked_prefill_tokens"] - fed0 == 2 * 33
    assert st["prefix_hits"] == 0 and st["cached_blocks"] == 0


# -- 5. q_group 4 on heads of 64 lanes in the ragged kernel -----------------------

@pytest.mark.parametrize("q_lens,pos0s", [
    ([1, 1, 1], [9, 70, 30]),
    ([41, 1, 1], [3, 9, 30]),
], ids=["decode-rows", "a-wide-q-step"])
def test_the_ragged_kernel_folds_four_query_heads_of_64_lanes(q_lens, pos0s):
    """32 query heads on 8 KV heads of 64 lanes — a K|V row of 128 lanes,
    a folded q block of 8 x 4 = 32 rows: grouped heads had run at 128
    lanes a head only, 64-lane heads ungrouped only. Interpret mode
    against ``jax.numpy`` — decode rows, and a 41-row chunk whose q
    blocks a wide grid step serves — over page tables."""
    from paddle_tpu.ops.ragged_paged_attention import (
        q_step_blocks, ragged_layout, ragged_paged_attention,
        reference_ragged_attention)
    H, Hkv, Dh, bs, NB, T = 32, 8, 64, 16, 12, 6
    S = len(q_lens)
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, pos0s)
    Q = len(blk_seq) * 8
    rng = np.random.default_rng(11)
    q = rng.standard_normal((H, Q, Dh)).astype(np.float32)
    pool = rng.standard_normal((1, NB + 1, Hkv, bs, 2 * Dh)).astype(np.float32)
    tables = (1 + np.arange(S * T).reshape(S, T) % NB).astype(np.int32)
    lo = np.zeros(S, np.int32)
    kv_len = np.asarray([p + n for p, n in zip(pos0s, q_lens)], np.int32)
    if max(q_lens) > 8:
        assert q_step_blocks(Hkv, H // Hkv, bs, 2 * Dh, jnp.float32,
                             q_blocks=Q // 8) > 1
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
