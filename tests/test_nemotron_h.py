"""Nemotron-H (every block ONE of a Mamba-2 mixer, attention or a LatentMoE:
layers without an FFN and layers without a mixer in one spec) against its
plain reference (``benchmark/lib/reference_nemotron_h.py``: float32,
``highest``, the recurrence a sequential scan, no cache, no chunks, no
kernels), at toy widths (``tiny-nemotron-h-config.json``: blocks
``MEM*EM``; 8 query heads on 2 KV heads; a mixer of 4 heads of 16 with
state 32, chunk 16; 16 relu^2 experts in a latent of 32, top 4, experts
4..11 held) with the benchmark's seeded weights, on the CPU in float32.
Logits and states are compared, never sampled tokens. Tolerances: float32
sums in another order differ by ~1e-6 of a unit-RMS value, so 1e-4 on
logits of spread 1 and on states is two orders of room and still two
under what bfloat16 anywhere would give.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import family_nemotron_h as F
from benchmark.lib import reference_nemotron_h as R
from paddle_tpu.models import axk1
from paddle_tpu.models import decoder_spec as DS
from paddle_tpu.models.nemotron_h import NemotronHConfig
from paddle_tpu.serving import GenerationEngine

import _toys

ORDER_OF_SUM = 1e-4        # see the module doc
TOY = _toys.config("nemotron_h")
SLOT_BYTES = 3 * (3 * 192 + 4 * 16 * 32) * 4      # three M blocks


@pytest.fixture(scope="module")
def net():
    return _toys.seeded("nemotron_h")


@pytest.fixture(scope="module")
def make():
    return _toys.weights("nemotron_h")


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
    # the reference's memory-saving forms change nothing
    np.testing.assert_allclose(
        R.logits(make, TOY, ids[:, :48], q_block=16),
        R.logits(make, TOY, ids[:, :48]), atol=1e-5)


def test_a_published_block_is_one_layer_of_the_spec(net):
    spec = DS.serving_decoder(net).spec
    assert [(ls.attention, ls.ffn, ls.state is not None)
            for ls in spec.layers] == [
        (None, DS.NO_FFN, True), (None, DS.ROUTED, False),
        (None, DS.NO_FFN, True), (DS.FULL, DS.NO_FFN, False),
        (None, DS.ROUTED, False), (None, DS.NO_FFN, True)]
    assert spec.cache_layers == (3,) and spec.state_layers == (0, 2, 5)
    assert [ls.routes for ls in spec.layers] == [False, True, False, False,
                                                 True, False]
    assert [ls.has_mixer for ls in spec.layers] == [True, False, True, True,
                                                    False, True]
    assert spec.state.nbytes * 3 == SLOT_BYTES
    (group,) = spec.cache_groups
    assert group.layers == (3,) and group.q_group == 4
    # the published stage: MEMEMEM*EME, 21.6 MB of state a slot, 1 KB of
    # cache a token
    cfg = NemotronHConfig(num_hidden_layers=11, experts_held=(0, 128))
    assert cfg.hybrid_override_pattern == "MEMEMEM*EME"
    big = cfg.state_spec
    assert dict((n, s) for n, s, _ in big.parts) == {
        "conv": (3, 10240), "ssm": (128, 64, 128)}
    assert big.nbytes * 5 == 21_585_920
    assert F.state_bytes_per_slot(TOY) == SLOT_BYTES
    assert F.state_layers(TOY) == 3 and F.cache_layers(TOY) == 1
    with pytest.raises(ValueError, match="one of 'M'"):
        NemotronHConfig.tiny(hybrid_override_pattern="M-*EM")


def test_the_choice_is_by_score_plus_bias_and_the_weights_sum_to_the_scale(
        make):
    d = R.Dims.of(TOY)
    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                make.layer(1))
    u = jnp.asarray(np.random.default_rng(3).standard_normal((96, 64)),
                    jnp.float32)
    idx, w, s = R.route(d, lw["router"], lw["router_bias"], u)
    plain, _, _ = R.route(d, lw["router"], lw["router_bias"], u,
                          select_bias=False)
    differ = sum(set(a.tolist()) != set(b.tolist())
                 for a, b in zip(np.asarray(idx), np.asarray(plain)))
    assert differ >= 10                    # the bias decides rows
    np.testing.assert_allclose(w.sum(-1), 5.0, atol=1e-5)
    got, gw, _ = axk1.route_top_k(u, lw["router"], 4, 5.0, True,
                                  scoring="sigmoid",
                                  select_bias=lw["router_bias"])
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(idx, -1))
    np.testing.assert_allclose(np.sort(gw, -1), np.sort(w, -1), atol=1e-6)


# -- 2. the shares add up -------------------------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_whole_block(make):
    """Block 1 (``E``) of the uncut model against four chips' shares: each
    share's routed part — its held experts' weighted sum IN THE LATENT,
    projected up by the ``W_up`` every chip holds — summed over the shares,
    plus the shared expert ONCE, is the uncut reference's whole block; and
    the program's block with a share's experts adds that share's part."""
    whole = dict(TOY, experts_held=[0, 16])
    d_all = R.Dims.of(whole)
    f32 = lambda lw: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), lw)
    u = jnp.asarray(np.random.default_rng(5).standard_normal((40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        lw_all = f32(F.Weights(_toys.SEEDS["nemotron_h"], whole,
                               "float32").layer(1))
        routed_all, shared = R.moe(d_all, lw_all, u)
        parts = []
        for lo in (0, 4, 8, 12):
            share = dict(TOY, experts_held=[lo, lo + 4])
            lw = f32(F.Weights(_toys.SEEDS["nemotron_h"], share,
                               "float32").layer(1))
            # a share's experts are the uncut block's, value for value
            np.testing.assert_array_equal(
                lw["experts_up"], lw_all["experts_up"][lo:lo + 4])
            routed, shared_here = R.moe(R.Dims.of(share), lw, u)
            np.testing.assert_allclose(shared_here, shared, atol=1e-6)
            parts.append(routed)
    assert float(jnp.abs(routed_all).max()) > 0.1
    assert all(float(jnp.abs(p).max()) > 0.01 for p in parts)
    np.testing.assert_allclose(sum(parts) + shared, routed_all + shared,
                               atol=ORDER_OF_SUM)
    # the program's E block holding experts 4..11: the second and third
    # shares' parts and the shared expert once
    block = _toys.seeded("nemotron_h").layers[1]
    got, counters = block.ffn.apply(u, jnp.ones(40, bool))
    np.testing.assert_allclose(got, parts[1] + parts[2] + shared,
                               atol=ORDER_OF_SUM)
    assert int(counters[2]) == 40 and 0 < int(counters[0]) < 160


# -- 3. routed_experts: two matrices an expert ----------------------------------

def _experts(n, E, I, seed, gated):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),
                               jnp.float32)
    return ((f(n, E, I),) if gated else ()) + (f(n, E, I), f(n, I, E))


def _by_loop(x, valid, idx, w, experts, held):
    lo, hi = held
    y = jnp.zeros(x.shape, jnp.float32)
    for j, e in enumerate(range(lo, hi)):
        w_e = jnp.sum(jnp.where((idx == e) & valid[:, None], w, 0.0), -1)
        if len(experts) == 3:
            h = jax.nn.silu(x @ experts[0][j]) * (x @ experts[1][j])
        else:
            h = jnp.square(jax.nn.relu(x @ experts[0][j]))
        y = y + w_e[:, None] * (h @ experts[-1][j])
    return y


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
@pytest.mark.parametrize("plan", [None, (8, 24, False), (8, 24, True)],
                         ids=["own-plan", "three-trips", "by-gather"])
def test_routed_experts_runs_the_form_it_is_handed(monkeypatch, gated, plan):
    """Two matrices an expert are ``down(relu(up x)^2)``, three the gated
    form as ever, each against a loop over the held experts: under the
    plan's own layout and under forced ones of several trips (toy shapes
    never fill a second trip by themselves) through both ways back."""
    Q, k, n_all, held, E, I = 24, 3, 12, (2, 10), 32, 40
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((Q, E)), jnp.float32)
    valid = jnp.asarray(rng.random(Q) < 0.8)
    idx = jnp.asarray(np.stack([rng.permutation(n_all)[:k]
                                for _ in range(Q)]), jnp.int32)
    w = jnp.asarray(rng.random((Q, k)), jnp.float32)
    experts = _experts(held[1] - held[0], E, I, 12, gated)
    if plan is not None:
        monkeypatch.setattr(axk1, "routed_plan", lambda *a: plan)
    with jax.default_matmul_precision("highest"):
        y, counters = axk1.routed_experts(x, valid, idx, w, experts, held,
                                          n_all)
        want = _by_loop(x, valid, idx, w, experts, held)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(y, want, atol=ORDER_OF_SUM)
    on = (np.asarray(idx) >= 2) & (np.asarray(idx) < 10) \
        & np.asarray(valid)[:, None]
    assert int(counters[0]) == on.sum() and int(counters[2]) == valid.sum()
    assert int(counters[3]) % 8 == 0 and int(counters[4]) == 0
    np.testing.assert_array_equal(y[~np.asarray(valid)], 0.0)


def test_what_is_no_expert_is_refused_and_the_plan_prices_the_form():
    x = jnp.zeros((8, 16), jnp.float32)
    idx, w = jnp.zeros((8, 2), jnp.int32), jnp.ones((8, 2), jnp.float32)
    with pytest.raises(ValueError, match=r"\(gate, up, down\) or \(up, down\)"):
        axk1.routed_experts(x, jnp.ones(8, bool), idx, w,
                            (jnp.zeros((4, 16, 8)),), (0, 4), 4)
    with pytest.raises(ValueError, match="2 or 3 matrices"):
        axk1.routed_plan(4, 4, 8, 2, 16, 8, 4)
    # the published launch shapes: a plain launch of 128 rows and a chunk
    # launch of 1,152, 128 of 512 experts held, 22 a row, 1,024 x 2,688
    for rows in (128, 1152):
        T, M, by_gather = axk1.routed_plan(128, 512, rows, 22, 1024, 2688, 2)
        assert T in axk1.ROW_TILES and M % T == 0 and (M // T) % 2 == 1
        assert not by_gather
    # the default is the gated form: the other five cells' plans stand
    assert axk1.routed_plan(12, 192, 128, 8, 7168, 2048) \
        == axk1.routed_plan(12, 192, 128, 8, 7168, 2048, 3) == (16, 240, False)


# -- 4. serving through the pool ------------------------------------------------

def test_chunked_prefill_then_decode_through_pool_and_state_agrees(
        engine, make):
    """Prompts whose lengths straddle the chunk (16) and a prefill budget
    (24) that splits them at boundaries that are no multiple of it, then
    decode steps: every served token is the reference's first choice by
    its own logits, and what each slot's state rows hold once its request
    is in is what the reference's full forward leaves behind in the three
    ``M`` blocks (prefill in chunks + decode through ONE cache layer and
    THREE state layers against one pass from zero)."""
    eng, pool = engine, engine._pool
    chunks0 = eng.stats()["prefill_chunks"]
    for pair in ((15, 17), (33, 5)):
        prompts = [_ids(n, seed=n).tolist() for n in pair]
        handles = [eng.submit(p, 10) for p in prompts]
        outs = [[int(t) for t in h.stream()] for h in handles]
        while pool.n_active:               # the last launch's landing
            pass
        state = [np.asarray(a) for a in pool.state_data]
        assert state[0].shape[0] == state[1].shape[0] == 3
        for slot, (p, o) in enumerate(zip(prompts, outs)):
            assert len(o) == 10
            assert float(_gaps(make, p, o).max()) < ORDER_OF_SUM
            fed = p + o[:-1]               # the last token was never fed
            left = R.final_states(make, TOY, _padded(fed), len(fed))
            assert len(left) == 3
            for layer, (tail, h) in enumerate(left):
                np.testing.assert_allclose(state[0][layer, slot], tail,
                                           atol=ORDER_OF_SUM)
                np.testing.assert_allclose(state[1][layer, slot], h,
                                           atol=ORDER_OF_SUM)
    st = eng.stats()
    assert st["prefill_chunks"] - chunks0 >= 5 and st["preempts"] == 0
    assert st["prefix_hits"] == 0 and st["cached_blocks"] == 0
    assert pool.blocks_in_use == 0
    assert st["state"]["layers"] == 3 \
        and st["state"]["slot_bytes"] == SLOT_BYTES
    # the record: the cache's and the state's layers are stamped (1 and 3
    # of 6), and the two E blocks' counters ride the result
    rec = [c for c in eng.flight_recorder.snapshot()["cycles"]
           if c.get("launch_q")]
    assert {c["cache_layers"] for c in rec} == {1}
    assert {c["state_layers"] for c in rec} == {3}
    assert all({"state_slots", "ssm_rows", "moe_pairs", "moe_experts_hit",
                "moe_rows_walked"} <= set(c) for c in rec)
    routed = [c for c in rec if c.get("moe_rows")]
    # two E blocks, 4 choices a real row, half the experts held
    assert sum(c["moe_rows"] for c in routed) \
        == 2 * sum(c["launch_rows"] for c in routed)
    assert 0 < sum(c["moe_pairs"] for c in routed) \
        < 4 * sum(c["moe_rows"] for c in routed)
    assert all(c.get("moe_zero_pairs", 0) == 0 for c in rec)


def test_a_reused_slot_serves_a_fresh_sequence_from_zero(net, make):
    """ONE slot. Request A ends on an EOS the host learns one launch late,
    so a launch that still writes A's state is in the air when B takes the
    slot: B's text is what a fresh engine gave it, and the reference's."""
    pa, pb = _ids(21, seed=71).tolist(), _ids(19, seed=72).tolist()
    eng = GenerationEngine(net, num_slots=1, max_len=64, block_size=8,
                           prefill_budget=24)
    want = [int(t) for t in eng.submit(pb, 10).stream()]      # fresh
    a_alone = [int(t) for t in eng.submit(pa, 8).stream()]
    eos = a_alone[3]
    n_a = a_alone.index(eos) + 1
    ha = eng.submit(pa, 8, eos_token_id=eos)
    hb = eng.submit(pb, 10)
    got_a = [int(t) for t in ha.stream()]
    got = [int(t) for t in hb.stream()]
    eng.close()
    assert got_a == a_alone[:n_a]
    assert got == want
    assert float(_gaps(make, pb, got).max()) < ORDER_OF_SUM


def test_a_preempted_request_is_re_fed_to_the_same_tokens(net, make):
    """Two requests that outgrow four blocks: the younger is preempted —
    its state rows simply abandoned — re-admitted and re-fed from position
    0 (prompt + what it had generated, in chunks); both stay the
    reference's own text."""
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
    """The plan counts the three ``M`` blocks' state rows (the slots' and
    the row no slot owns) beside the ONE cache layer's blocks, and the
    analyzer bills the step kernel's state — an output aliased to its
    operand — once: a step that ran leaves no error, a budget short by a
    slot's state is refused."""
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           hbm_budget_bytes=1 << 30)
    assert eng._plan["fits"] and eng._plan["state_bytes"] == 3 * SLOT_BYTES
    assert eng._plan["pool_bytes"] == eng._pool.capacity_bytes \
        + 3 * SLOT_BYTES
    list(eng.submit(_ids(12, seed=5).tolist(), 3).stream())
    report = eng.analyze()
    eng.close()
    assert not [f for f in report.findings if f.severity == "error"]
    with pytest.raises(Exception, match="does not fit"):
        GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                         hbm_budget_bytes=eng._plan["static_peak_bytes"]
                         - SLOT_BYTES)


def test_a_block_computes_nothing_for_the_half_it_lacks(engine):
    """The compiled step's text by layer scope: an ``M`` block names the
    mixer's sections and no attention, no cache write, no FFN; the ``*``
    block no mixer and no FFN; an ``E`` block the expert layer's —
    ``latent_proj`` and ``shared_expert`` inside ``moe_experts`` — and
    nothing of a mixer."""
    import re
    Q, T = 32, 4
    text = engine._fused_step_fn(Q, T).jitted.lower(
        engine._params, engine._buffers, engine._pool_operand(),
        *engine._null_step_operands(Q, T)).as_text(debug_info=True)
    by_layer = {}
    for path in re.findall(r'"(jit\([^"]*)"', text):
        m = re.search(r"/layer(\d+)/", path)
        if m:
            by_layer.setdefault(int(m.group(1)), set()).add(
                DS.section_of(path))
    mixer = {DS.SSM_PROJ, DS.SSM_CONV, DS.SSM_SCAN}
    attention = {DS.QKV, DS.CACHE_WRITE, DS.ATTENTION}
    experts = {DS.ROUTER, DS.MOE_SCOPE, DS.LATENT_PROJ, DS.SHARED_EXPERT}
    for li in (0, 2, 5):
        assert mixer <= by_layer[li]
        assert not by_layer[li] & (attention | experts | {DS.MLP})
    assert attention <= by_layer[3]
    assert not by_layer[3] & (mixer | experts | {DS.MLP})
    for li in (1, 4):
        assert experts <= by_layer[li]
        assert not by_layer[li] & (mixer | attention | {DS.O_PROJ})


@pytest.mark.parametrize("kwargs,match", [
    (dict(spec_draft="auto"), "spec_draft does not compose with a recurrent "
                              "state"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes does not compose with "
                                    "a recurrent state"),
    (dict(kv_dtype="int8", block_size=32), "int8/fp8 KV blocks do not "
                                           "compose with a recurrent state"),
    (dict(mesh="a mesh"), "beside attention or in place of it"),
], ids=["drafter", "host_tier", "int8-blocks", "mesh"])
def test_what_needs_a_state_snapshot_is_refused_by_name(net, kwargs, match):
    """The drafter head the model is published with (``*E``) would be
    speculation over a recurrence: refused by name, as the other three."""
    with pytest.raises(ValueError, match=match):
        GenerationEngine(net, num_slots=2, max_len=32, **kwargs)
