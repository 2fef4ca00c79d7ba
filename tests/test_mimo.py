"""MiMo-V2-Flash (window and global layers, sink logits, K 192 | V 128
lanes, a bias-corrected top-k) against its plain reference
(``benchmark/lib/reference_mimo.py``: float32, ``highest``, no cache, no
kernels), at ``MiMoV2Config.tiny()`` sizes with the benchmark's seeded
weights, on the CPU in float32. Logits are compared, never sampled
tokens. Every tolerance says why it is what it is: float32 sums of a few
hundred products in another order differ by ~1e-6 of a unit-RMS value, so
1e-4 on logits of spread 1 is two orders of room and still three under
what bfloat16 or int8 anywhere would give (1e-2 and up) — and two under
what each CONTROL moves them by (a fact of the mathematics left out or
off by one: 1e-2 at the least, asserted below).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import family_mimo as F
from benchmark.lib import reference_mimo as R
from paddle_tpu.models import axk1 as AX
from paddle_tpu.models import mimo as MM
from paddle_tpu.models.decoder_spec import serving_decoder
from paddle_tpu.serving import GenerationEngine

import _toys

SEED = _toys.SEEDS["mimo"]
ORDER_OF_SUM = 1e-4        # see the module doc
A_FACT_MOVES = 1e-2        # the least a control must move a logit by


def _model(**over):
    """The ``model`` group of a configuration at toy sizes: layers global
    (dense), window, window, global; window 8; 16 experts, top 4."""
    return _toys.config("mimo", **over)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def net():
    return _toys.seeded("mimo")


@pytest.fixture(scope="module")
def make():
    return _toys.weights("mimo")


def _ids(rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        1, 256, size=(rows, length)).astype(np.int32)


# -- 1. the plain forward pass -------------------------------------------------

@pytest.fixture(scope="module")
def forward(net):
    """``(ids, the program's logits)`` on two rows six windows long: one
    eager forward for the comparison and its six controls."""
    ids = _ids(2, 48)
    return ids, np.asarray(net(jnp.asarray(ids))._data)


def test_the_programs_forward_is_the_references(forward, make, model):
    ids, program = forward
    want = R.logits(make, model, ids, q_block=16)
    assert float(want.std()) > 0.5                 # logits of spread ~1
    np.testing.assert_allclose(program, want, atol=ORDER_OF_SUM)


@pytest.mark.parametrize("depart", [
    dict(sinks=False), dict(window=7), dict(window=9),
    dict(value_scale=1.0), dict(rotary=None), dict(select_bias=False),
], ids=["no-sinks", "window-127-of-128", "window-129-of-128",
        "value-scale-left-out", "rotary-on-every-lane",
        "selection-bias-left-out"])
def test_each_fact_of_the_mathematics_decides_the_logits(forward, make,
                                                         model, depart):
    """The controls: the reference with ONE fact changed no longer agrees
    with the program — so the seeded weights make that fact decide, and
    the comparison above would catch the program getting it wrong."""
    ids, program = forward
    off = R.logits(make, model, ids, q_block=16, depart=depart)
    assert float(np.abs(off - program).max()) > A_FACT_MOVES
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(program, off, atol=ORDER_OF_SUM)


def test_the_sinks_take_a_visible_share_of_the_mass(make, model):
    """Seeded sinks N(2.5, 1) against 8 keys of score spread 1.44."""
    lw = make.layer(1)
    assert lw["sink"].shape == (8,) and "sink" not in make.layer(0)
    share = np.exp(np.asarray(lw["sink"], np.float64)) / (
        np.exp(np.asarray(lw["sink"], np.float64)) + 8 * np.exp(1.44 ** 2 / 2))
    assert 0.05 < float(share.mean()) < 0.6


# -- 2. the router's selection bias -------------------------------------------

def test_the_choice_is_by_score_plus_bias_and_the_weights_by_score():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((6, 64)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((16, 64)) / 8, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32)
    idx, w, scores = AX.route_top_k(x, wr, 4, 1.0, True, select_bias=bias)
    plain, w_plain, _ = AX.route_top_k(x, wr, 4, 1.0, True)
    s = np.asarray(scores, np.float64)
    changed = 0
    for r in range(6):
        want = np.argsort(-(s[r] + np.asarray(bias, np.float64)))[:4]
        assert sorted(np.asarray(idx[r])) == sorted(want)
        np.testing.assert_allclose(
            np.asarray(w[r]), s[r][np.asarray(idx[r])] / s[r][want].sum(),
            rtol=1e-5)
        changed += sorted(np.asarray(idx[r])) != sorted(np.asarray(plain[r]))
    assert changed >= 2                   # the bias changes some choices
    none_idx, none_w, _ = AX.route_top_k(x, wr, 4, 1.0, True,
                                         select_bias=None)
    np.testing.assert_array_equal(np.asarray(none_idx), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(none_w), np.asarray(w_plain))


def test_the_seeded_bias_changes_some_choices(make, model):
    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                make.layer(1))
    x = jnp.asarray(np.random.default_rng(3).standard_normal((64, 64)),
                    jnp.float32)
    d = R.Dims.of(model)
    with_bias, _, _ = R.route(d, lw, x)
    without, _, _ = R.route(R.Dims.of(model, select_bias=False), lw, x)
    differ = sum(sorted(a) != sorted(b) for a, b in
                 zip(np.asarray(with_bias), np.asarray(without)))
    assert 8 <= differ <= 60


# -- 3. the shares add up ------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer(model):
    """Four chips of four experts each (the toy's 16): the parts that the
    shares' programs give for one expert layer add up to the uncut
    reference's layer — no shared expert, so nothing is counted twice."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    valid = jnp.ones(24, bool)
    whole = _model(experts_held=[0, 16])
    lw = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        F.Weights(SEED, whole, "float32").layer(1))
    with jax.default_matmul_precision("highest"):
        want, _ = R.expert_ffn(R.Dims.of(whole), lw, x)
    total, pairs = 0.0, 0
    for lo in range(0, 16, 4):
        share = _model(experts_held=[lo, lo + 4])
        layer = F.build_lm(share, SEED, "float32").layers[1]
        out, counters = layer.ffn.apply(x, valid)
        total = total + out
        pairs += int(counters[0])
        assert int(counters[2]) == 24
    assert pairs == 24 * 4               # every (row, expert) pair once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=ORDER_OF_SUM)


# -- 4. serving through both cache groups --------------------------------------

def _gaps(make, model, prompt, tokens):
    """Normalised reference gap of each served token (0 = the
    reference's own first choice)."""
    text = list(prompt) + list(tokens)
    width = -(-len(text) // 16) * 16
    ids = np.zeros((1, width), np.int32)
    ids[0, :len(text)] = text
    pos = (len(prompt) - 1 + np.arange(len(tokens)))[None]
    out = R.served_margins(make, model, ids, pos,
                           np.asarray(tokens, np.int32)[None],
                           rows_per_call=1, q_block=16)
    return out["gap"][0] / out["std"][0]


def test_the_spec_has_a_global_and_a_window_group(net):
    spec = serving_decoder(net).spec
    groups = spec.cache_groups
    assert [(g.window, g.layers) for g in groups] == [(0, (0, 3)),
                                                      (8, (1, 2))]
    assert [(g.cache.rows, g.cache.lanes, g.cache.kv_lanes)
            for g in groups] == [(2, 40, (24, 16)), (4, 40, (24, 16))]
    assert [ls.sinks for ls in spec.layers] == [False, True, True, False]
    assert [ls.ffn for ls in spec.layers] == ["dense"] + ["routed"] * 3
    assert [spec.layer_group(i) for i in range(4)] == [(0, 0), (1, 0),
                                                       (1, 1), (0, 1)]
    # the published widths: K 192 | V 128 stored 384 wide, V from lane 256
    assert MM.stored_lanes(192, 128) == 384
    big = MM.MiMoV2Config()
    assert big.rotary_lanes(False) == big.rotary_lanes(True) == 64
    assert big.hybrid_layer_pattern[:7] == [0, 1, 1, 1, 1, 0, 1]
    assert sum(big.hybrid_layer_pattern) == 39 and len(
        big.hybrid_layer_pattern) == 48


def test_chunked_prefill_then_decode_through_both_groups_agrees(
        net, make, model):
    """Prompts of 44 and 58 tokens in chunks of 16 (prefill_budget) over
    blocks of 8 and a window of 8, six decode steps each: contexts up to
    eight windows long (the toy's step is ~10 s a program here, and these
    two need three: q 8, 16 and 32 rows against tables of 8 blocks), so
    the window group frees blocks behind both slots while the global group
    keeps them all; every served token is the
    reference's first choice by its own logits (gap under 1e-4 of the
    row's spread: float32 against float32, prefill + decode through the
    cache against the full forward)."""
    prompts = [_ids(1, n, seed=n)[0].tolist() for n in (44, 58)]
    eng = GenerationEngine(net, num_slots=2, max_len=64, block_size=8,
                           prefill_budget=16)
    pool = eng._pool
    assert [(g.window, g.num_layers, g.num_heads, g.num_blocks)
            for g in pool.groups] == [(0, 2, 2, 16), (8, 2, 4, 8)]
    handles = [eng.submit(p, 6) for p in prompts]
    outs = [[int(t) for t in h.stream()] for h in handles]
    st = eng.stats()
    eng.close()
    rec = eng.flight_recorder.snapshot()["cycles"]
    assert st["prefill_chunks"] >= 7 and st["preempts"] == 0
    for p, o in zip(prompts, outs):
        assert len(o) == 6
        assert float(_gaps(make, model, p, o).max()) < ORDER_OF_SUM
    # freed behind the window, all returned at the end, prefix cache off
    assert st["window_blocks_freed"] >= 10
    assert st["prefix_hits"] == 0 and st["cached_blocks"] == 0
    assert pool.blocks_in_use == 0 and pool.group_blocks_in_use(1) == 0
    launch = [c for c in rec if "kv_tokens_window" in c]
    assert launch and all("kv_live_bytes" in c for c in launch)
    assert sum(c["window_blocks_freed"] for c in launch) \
        == st["window_blocks_freed"]
    for c in launch:
        assert c["kv_tokens_window"] <= c["kv_tokens"]
        assert c["kv_row_tokens_window"] <= c["kv_row_tokens"]
        # a window layer reads at most W - 1 + rows a slot
        assert c["kv_tokens_window"] <= c["launch_rows"] + 7 * 2
    # late in the run the window group holds far less than the global
    late = launch[-1]
    per_token = late["kv_live_bytes"] / late["kv_live_tokens"]
    uniform = 8 * 40 * 4 * (2 * 2 + 2 * 4) / 8      # bytes a token, float32
    assert per_token < 0.7 * uniform


def test_block_pressure_in_either_group_preempts_and_stays_exact(
        net, make, model):
    """Two requests that outgrow four blocks of the global group (contexts
    of 23 and 25 tokens: three and four blocks of 8, tables of 1, 2 and 4):
    the younger is preempted (BOTH groups' blocks come back), re-admitted
    and replayed through chunks; both stay the reference's own text."""
    pa, pb = _ids(1, 9, seed=61)[0].tolist(), _ids(1, 11, seed=62)[0].tolist()
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           num_blocks=4, prefill_budget=16)
    ha, hb = eng.submit(pa, 14), eng.submit(pb, 14)
    oa = [int(t) for t in ha.stream()]
    ob = [int(t) for t in hb.stream()]
    preempts = eng.stats()["preempts"]
    eng.close()
    assert preempts >= 1
    assert float(_gaps(make, model, pa, oa).max()) < ORDER_OF_SUM
    assert float(_gaps(make, model, pb, ob).max()) < ORDER_OF_SUM
    assert eng._pool.blocks_in_use == 0
    assert eng._pool.group_blocks_in_use(1) == 0


def test_the_plan_and_the_analyzer_take_the_grouped_step(net):
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           hbm_budget_bytes=1 << 30)
    assert eng._plan["fits"] and eng._plan["group_blocks"] == [8, 8]
    list(eng.submit(_ids(1, 12, seed=5)[0].tolist(), 3).stream())
    report = eng.analyze()
    eng.close()
    assert not [f for f in report.findings if f.severity == "error"]


# -- 5. the refusals ------------------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(spec_draft="auto"), "spec_draft does not compose with more than "
                              "one cache group"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes does not compose with "
                                    "more than one cache group"),
    (dict(kv_dtype="int8", block_size=32), "int8/fp8 KV blocks do not "
                                           "compose with more than one"),
    (dict(mesh="a mesh"), "does not compose with more than one cache group"),
], ids=["spec_draft", "host_tier", "int8-blocks", "mesh"])
def test_what_is_not_built_over_cache_groups_is_refused_by_name(
        net, kwargs, match):
    with pytest.raises(ValueError, match=match):
        GenerationEngine(net, num_slots=2, max_len=32, **kwargs)


def test_the_spec_refuses_what_the_groups_do_not_cover():
    from paddle_tpu.models import decoder_spec as DS
    full = DS.CacheSpec(rows=2, lanes=32)
    lat = DS.CacheSpec(rows=1, lanes=128, v_aliases_k=True, v_lanes=32)
    with pytest.raises(ValueError, match="full attention kind only"):
        DS.LayerSpec(DS.LATENT, lat, DS.DENSE, window=8)
    with pytest.raises(ValueError, match="more than one cache group"):
        DS.DecoderSpec((DS.LayerSpec(DS.FULL, full, DS.DENSE),
                        DS.LayerSpec(DS.LATENT, lat, DS.DENSE)), 256, 64)
    with pytest.raises(ValueError, match="more than one cache group"):
        DS.DecoderSpec(
            (DS.LayerSpec(DS.FULL, full, DS.DENSE),
             DS.LayerSpec(DS.FULL, full, DS.DENSE, window=8)), 256, 64,
            DS.GenerationRule(block_length=4, denoising_steps=4,
                              mask_token_id=255))
    with pytest.raises(ValueError, match="do not fit a stored row"):
        DS.CacheSpec(rows=2, lanes=32, k_lanes=24, v_lanes=16)
    one = DS.DecoderSpec((DS.LayerSpec(DS.FULL, full, DS.DENSE),) * 3, 256,
                         64)
    assert len(one.cache_groups) == 1 and one.cache_groups[0].layers == (
        0, 1, 2)
