"""LongCat-Flash (two latent-attention blocks and two dense FFNs a layer,
the routed experts on a shortcut around the second pair, a third of the
router's outputs identity experts) against its plain reference
(``benchmark/lib/reference_longcat.py``: float32, ``highest``, no cache,
no kernels), at the toy sizes of
``benchmark/tests/data/tiny-longcat-config.json`` (2 published layers = 4
sub-blocks, a router of 16 + 8 identity outputs, top 4, experts 4..11
held) with the benchmark's seeded weights, on the CPU in float32. Logits
are compared, never sampled tokens. The tolerance is ``test_axk1.py``'s:
float32 sums of a few hundred products in another order differ by ~1e-6
of a unit-RMS value, so 1e-4 on logits of spread 1 is two orders of room
and still two under what bfloat16 anywhere would give.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import family_longcat as F
from benchmark.lib import reference_longcat as R
from paddle_tpu.models import axk1 as AX
from paddle_tpu.models.decoder_spec import serving_decoder

import _toys

SEED = _toys.SEEDS["longcat"]
ORDER_OF_SUM = 1e-4        # see the module doc
# the file's one engine: two slots, contexts of up to eight blocks of 8,
# chunks of at most 16 tokens
TWO_SLOTS = dict(num_slots=2, max_len=64, block_size=8, prefill_budget=16)


@pytest.fixture(scope="module")
def model():
    return _toys.config("longcat")


@pytest.fixture(scope="module")
def net():
    return _toys.seeded("longcat")


@pytest.fixture(scope="module")
def make():
    return _toys.weights("longcat")


def _ids(rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        1, 256, size=(rows, length)).astype(np.int32)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# -- 1. the plain forward ------------------------------------------------------

def test_forward_agrees_with_the_reference_and_the_multipliers_matter(
        net, make, model):
    ids = _ids(2, 48)
    want = R.logits(make, model, ids, q_block=16)
    got = np.asarray(net(jnp.asarray(ids))._data)
    assert float(want.std()) > 0.5                    # logits of spread ~1
    np.testing.assert_allclose(got, want, atol=ORDER_OF_SUM)
    # the two MLA multipliers are part of the mathematics: without them
    # the reference gives other logits
    off = R.logits(make, dict(model, mla_scale_q_lora=False,
                              mla_scale_kv_lora=False), ids, q_block=16)
    assert float(np.abs(off - want).max()) > 100 * ORDER_OF_SUM
    d = R.Dims.of(model)
    assert (d.q_scale, d.kv_scale) == pytest.approx(
        ((64 / 48) ** 0.5, 2 ** 0.5))
    assert net.layers[0].attn.scale == pytest.approx(32 ** -0.5)   # no YaRN


def test_the_spec_is_two_layers_a_published_layer_in_one_cache_group(net):
    spec = serving_decoder(net).spec
    assert [(ls.attention, ls.ffn, ls.shortcut, ls.routes)
            for ls in spec.layers] == [
        ("latent", "dense", 1, True), ("latent", "dense", 0, False)] * 2
    (group,) = spec.cache_groups
    assert group.layers == (0, 1, 2, 3) and group.attention == "latent"
    assert (spec.cache.rows, spec.cache.lanes, spec.cache.v_lanes) \
        == (1, 128, 32)
    from paddle_tpu.models.longcat import LongCatConfig
    assert LongCatConfig().latent_lanes == 640
    assert (LongCatConfig().q_scale, LongCatConfig().kv_scale) \
        == pytest.approx((2.0, 12 ** 0.5))
    assert LongCatConfig().router_width == 768


# -- 2. the router, the identity experts, the shares ---------------------------

def test_the_choice_is_by_score_plus_bias_and_the_weights_are_the_scores(
        make, model):
    d = R.Dims.of(model)
    lw = _f32(make.layer(0))
    u = jnp.asarray(np.random.default_rng(3).standard_normal((64, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w, g = R.route(d, lw["router"], lw["router_bias"], u)
        plain, _, _ = R.route(d, lw["router"], lw["router_bias"], u,
                              select_bias=False)
    idx, plain, g, w = (np.asarray(a) for a in (idx, plain, g, w))
    differs = [set(a) != set(b) for a, b in zip(idx, plain)]
    assert 0 < sum(differs) < 64          # on some rows, not on all
    np.testing.assert_allclose(w, 6 * np.take_along_axis(g, idx, -1),
                               rtol=1e-6)
    np.testing.assert_allclose(g.sum(-1), 1.0, rtol=1e-5)   # over all 24
    assert not np.allclose(w.sum(-1), 6.0)                  # not normalised
    # the program's router is the same function
    pi, pw, _ = AX.route_top_k(u, lw["router"], 4, 6.0, norm=False,
                               scoring="softmax",
                               select_bias=lw["router_bias"])
    assert [set(r) for r in np.asarray(pi)] == [set(r) for r in idx]
    np.testing.assert_allclose(np.sort(np.asarray(pw)), np.sort(w),
                               rtol=1e-5)


def test_an_all_identity_row_gets_its_weights_times_the_row_and_nothing_else(
        net, monkeypatch):
    """Rows whose four choices are all identity experts get exactly ``(sum
    w) u``; a row with no HELD choice gets 0 from the grouped products; pad
    rows are neither routed nor counted."""
    moe = net.layers[0].moe
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.standard_normal((6, 64)), jnp.float32)
    # 0: all identity; 1: absent real experts only; 2: held + identity;
    # 3: held only; 4, 5: pad rows that name held and identity experts
    idx = jnp.asarray([[16, 17, 20, 23], [0, 1, 13, 15], [4, 5, 16, 17],
                       [4, 6, 8, 11], [4, 5, 16, 17], [16, 17, 18, 19]],
                      jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 0.9, (6, 4)), jnp.float32)
    valid = jnp.asarray([True] * 4 + [False] * 2)
    experts = (moe.experts_gate._data, moe.experts_up._data,
               moe.experts_down._data)
    y, counters = AX.routed_experts(u, valid, idx, w, experts, (4, 12), 24)
    assert np.all(np.asarray(y)[[0, 1, 4, 5]] == 0.0)
    assert np.abs(np.asarray(y)[[2, 3]]).max() > 0
    assert [int(c) for c in counters[:3]] == [2 + 4, 5, 4]
    assert int(counters[4]) == 0        # routed_experts counts none itself

    import paddle_tpu.models.longcat as LC
    monkeypatch.setattr(LC, "route_top_k", lambda *a, **k: (idx, w, None))
    m, counters = moe.apply(u, valid)
    m = np.asarray(m)
    np.testing.assert_array_equal(
        m[0], np.asarray(jnp.sum(w[0]) * u[0]))             # exactly
    assert np.all(m[1] == 0.0) and np.all(m[4:] == 0.0)
    np.testing.assert_allclose(
        m[2], np.asarray(y[2] + (w[2, 2] + w[2, 3]) * u[2]), rtol=1e-6)
    assert [int(c) for c in counters] == [6, 5, 4, int(counters[3]), 4 + 2]


def test_the_shares_the_identity_term_once_and_the_dense_path_once_add_up(
        make, model):
    """Four chips of four real experts each: what each share's routed part
    adds beyond the identity term, summed, plus the identity term counted
    ONCE and the dense FFN counted once, is the uncut reference's
    sub-block."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    valid = jnp.ones(24, bool)
    whole = _toys.config("longcat", experts_held=[0, 16])
    d = R.Dims.of(whole)
    lw = _f32(F.Weights(SEED, whole, "float32").layer(2))
    with jax.default_matmul_precision("highest"):
        u = R._rms_norm(x, lw["ffn_norm"], d.eps)
        want_m, _ = R.moe(d, lw, u)
        idx, w, _ = R.route(d, lw["router"], lw["router_bias"], u)
        identity = jnp.sum(jnp.where(idx >= 16, w, 0.0), -1)[:, None] * u
        dense = R._swiglu(u, lw["gate"], lw["up"], lw["down"])
    total, zero_pairs = identity, None
    for lo in range(0, 16, 4):
        share = _toys.config("longcat", experts_held=[lo, lo + 4])
        layer = F.build_lm(share, SEED, "float32").layers[2]
        y, counters, m = layer._ffn(x, valid)
        total = total + (m - identity)
        np.testing.assert_allclose(np.asarray(y - x), np.asarray(dense),
                                   atol=ORDER_OF_SUM)       # on every chip
        assert int(counters[2]) == 24
        zero_pairs = int(counters[4])
        assert zero_pairs == int(np.sum(np.asarray(idx) >= 16))   # whole
    assert 0 < zero_pairs < 24 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want_m),
                               atol=ORDER_OF_SUM)


# -- 3. serving through the latent paged cache ---------------------------------

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


def _zero_pairs_of(make, model, text, rows):
    """(row, identity expert) pairs the REFERENCE's router counts on the
    first ``rows`` rows of ``text``, both published layers."""
    d = R.Dims.of(model)
    width = -(-len(text) // 16) * 16
    ids = np.zeros((1, width), np.int32)
    ids[0, :len(text)] = text
    x = _f32(make.embed())[jnp.asarray(ids)]
    m, total = jnp.zeros_like(x), 0
    pos = jnp.arange(width, dtype=jnp.int32)
    for i in range(d.sub_blocks):
        lw = make.layer(i)
        if i % 2 == 0:
            with jax.default_matmul_precision("highest"):
                f = _f32(lw)
                xa = x[0] + R.attention(d, f, R._rms_norm(
                    x[0], f["attn_norm"], d.eps), pos, q_block=16)
                idx, _, _ = R.route(d, f["router"], f["router_bias"],
                                    R._rms_norm(xa, f["ffn_norm"], d.eps))
            total += int(np.sum(np.asarray(idx)[:rows] >= d.real))
        x, m, _ = R.sub_block(d, lw, x, m, opens=i % 2 == 0, q_block=16)
    return total


def test_chunked_prefill_then_decode_agrees_with_the_reference(
        net, make, model, engines):
    """Prompts of 5 to 41 tokens in chunks of 16 over blocks of 8, ten
    decode steps each, through all four latent caches: every served token
    is the reference's first choice by its own logits, and the launch
    counters say what the reference's router says."""
    prompts = [_ids(1, n, seed=n)[0].tolist() for n in (5, 19, 41)]
    eng = engines(net, **TWO_SLOTS)
    before = len(eng.flight_recorder.snapshot()["cycles"])
    handles = [eng.submit(p, 10) for p in prompts]
    outs = [[int(t) for t in h.stream()] for h in handles]
    _toys.settle(eng)
    rec = eng.flight_recorder.snapshot()["cycles"][before:]
    assert eng.stats()["kv_dtype"] == "float32"
    assert eng._pool.shape == (4, eng._pool.num_blocks + 1, 1, 8, 128)
    for p, o in zip(prompts, outs):
        assert len(o) == 10
        assert float(_gaps(make, model, p, o).max()) < ORDER_OF_SUM
    launch = [c for c in rec if "moe_rows" in c]
    # two published layers route; four sub-blocks hold a cache
    assert launch and all(c["moe_rows"] == 2 * c["launch_rows"]
                          for c in launch)
    assert all(c["moe_pairs"] + c["moe_zero_pairs"] <= 4 * c["moe_rows"]
               for c in launch)
    # the launches fed every row of every text but its last token (which
    # nothing follows): the identity pairs they counted are the ones the
    # reference's router counts on those rows
    assert sum(c["launch_rows"] for c in launch) \
        == sum(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    assert sum(c["moe_zero_pairs"] for c in launch) == sum(
        _zero_pairs_of(make, model, p + o, len(p) + len(o) - 1)
        for p, o in zip(prompts, outs)) > 0


def test_a_preempted_request_resumes_and_still_agrees(net, make, model):
    """Two requests whose growth exceeds four blocks: the younger is
    preempted, re-admitted and replayed through chunks; both stay the
    reference's own text — the carried value is recomputed with the
    rows, nothing of it outlives a launch."""
    from paddle_tpu.serving import GenerationEngine
    pa, pb = _ids(1, 6, seed=61)[0].tolist(), _ids(1, 7, seed=62)[0].tolist()
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           num_blocks=4, prefill_budget=16)
    ha, hb = eng.submit(pa, 22), eng.submit(pb, 22)
    oa = [int(t) for t in ha.stream()]
    ob = [int(t) for t in hb.stream()]
    preempts = eng.stats()["preempts"]
    eng.close()
    assert preempts >= 1
    assert float(_gaps(make, model, pa, oa).max()) < ORDER_OF_SUM
    assert float(_gaps(make, model, pb, ob).max()) < ORDER_OF_SUM
    assert eng._pool.blocks_in_use == 0


def test_the_steps_sections_put_the_experts_under_the_opening_sub_block(
        net, engines):
    """The compiled step names the new sections: ``zero_experts`` inside
    ``moe_experts`` under the OPENING sub-block's ``layer{i}``, the add that
    closes the shortcut under the CLOSING one's ``shortcut``."""
    from paddle_tpu.models.generation import build_fused_step_fn
    eng = engines(net, **TWO_SLOTS)
    fn = build_fused_step_fn(net, 2, 8, 1, 8)
    text = jax.jit(fn).lower(
        eng._params, eng._buffers, eng._pool_operand(),
        *eng._null_step_operands(8, 1)).as_text(debug_info=True)
    for opening, closing in ((0, 1), (2, 3)):
        assert f"layer{opening}/moe_experts/zero_experts/" in text
        assert f"layer{opening}/moe_experts/router/" in text
        assert f"layer{closing}/shortcut/add" in text
        assert f"layer{closing}/moe_experts/" not in text
        assert f"layer{opening}/shortcut/" not in text


@pytest.mark.parametrize("kwargs,match", [
    (dict(mesh="a mesh"), "tensor-parallel"),
    (dict(spec_draft="auto"), "spec_draft"),
    (dict(kv_dtype="int8"), "int8/fp8 KV blocks"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
])
def test_what_a_latent_pool_cannot_do_yet_is_refused_by_name(net, kwargs,
                                                             match):
    from paddle_tpu.serving import GenerationEngine
    kw = dict(num_slots=2, max_len=32, block_size=8)
    kw.update(kwargs)
    with pytest.raises(ValueError, match=match):
        GenerationEngine(net, **kw)
