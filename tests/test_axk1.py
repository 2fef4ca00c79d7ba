"""A.X-K1 (latent attention + routed experts) against its plain reference
(``benchmark/lib/reference_axk1.py``: float32, ``highest``, no cache, no
kernels), at ``AXK1Config.tiny()`` sizes with the benchmark's seeded
weights, on the CPU in float32. Logits are compared, never sampled
tokens. Every tolerance says why it is what it is: float32 sums of a few
hundred products in another order differ by ~1e-6 of a unit-RMS value, so
1e-4 on logits of spread 1 is two orders of room and still three under
what bfloat16 or int8 anywhere would give (1e-2 and up).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import family_axk1 as F
from benchmark.lib import reference_axk1 as R
from paddle_tpu.models import axk1 as AX
from paddle_tpu.models.decoder_spec import serving_decoder
from paddle_tpu.serving import GenerationEngine

import _toys

SEED = _toys.SEEDS["axk1"]
ORDER_OF_SUM = 1e-4        # see the module doc


def _model(**over):
    """The ``model`` group of a configuration at toy sizes."""
    return _toys.config("axk1", **over)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def net():
    return _toys.seeded("axk1")


@pytest.fixture(scope="module")
def make():
    return _toys.weights("axk1")


def _ids(rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        1, 256, size=(rows, length)).astype(np.int32)


# -- 1. MLA: naive = absorbed = the program's layer, YaRN past 32 ------------

def test_mla_naive_absorbed_and_the_program_agree_past_the_original_context(
        net, make, model):
    ids = _ids(2, 96)                 # original_max_position_embeddings: 32
    naive = R.logits(make, model, ids)
    absorbed = R.logits(make, model, ids, form="absorbed", q_block=32)
    program = np.asarray(net(jnp.asarray(ids))._data)
    assert float(naive.std()) > 0.5                   # logits of spread ~1
    np.testing.assert_allclose(absorbed, naive, atol=ORDER_OF_SUM)
    np.testing.assert_allclose(program, naive, atol=ORDER_OF_SUM)


def test_yarn_frequencies_ramp_between_interpolated_and_unscaled(model):
    d = R.Dims.of(model)
    ref = R.yarn_inv_freq(d)
    got = AX.yarn_inv_freq(d.rope, d.theta, model["rope_scaling"])
    np.testing.assert_allclose(got, ref, rtol=1e-7)   # the same float64 formula
    plain = 10000.0 ** (-np.arange(0, 16, 2) / 16.0)
    assert ref[0] == pytest.approx(plain[0])          # fast: unscaled
    assert ref[-1] == pytest.approx(plain[-1] / 4.0)  # slow: f / factor
    assert np.all(np.diff(ref / plain) <= 1e-6)       # and a ramp between
    assert AX.yarn_attention_scale(AX.AXK1Config.tiny()) == pytest.approx(
        32 ** -0.5 * (0.1 * np.log(4.0) + 1.0) ** 2)
    assert AX.yarn_attention_scale(AX.AXK1Config()) == pytest.approx(
        192 ** -0.5 * 1.3466 ** 2, rel=1e-4)


# -- 2. the router -------------------------------------------------------------

def test_router_scores_top_k_normalisation_and_scale():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((5, 64)).astype(np.float32)
    wg = (rng.standard_normal((16, 64)) / 8).astype(np.float32)
    idx, w, scores = AX.route_top_k(jnp.asarray(x), jnp.asarray(wg), 4, 2.5)
    by_hand = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ wg.T)))
    np.testing.assert_allclose(np.asarray(scores), by_hand, atol=1e-6)
    for r in range(5):
        top = np.argsort(-by_hand[r])[:4]
        assert sorted(np.asarray(idx[r])) == sorted(top)
        want = 2.5 * by_hand[r][np.asarray(idx[r])] / by_hand[r][top].sum()
        np.testing.assert_allclose(np.asarray(w[r]), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)


def test_a_near_tie_is_decided_by_float32_scores():
    """Two experts whose scores (0.62338, 0.62429) differ by 9e-4: one
    bfloat16 step at 0.62 is 3.9e-3 and both round to 0.625, so bfloat16
    scores tie and top-k would keep the LOWER
    index; float32 scores keep the higher score, as the reference does."""
    bf = jnp.bfloat16
    x = jnp.zeros((1, 64), bf).at[0, 0].set(1.0)
    wg = jnp.full((16, 64), -4.0, bf)                 # everyone else: ~0.018
    wg = wg.at[3, 0].set(0.50390625).at[9, 0].set(0.5078125)   # one step up
    idx, w, scores = AX.route_top_k(x, wg, 1, 2.5)
    assert scores.dtype == jnp.float32
    assert float(scores[0, 9]) > float(scores[0, 3])
    assert scores[0, 9].astype(bf) == scores[0, 3].astype(bf)
    assert int(idx[0, 0]) == 9
    d = R.Dims.of(_model(num_experts_per_tok=1))
    ref_idx, _, _ = R.route(d, wg.astype(jnp.float32), x.astype(jnp.float32))
    assert int(ref_idx[0, 0]) == 9


# -- 3. the shares add up ------------------------------------------------------

def test_the_shares_routed_parts_and_the_shared_expert_once_add_up(model):
    """Four chips of four experts each: what each share's layer adds beyond
    the shared expert, summed, plus the shared expert counted once, is the
    uncut reference's expert layer."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    valid = jnp.ones(24, bool)
    whole = _model(experts_held=[0, 16])
    lw = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        F.Weights(SEED, whole, "float32").layer(1))
    with jax.default_matmul_precision("highest"):
        want, _ = R.expert_ffn(R.Dims.of(whole), lw, x)
        shared = R._swiglu(x, lw["shared_gate"], lw["shared_up"],
                           lw["shared_down"])
    total = shared
    for lo in range(0, 16, 4):
        share = _model(experts_held=[lo, lo + 4])
        layer = F.build_lm(share, SEED, "float32").layers[1]
        out, counters = layer.ffn.apply(x, valid)
        total = total + (out - shared)
        assert int(counters[2]) == 24
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=ORDER_OF_SUM)


# -- 4. dropless; pad rows: test_axk1_experts.py -----------------------------


# -- 5. serving through the latent paged cache ---------------------------------

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


def test_chunked_prefill_then_decode_agrees_with_the_reference(
        net, make, model):
    """Prompts of 5 to 41 tokens in chunks of 16 (prefill_budget) over
    blocks of 8, twelve decode steps each: block borders are crossed in
    the chunks and in the decode, and every served token is the
    reference's first choice by its own logits (gap under 1e-4 of the
    row's spread: float32 against float32)."""
    prompts = [_ids(1, n, seed=n)[0].tolist() for n in (5, 19, 33, 41)]
    eng = GenerationEngine(net, num_slots=4, max_len=64, block_size=8,
                           prefill_budget=16)
    handles = [eng.submit(p, 12) for p in prompts]
    outs = [[int(t) for t in h.stream()] for h in handles]
    rec = eng.flight_recorder.snapshot()["cycles"]
    st = eng.stats()
    eng.close()
    assert st["kv_dtype"] == "float32" and st["prefill_chunks"] >= 7
    for p, o in zip(prompts, outs):
        assert len(o) == 12
        assert float(_gaps(make, model, p, o).max()) < ORDER_OF_SUM
    # the routed layers' counters ride the launch's one fetch
    launch = [c for c in rec if "moe_rows" in c]
    assert launch and all(c["moe_rows"] == 2 * c["launch_rows"]
                          for c in launch)            # two expert layers
    assert all(0 <= c["moe_pairs"] <= 4 * c["moe_rows"] for c in launch)
    assert all(c["kv_row_tokens"] >= c["launch_rows"] for c in launch)


# the one engine of the tests that only serve a few requests: two slots,
# contexts of up to six blocks of 8, chunks of at most 16 tokens
TWO_SLOTS = dict(num_slots=2, max_len=48, block_size=8, prefill_budget=16)


def test_a_preempted_request_resumes_and_still_agrees(net, make, model):
    """Two requests whose growth exceeds six blocks: the younger is
    preempted, re-admitted and replayed through chunks; both stay the
    reference's own text."""
    pa, pb = _ids(1, 6, seed=61)[0].tolist(), _ids(1, 7, seed=62)[0].tolist()
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8, num_blocks=4,
                           prefill_budget=16)
    ha, hb = eng.submit(pa, 22), eng.submit(pb, 22)
    oa = [int(t) for t in ha.stream()]
    ob = [int(t) for t in hb.stream()]
    preempts = eng.stats()["preempts"]
    eng.close()
    assert preempts >= 1
    assert float(_gaps(make, model, pa, oa).max()) < ORDER_OF_SUM
    assert float(_gaps(make, model, pb, ob).max()) < ORDER_OF_SUM
    assert eng._pool.blocks_in_use == 0


def test_a_cow_copy_moves_a_latent_block_in_every_layer(net, engines):
    """Paging, COW and the prefix trie work on block ids: the engine's
    copy program clones block ``src`` over ``dst`` across every layer of
    the latent pool ``[L, NB + 1, 1, bs, lanes]`` as of any other."""
    eng = engines(net, **TWO_SLOTS)     # fresh here: the file's first use
    pool = eng._pool
    assert pool.shape == (3, pool.num_blocks + 1, 1, 8, 128)
    list(eng.submit(_ids(1, 20, seed=8)[0].tolist(), 2).stream())
    before = np.asarray(pool.data)
    src = int(np.argmax(np.abs(before[0, :, 0]).sum(axis=(1, 2))))
    dst = pool.num_blocks                   # a block nothing has touched
    assert np.abs(before[:, src]).sum() > 0 and not before[:, dst].any()
    eng._run_copy(dst, src)
    after = np.asarray(pool.data)
    np.testing.assert_array_equal(after[:, dst], before[:, src])
    np.testing.assert_array_equal(after[:, src], before[:, src])


def test_a_shared_prefix_is_served_from_the_trie(net, make, model, engines):
    pre = _ids(1, 24, seed=9)[0].tolist()
    eng = engines(net, **TWO_SLOTS)
    before = eng.stats()
    first = [int(t) for t in eng.submit(pre + [5, 6], 4).stream()]
    again = [int(t) for t in eng.submit(pre + [7, 8, 9], 4).stream()]
    st = eng.stats()
    assert st["prefix_hits"] - before["prefix_hits"] >= 1
    assert st["prefill_tokens_saved"] - before["prefill_tokens_saved"] >= 16
    assert float(_gaps(make, model, pre + [5, 6], first).max()) < ORDER_OF_SUM
    assert float(_gaps(make, model, pre + [7, 8, 9], again).max()) \
        < ORDER_OF_SUM


# -- the decoder spec and the refusals ------------------------------------------

def test_the_decoder_spec_describes_both_models(net):
    ax = serving_decoder(net).spec
    assert ax.attention == "latent"
    assert [ls.ffn for ls in ax.layers] == ["dense", "routed", "routed"]
    assert (ax.cache.rows, ax.cache.lanes, ax.cache.v_aliases_k,
            ax.cache.v_lanes) == (1, 128, True, 32)
    gpt = serving_decoder(_toys.default("gpt2")).spec
    assert gpt.attention == "full"
    assert {ls.ffn for ls in gpt.layers} == {"dense"}
    assert (gpt.cache.rows, gpt.cache.lanes, gpt.cache.v_aliases_k) \
        == (4, 32, False)
    assert AX.AXK1Config().latent_lanes == 640       # 576 -> whole tiles
    with pytest.raises(TypeError, match="serving_decoder"):
        serving_decoder(object())


@pytest.mark.parametrize("kwargs,match", [
    (dict(mesh="a mesh"), "tensor-parallel"),
    (dict(spec_draft="auto"), "spec_draft"),
    (dict(kv_dtype="int8"), "int8/fp8 KV blocks"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
])
def test_what_a_latent_pool_cannot_do_yet_is_refused_by_name(net, kwargs,
                                                             match):
    kw = dict(num_slots=2, max_len=32, block_size=8)
    kw.update(kwargs)
    with pytest.raises(ValueError, match=match):
        GenerationEngine(net, **kw)
