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

SEED = 2 ** 31 + 77
SCALES = {"gain": 1.0, "norm_std": 0.1, "embed_std": 1.0}
ORDER_OF_SUM = 1e-4        # see the module doc


def _model(**over):
    """The ``model`` group of a configuration at toy sizes."""
    cfg = AX.AXK1Config.tiny()
    m = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
        "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
        "rope_theta", "rope_scaling", "max_position_embeddings")}
    m.update(experts_held=[4, 12], weight_scales=SCALES)
    m.update(over)
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def net(model):
    return F.build_lm(model, SEED, "float32")


@pytest.fixture(scope="module")
def make(model):
    return F.Weights(SEED, model, "float32")


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


# -- 4. dropless; pad rows ------------------------------------------------------

def _experts(rng, n, E=64, I=32):
    return tuple(jnp.asarray(rng.standard_normal(s) / 8, jnp.float32)
                 for s in ((n, E, I), (n, E, I), (n, I, E)))


def _dense_experts(x, idx, w, experts, held):
    gate, up, down = (np.asarray(a, np.float64) for a in experts)
    x = np.asarray(x, np.float64)
    y = np.zeros_like(x)
    for r in range(x.shape[0]):
        for e, we in zip(np.asarray(idx[r]), np.asarray(w[r], np.float64)):
            if held[0] <= e < held[1]:
                j = e - held[0]
                g = x[r] @ gate[j]
                y[r] += we * ((g / (1 + np.exp(-g)) * (x[r] @ up[j])) @ down[j])
    return y


def _forced(monkeypatch, T, M, gather):
    """``routed_experts`` under the layout ``(T, M, combine)`` whatever
    the shapes say: toy shapes alone would never fill a second trip."""
    monkeypatch.setattr(AX, "routed_plan", lambda *shapes: (T, M, gather))


@pytest.mark.parametrize("gather", [False, True], ids=["product", "gather"])
def test_one_expert_gets_every_token_one_gets_none_and_nothing_is_dropped(
        monkeypatch, gather):
    rng = np.random.default_rng(4)
    Q, held = 40, (4, 8)
    x = jnp.asarray(rng.standard_normal((Q, 64)), jnp.float32)
    # every row chooses expert 4 and one of 6, 7, 12; nobody chooses 5
    idx = jnp.asarray(np.stack([np.full(Q, 4), rng.choice([6, 7, 12], Q)], 1),
                      jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, (Q, 2)), jnp.float32)
    experts = _experts(rng, 4)
    want = _dense_experts(x, idx, w, experts, held)
    # trips of 8 rows: many trips, expert 4 alone fills five; 64: one or two
    for T, M in ((1, 8), (8, 8), (8, 24), (1, 64), (16, 144)):
        _forced(monkeypatch, T, M, gather)
        y, (pairs, hit, rows, walked) = AX.routed_experts(
            x, jnp.ones(Q, bool), idx, w, experts, held, 16)
        np.testing.assert_allclose(np.asarray(y), want, atol=ORDER_OF_SUM)
        on_held = np.asarray(idx)[(np.asarray(idx) >= 4) & (np.asarray(idx) < 8)]
        assert (int(pairs), int(rows)) == (on_held.size, Q)
        assert int(hit) == 3                          # 4, 6, 7; never 5
        # every group padded to whole tiles, none for the expert without a pair
        assert int(walked) == sum(-(-int(c) // T) * T
                                  for c in np.bincount(on_held))


def _routed_case(name):
    """``(Q, k, experts, held, idx, valid)`` of a named layout case; rows
    choose distinct experts unless the case says otherwise. ``T`` is 8."""
    rng = np.random.default_rng(sum(map(ord, name)))
    Q, k, N, held, valid = 40, 2, 16, (0, 16), None
    draw = lambda Q, k, N: np.stack(
        [rng.permutation(N)[:k] for _ in range(Q)])
    if name == "no-pair-held":
        held, idx = (4, 8), draw(Q, k, 4)              # experts 0-3 only
    elif name == "all-on-one-expert":
        k, idx = 1, np.full((Q, 1), 5)
    elif name in ("exactly-T", "T-minus-1", "T-plus-1"):
        c = {"exactly-T": 8, "T-minus-1": 7, "T-plus-1": 9}[name]
        k, idx = 1, np.full((Q, 1), 3)                 # expert 2: c rows
        idx[:c, 0] = 2
    elif name == "pad-query-rows":
        idx = draw(Q, k, N)
        valid = rng.uniform(size=Q) < 0.6
    elif name == "held-range-inside":
        held, idx = (5, 11), draw(Q, k, N)
    elif name == "axk1-ep16":                          # 12 of 192, k 8, Q 128
        Q, k, N, held = 32, 4, 48, (3, 6)
        idx = draw(Q, k, N)
    elif name == "mimo-v2-flash-ep16":                 # 16 of 256, k 8
        Q, k, N, held = 48, 4, 64, (0, 4)
        idx = draw(Q, k, N)
    elif name == "sdar-30b-a3b-pp8":                   # all 128, k 8, 5/8 real
        Q, k, N, held = 64, 4, 32, (0, 32)
        idx = draw(Q, k, N)
        valid = np.arange(Q) % 8 < 5
    elif name == "lfm2-24b-a2b-pp4":                   # all 64, k 4
        Q, k, N, held = 72, 2, 16, (0, 16)
        idx = draw(Q, k, N)
    else:
        raise KeyError(name)
    valid = np.ones(Q, bool) if valid is None else valid
    return Q, k, N, held, idx.astype(np.int32), valid


ROUTED_CASES = ["no-pair-held", "all-on-one-expert", "exactly-T", "T-minus-1",
                "T-plus-1", "pad-query-rows", "held-range-inside",
                "axk1-ep16", "mimo-v2-flash-ep16", "sdar-30b-a3b-pp8",
                "lfm2-24b-a2b-pp4"]
# the layout forced on a case — one trip, two, three, a trip a tile, no
# alignment, each under both combines — and what the shapes themselves say
ROUTED_PLANS = {"one-trip": (8, 1096), "two-trips": 2, "three-trips": 3,
                "tile-trips": (8, 8), "unaligned": (1, 24)}
ROUTED_LAYOUTS = [(plan, gather) for plan in ROUTED_PLANS
                  for gather in (False, True)] + [("own-rule", None)]


@pytest.mark.parametrize(
    "plan,gather", ROUTED_LAYOUTS,
    ids=[p + {False: "-product", True: "-gather", None: ""}[g]
         for p, g in ROUTED_LAYOUTS])
@pytest.mark.parametrize("case", ROUTED_CASES)
def test_the_aligned_layout_is_the_dense_per_expert_sum(monkeypatch, case,
                                                        plan, gather):
    """Whatever the layout — tile, rows a trip, combine — the held experts'
    part is the sum a loop over rows and experts gives, the counters count
    real rows only, and the rows walked are every group's whole tiles."""
    Q, k, N, held, idx, valid = _routed_case(case)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((Q, 64)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, (Q, k)), jnp.float32)
    experts = _experts(rng, held[1] - held[0])
    routed = np.where(valid[:, None], idx, -1)
    on_held = routed[(routed >= held[0]) & (routed < held[1])] - held[0]
    counts = np.bincount(on_held, minlength=1)
    if plan == "own-rule":
        T, M, _ = AX.routed_plan(held[1] - held[0], N, Q, k, 64, 32)
    else:
        trips = ROUTED_PLANS[plan]
        T, M = trips if isinstance(trips, tuple) else (
            8, 8 * max(1, -(-int(sum(-(-counts // 8))) // trips)))
        _forced(monkeypatch, T, M, gather)
    y, counters = jax.jit(
        lambda *a: AX.routed_experts(*a, held, N))(
            x, jnp.asarray(valid), jnp.asarray(idx), w, experts)
    want = _dense_experts(x, routed, w, experts, held)
    np.testing.assert_allclose(np.asarray(y), want, atol=ORDER_OF_SUM)
    assert np.all(np.asarray(y)[~valid] == 0.0)
    assert [int(c) for c in counters] == [
        on_held.size, int(np.sum(counts > 0)), int(valid.sum()),
        int(sum(-(-counts // T) * T))]


# (held, experts, rows, k, E, I) of the routed-expert cells' launches
# (benchmark/configs; rows = the tower rows of their programs) -> the plan
CELL_PLANS = {
    "lfm2-24b-a2b-pp4": ((64, 64, 1152, 4, 2048, 1536), (128, 1152, True)),
    "sdar-30b-a3b-pp8": ((128, 128, 1024, 8, 2048, 768), (64, 1728, True)),
    "sdar-30b-a3b-pp8-chunk": ((128, 128, 2048, 8, 2048, 768),
                               (128, 1664, True)),
    "axk1-ep16": ((12, 192, 128, 8, 7168, 2048), (16, 240, False)),
    "axk1-ep16-chunk": ((12, 192, 1152, 8, 7168, 2048), (64, 960, False)),
    "mimo-v2-flash-ep16": ((16, 256, 1152, 8, 4096, 2048), (64, 1216, False)),
}


def _compilers_tile(rows):
    """The row tile the TPU's ragged dot walks ``rows`` rows in: the
    largest power of two up to 512 that divides them (PERF.md 44; held to
    the compiler itself in tests/test_tpu_compile.py)."""
    return min(512, rows & -rows)


@pytest.mark.parametrize("cell", list(CELL_PLANS))
def test_the_plan_is_a_function_of_shapes_and_a_trip_ends_on_its_tile(cell):
    shapes, want = CELL_PLANS[cell]
    T, M, by_gather = AX.routed_plan(*shapes)
    assert (T, M, by_gather) == want == AX.routed_plan(*shapes)
    n, N, rows, k, E, I = shapes
    # 41.2: a trip of 144 pairs was walked in tiles of 16. A trip is whole
    # tiles of T, and T is the tile the compiler walks it in: an expert's
    # rows begin on a tile and no tile holds two experts' rows
    assert T in AX.ROW_TILES and M % T == 0 and _compilers_tile(M) == T
    # the tile holds what an expert expects (a launch's rows x k over the
    # experts) with room, and is not the next size up from one that would
    each = rows * k / N
    assert each <= T <= max(AX.ROW_TILES[0], 4 * each)
    # issue 44, step 4: a trip's temporaries within the bound, half of it
    # where the way back keeps a buffer of the layout beside them
    assert M * (8 * E + 10 * I) <= AX.TRIP_BYTES // (2 if by_gather else 1)
    # a short layout (a share of the experts held) takes ONE trip: a tile
    # an expert and a spare fit it
    assert by_gather or M > n * T


@pytest.mark.parametrize("rows", [8, 64, 128, 144, 640, 1024, 1152, 2048,
                                  4096])
@pytest.mark.parametrize("n,N,k,E,I", [(12, 192, 8, 7168, 2048),
                                       (64, 64, 4, 2048, 1536),
                                       (128, 128, 8, 2048, 768),
                                       (4, 16, 4, 64, 32)])
def test_every_plan_walks_whole_tiles_of_its_own(rows, n, N, k, E, I):
    T, M, _ = AX.routed_plan(n, N, rows, k, E, I)
    assert T in AX.ROW_TILES and M >= T and _compilers_tile(M) == T


def test_pad_rows_change_neither_outputs_nor_counters():
    rng = np.random.default_rng(5)
    held = (0, 4)
    experts = _experts(rng, 4)
    x = jnp.asarray(rng.standard_normal((6, 64)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 6, (6, 2)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, (6, 2)), jnp.float32)
    y, counters = AX.routed_experts(x, jnp.ones(6, bool), idx, w, experts,
                                    held, 6)
    # the same rows scattered among pad rows that "choose" held experts
    at = np.asarray([0, 3, 8, 9, 17, 23])
    big = lambda a, fill: jnp.full((24,) + a.shape[1:], fill, a.dtype
                                   ).at[at].set(a)
    valid = jnp.zeros(24, bool).at[at].set(True)
    y2, counters2 = AX.routed_experts(big(x, 7.0), valid, big(idx, 1),
                                      big(w, 1.0), experts, held, 6)
    np.testing.assert_allclose(np.asarray(y2)[at], np.asarray(y),
                               atol=ORDER_OF_SUM)
    assert np.all(np.asarray(y2)[~np.asarray(valid)] == 0.0)
    # the rows walked may differ (another row count, another tile): the
    # three counters of real rows do not
    assert [int(c) for c in counters2[:3]] == [int(c) for c in counters[:3]]


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


def test_a_cow_copy_moves_a_latent_block_in_every_layer(net):
    """Paging, COW and the prefix trie work on block ids: the engine's
    copy program clones block ``src`` over ``dst`` across every layer of
    the latent pool ``[L, NB + 1, 1, bs, lanes]`` as of any other."""
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8)
    pool = eng._pool
    assert pool.shape == (3, pool.num_blocks + 1, 1, 8, 128)
    list(eng.submit(_ids(1, 20, seed=8)[0].tolist(), 2).stream())
    before = np.asarray(pool.data)
    src = int(np.argmax(np.abs(before[0, :, 0]).sum(axis=(1, 2))))
    dst = pool.num_blocks                   # a block nothing has touched
    assert np.abs(before[:, src]).sum() > 0 and not before[:, dst].any()
    eng._run_copy(dst, src)
    after = np.asarray(pool.data)
    eng.close()
    np.testing.assert_array_equal(after[:, dst], before[:, src])
    np.testing.assert_array_equal(after[:, src], before[:, src])


def test_a_shared_prefix_is_served_from_the_trie(net, make, model):
    pre = _ids(1, 24, seed=9)[0].tolist()
    eng = GenerationEngine(net, num_slots=2, max_len=48, block_size=8,
                           prefill_budget=16)
    first = [int(t) for t in eng.submit(pre + [5, 6], 4).stream()]
    again = [int(t) for t in eng.submit(pre + [7, 8, 9], 4).stream()]
    st = eng.stats()
    eng.close()
    assert st["prefix_hits"] >= 1 and st["prefill_tokens_saved"] >= 16
    assert float(_gaps(make, model, pre + [5, 6], first).max()) < ORDER_OF_SUM
    assert float(_gaps(make, model, pre + [7, 8, 9], again).max()) \
        < ORDER_OF_SUM


# -- the decoder spec and the refusals ------------------------------------------

def test_the_decoder_spec_describes_both_models(net):
    from paddle_tpu.models import GPTConfig, GPTForPretraining
    ax = serving_decoder(net).spec
    assert ax.attention == "latent"
    assert [ls.ffn for ls in ax.layers] == ["dense", "routed", "routed"]
    assert (ax.cache.rows, ax.cache.lanes, ax.cache.v_aliases_k,
            ax.cache.v_lanes) == (1, 128, True, 32)
    gpt = serving_decoder(GPTForPretraining(GPTConfig.tiny())).spec
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
