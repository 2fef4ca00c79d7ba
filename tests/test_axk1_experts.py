"""A.X-K1's expert layer alone (``models/axk1.py:routed_experts``, shared by
the four routed families): its LAYOUT against a dense per-expert sum under
forced ``(T, M, combine)`` plans, ``routed_plan``'s own picks for the
routed-expert cells, pad rows. No engine and no reference model: the served
model against ``reference_axk1`` is ``test_axk1.py``, of which this was
section 4 until a file had to fit a worker's share of the suite (PR 45).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import axk1 as AX

ORDER_OF_SUM = 1e-4        # float32 sums in another order: test_axk1.py's doc


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
        y, (pairs, hit, rows, walked, zero) = AX.routed_experts(
            x, jnp.ones(Q, bool), idx, w, experts, held, 16)
        assert int(zero) == 0         # no identity experts in this router
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
        int(sum(-(-counts // T) * T)), 0]


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
