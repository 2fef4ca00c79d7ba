"""The plain reference of MiMo-V2-Flash (``lib/reference_mimo.py``) against
facts worked out by hand, at toy sizes in float32 on the CPU: the window's
convention, the sink in the denominator, the partial rotary lanes, the
choice by score + bias, the share, and the layer-by-layer path against the
one-block path. The program is compared with it in ``tests/test_mimo.py``
(tier-1); here the reference itself is held to the equations."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import family_mimo as F
from benchmark.lib import reference_mimo as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "tests", "data",
                       "tiny-mimo-config.json")) as f:
    TOY = json.load(f)
MODEL = TOY["model"]
SEED = 2 ** 31 + 535


@pytest.fixture(scope="module")
def make():
    return F.Weights(SEED, MODEL, "float32")


def _window_layer(make):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  make.layer(1))


def _attention_by_hand(d, lw, h, window):
    """One head at a time, one row at a time, float64."""
    H, Hkv, Dk, Dv = d.swa if window else d.glob
    S = h.shape[0]
    rot = int(Dk * d.rotary_factor)
    theta = d.swa_theta if window else d.theta
    f = lambda a: np.asarray(a, np.float64)
    q = (f(h) @ f(lw["wq"])).reshape(S, H, Dk)
    k = (f(h) @ f(lw["wk"])).reshape(S, Hkv, Dk)
    v = d.value_scale * (f(h) @ f(lw["wv"])).reshape(S, Hkv, Dv)

    def rope(x):
        out = x.copy()
        for p in range(S):
            for i in range(rot // 2):
                ang = p * theta ** (-2.0 * i / rot)
                a, b = x[p, :, i], x[p, :, i + rot // 2]
                out[p, :, i] = a * np.cos(ang) - b * np.sin(ang)
                out[p, :, i + rot // 2] = b * np.cos(ang) + a * np.sin(ang)
        return out

    q, k = rope(q), rope(k)
    o = np.zeros((S, H, Dv))
    for i in range(S):
        first = max(0, i - d.window + 1) if window else 0
        for hh in range(H):
            kv = hh // (H // Hkv)
            s = k[first:i + 1, kv] @ q[i, hh] / np.sqrt(Dk)
            e = np.exp(s)
            denom = e.sum() + (np.exp(float(lw["sink"][hh])) if window else 0)
            o[i, hh] = (e / denom) @ v[first:i + 1, kv]
    return o.reshape(S, H * Dv) @ f(lw["wo"])


@pytest.mark.parametrize("layer,window", [(0, False), (1, True)],
                         ids=["global", "window"])
def test_attention_is_the_equations_row_by_row(make, layer, window):
    d = R.Dims.of(MODEL)
    lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                make.layer(layer))
    h = jnp.asarray(np.random.default_rng(1).standard_normal((20, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = R.attention(d, lw, h, jnp.arange(20, dtype=jnp.int32),
                          window=window, q_block=10)
    np.testing.assert_allclose(np.asarray(got),
                               _attention_by_hand(d, lw, h, window),
                               atol=2e-5)


def test_the_window_holds_w_keys_the_rows_own_included(make):
    """Changing the token at position p moves the window layer's output
    at rows p .. p + W - 1 and at no later row."""
    d = R.Dims.of(MODEL)
    lw = _window_layer(make)
    rng = np.random.default_rng(2)
    h = rng.standard_normal((24, 64)).astype(np.float32)
    h2 = h.copy()
    h2[4] += 1.0
    pos = jnp.arange(24, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(R.attention(d, lw, jnp.asarray(h), pos, window=True))
        b = np.asarray(R.attention(d, lw, jnp.asarray(h2), pos, window=True))
    moved = np.abs(a - b).max(axis=-1) > 1e-6
    assert moved[4:4 + d.window].all()
    assert not moved[:4].any() and not moved[4 + d.window:].any()


def test_the_sink_takes_mass_and_adds_no_value(make):
    d = R.Dims.of(MODEL)
    lw = _window_layer(make)
    h = jnp.asarray(np.random.default_rng(3).standard_normal((12, 64)),
                    jnp.float32)
    pos = jnp.arange(12, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        with_sink = np.asarray(R.attention(d, lw, h, pos, window=True))
        without = np.asarray(R.attention(R.Dims.of(MODEL, sinks=False), lw,
                                         h, pos, window=True))
        far = dict(lw, sink=jnp.full_like(lw["sink"], -60.0))
        none = np.asarray(R.attention(d, far, h, pos, window=True))
    np.testing.assert_allclose(none, without, atol=1e-6)   # a sink at -inf
    # row 0 sees one key: its weight is 1 / (1 + exp(sink - s)) < 1
    assert np.abs(with_sink[0]).max() < np.abs(without[0]).max()
    assert np.abs(with_sink - without).max() > 1e-2


def test_only_the_first_rotary_lanes_turn():
    x = jnp.asarray(np.random.default_rng(4).standard_normal((6, 2, 24)),
                    jnp.float32)
    pos = jnp.arange(6, dtype=jnp.int32) + 3
    out = np.asarray(R._rope(x, pos, 10000.0, 8))
    np.testing.assert_array_equal(out[..., 8:], np.asarray(x)[..., 8:])
    assert np.abs(out[..., :8] - np.asarray(x)[..., :8]).max() > 0.1
    # a rotation: the turned lanes keep their norm, pair by pair
    pair = lambda a, i: a[..., i] ** 2 + a[..., i + 4] ** 2
    for i in range(4):
        np.testing.assert_allclose(pair(out, i), pair(np.asarray(x), i),
                                   rtol=1e-5)
    assert int(192 * 0.334) == 64 and int(24 * 0.334) == 8


def test_layer_by_layer_with_a_cap_is_the_one_block_path(make):
    ids = np.random.default_rng(5).integers(1, 256, (4, 32)).astype(np.int32)
    whole = R.logits(make, MODEL, ids)
    blocks = R.hidden_states(make, MODEL, ids, rows_per_call=2, q_block=16,
                             cap_share=0.5)
    d = R.Dims.of(MODEL)
    with jax.default_matmul_precision("highest"):
        got = np.concatenate([np.asarray(R._linear(
            R._rms_norm(b, make.final_norm(), d.eps), make.head()))
            for b in blocks])
    np.testing.assert_allclose(got, whole, atol=1e-4)


def test_an_expert_over_its_cap_repeats_the_layer(make):
    """cap_share so small that every held expert overflows: the layer is
    repeated with every row and the result is the same."""
    ids = np.random.default_rng(6).integers(1, 256, (2, 32)).astype(np.int32)
    a = R.hidden_states(make, MODEL, ids, rows_per_call=2, cap_share=0.02)
    b = R.hidden_states(make, MODEL, ids, rows_per_call=2)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=1e-5)


def test_the_published_configuration_is_the_catalogs_row():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2-flash-ep16.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 16, 19072)
    assert len(cfg["hybrid_layer_pattern"]) == 48 \
        and len(cfg["moe_layer_freq"]) == 48       # the groups copied whole
    m = cfg["model"]
    assert m["hybrid_layer_pattern"] == cfg["hybrid_layer_pattern"][:7] \
        == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:7] == [0] + [1] * 6 \
        and m["first_k_dense_replace"] == 1
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "v_head_dim", "swa_num_key_value_heads", "swa_head_dim",
                "swa_v_head_dim", "sliding_window", "rope_theta",
                "swa_rope_theta", "partial_rotary_factor",
                "attention_value_scale", "num_experts_per_tok",
                "layernorm_epsilon"):
        assert m[key] == cfg[key], key
    assert m["n_routed_experts"] == 256 and m["experts_held"] == [0, 16]
    d = R.Dims.of(m)
    assert (d.glob, d.swa, d.window, d.layers) == (
        (64, 4, 192, 128), (64, 8, 192, 128), 128, 7)
    # the configuration file's own byte arithmetic
    g_att = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    w_att = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096
    experts = 16 * 3 * 4096 * 2048 + 256 * 4096
    total = (g_att + 3 * 4096 * 16384) + 5 * (w_att + experts) \
        + (g_att + experts) + 2 * 19072 * 4096
    assert round(g_att / 1e6, 2) == 89.13 and round(w_att / 1e6, 2) == 94.37
    assert round(total / 1e6) == 3430
