"""The plain reference of A.X-K1 against the equations written out by
hand for two tokens (loops over heads, positions and experts in float64),
and the shares adding up on the reference alone."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_axk1 as R
from benchmark.lib import weights_axk1 as W

SEED = 2 ** 31 + 11
MODEL = {
    "vocab_size": 16, "hidden_size": 8, "intermediate_size": 12,
    "moe_intermediate_size": 6, "num_hidden_layers": 2,
    "num_attention_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 4,
    "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 8},
    "max_position_embeddings": 32, "experts_held": [0, 4],
    "weight_scales": {"gain": 1.0, "norm_std": 0.1, "embed_std": 1.0}}


class Make:
    def __init__(self, model):
        self.model = model

    def embed(self):
        return W.embed(SEED, self.model, "float32")

    def layer(self, i):
        return W.layer_leaves(SEED, i, self.model, "float32")

    def final_norm(self):
        return W.final_norm(SEED, self.model, "float32")

    def head(self):
        return W.head(SEED, self.model, "float32")


def _f64(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), tree)


def _rms(x, g):
    return x / math.sqrt(float(np.mean(x * x)) + 1e-6) * g


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _swiglu(x, gate, up, down):
    return (_silu(x @ gate) * (x @ up)) @ down


def _rope1(x, pos):
    """Two dims, one frequency: dim 0 of 1 is ``theta^0 = 1`` unscaled and,
    under YaRN with factor 4, its ramp position decides the mix."""
    f = float(R.yarn_inv_freq(R.Dims.of(MODEL))[0])
    c, s = math.cos(pos * f), math.sin(pos * f)
    return np.asarray([x[0] * c - x[1] * s, x[0] * s + x[1] * c])


def _by_hand(ids):
    m = Make(MODEL)
    x = [_f64(m.embed())[t] for t in ids]
    scale = 4 ** -0.5 * (0.1 * math.log(4.0) + 1.0) ** 2
    for li in range(2):
        lw = _f64(m.layer(li))
        h = [_rms(v, lw["attn_norm"]) for v in x]
        q, ckv, kpe = [], [], []
        for p, v in enumerate(h):
            qq = (_rms(v @ lw["wq_a"], lw["q_norm"]) @ lw["wq_b"]).reshape(2, 4)
            q.append([(qq[hd, :2], _rope1(qq[hd, 2:], p)) for hd in range(2)])
            kv = v @ lw["wkv_a"]
            ckv.append(_rms(kv[:4], lw["kv_norm"]))
            kpe.append(_rope1(kv[4:], p))
        out = []
        for p in range(len(ids)):
            heads = []
            for hd in range(2):
                kvb = [(c @ lw["wkv_b"]).reshape(2, 4)[hd] for c in ckv]
                s = np.asarray([(q[p][hd][0] @ kvb[k][:2]
                                 + q[p][hd][1] @ kpe[k]) * scale
                                for k in range(p + 1)])
                w = np.exp(s - s.max())
                w /= w.sum()
                heads.append(sum(w[k] * kvb[k][2:] for k in range(p + 1)))
            out.append(np.concatenate(heads) @ lw["wo"])
        x = [a + b for a, b in zip(x, out)]
        for p, v in enumerate(x):
            hh = _rms(v, lw["ffn_norm"])
            if li == 0:
                y = _swiglu(hh, lw["gate"], lw["up"], lw["down"])
            else:
                g = 1.0 / (1.0 + np.exp(-(lw["router"] @ hh)))
                top = np.argsort(-g)[:2]
                y = _swiglu(hh, lw["shared_gate"], lw["shared_up"],
                            lw["shared_down"])
                for e in top:
                    y = y + 2.5 * g[e] / g[top].sum() * _swiglu(
                        hh, lw["experts_gate"][e], lw["experts_up"][e],
                        lw["experts_down"][e])
            x[p] = v + y
    g, head = _f64(m.final_norm()), _f64(m.head())
    return np.stack([_rms(v, g) @ head for v in x])


def test_two_tokens_against_the_equations_by_hand():
    ids = np.asarray([[3, 11]], np.int32)
    want = _by_hand(ids[0])
    for form in ("naive", "absorbed"):
        got = R.logits(Make(MODEL), MODEL, ids, form=form)[0]
        # float32 against float64 over a few dozen terms
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(want).max() > 0.1


def test_the_shares_add_up_on_the_reference_alone():
    """Two chips of two experts: each share's layer less the shared
    expert, summed, plus the shared expert once = the uncut layer."""
    d = R.Dims.of(MODEL)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((9, 8)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = Make(MODEL).layer(1)
        want, _ = R.expert_ffn(d, whole, x)
        shared = R._swiglu(x, whole["shared_gate"], whole["shared_up"],
                           whole["shared_down"])
        total = shared
        for lo in (0, 2):
            share = dict(MODEL, experts_held=[lo, lo + 2])
            lw = Make(share).layer(1)
            np.testing.assert_array_equal(
                np.asarray(lw["experts_up"]),
                np.asarray(whole["experts_up"])[lo:lo + 2])
            part, _ = R.expert_ffn(R.Dims.of(share), lw, x)
            total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)


def test_a_capped_expert_that_overflows_says_so():
    d = R.Dims.of(MODEL)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((16, 8)),
                    jnp.float32)
    lw = Make(MODEL).layer(1)
    with jax.default_matmul_precision("highest"):
        dense, _ = R.expert_ffn(d, lw, x)
        capped, over = R.expert_ffn(d, lw, x, cap=15)
        _, over_small = R.expert_ffn(d, lw, x, cap=2)
    np.testing.assert_allclose(np.asarray(capped), np.asarray(dense),
                               atol=1e-5)
    assert int(over) == 0 and int(over_small) > 0


def test_the_int8_control_moves_the_logits():
    ids = np.asarray([[3, 11, 7, 1]], np.int32)
    plain = R.logits(Make(MODEL), MODEL, ids)
    control = R.logits(Make(MODEL), MODEL, ids, quant="int8")
    assert 1e-3 < np.abs(plain - control).max() < 2.0
    with pytest.raises(ValueError, match="unknown quant"):
        R.logits(Make(MODEL), MODEL, ids, quant="fp4")
