"""The plain reference of LongCat-Flash against the equations written out
by hand for three tokens (loops over heads, positions and experts in
float64: two sub-blocks a layer, the shortcut carried by hand), and the
shares adding up on the reference alone."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import reference_longcat as R
from benchmark.lib import weights_longcat as W

SEED = 2 ** 31 + 46
MODEL = {
    "vocab_size": 16, "hidden_size": 8, "ffn_hidden_size": 12,
    "moe_intermediate_size": 6, "num_hidden_layers": 2,
    "attention_layers": 4, "first_k_dense_replace": 0,
    "num_attention_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 4,
    "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "n_routed_experts": 6, "zero_expert_num": 2, "moe_topk": 3,
    "routed_scaling_factor": 6, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
    "max_position_embeddings": 32, "experts_held": [0, 4],
    "weight_scales": {"gain": 1.0, "qk_gain": 1.5, "router_gain": 1.5,
                      "router_bias_std": 0.05, "norm_std": 0.1,
                      "embed_std": 1.0}}


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
    return x / math.sqrt(float(np.mean(x * x)) + 1e-5) * g


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _swiglu(x, gate, up, down):
    return (_silu(x @ gate) * (x @ up)) @ down


def _rope1(x, pos):
    """Two dims, one frequency: ``theta^0 = 1``, no scaling."""
    c, s = math.cos(pos), math.sin(pos)
    return np.asarray([x[0] * c - x[1] * s, x[0] * s + x[1] * c])


def _by_hand(ids):
    m = Make(MODEL)
    x = [_f64(m.embed())[t] for t in ids]
    q_scale, kv_scale = math.sqrt(8 / 4), math.sqrt(8 / 4)
    carried = None
    for sb in range(4):
        lw = _f64(m.layer(sb))
        h = [_rms(v, lw["attn_norm"]) for v in x]
        q, ckv, kpe = [], [], []
        for p, v in enumerate(h):
            qq = (_rms(v @ lw["wq_a"], lw["q_norm"]) @ lw["wq_b"]
                  * q_scale).reshape(2, 4)
            q.append([(qq[hd, :2], _rope1(qq[hd, 2:], p)) for hd in range(2)])
            kv = v @ lw["wkv_a"]
            ckv.append(_rms(kv[:4], lw["kv_norm"]) * kv_scale)
            kpe.append(_rope1(kv[4:], p))
        out = []
        for p in range(len(ids)):
            heads = []
            for hd in range(2):
                kvb = [(c @ lw["wkv_b"]).reshape(2, 4)[hd] for c in ckv]
                s = np.asarray([(q[p][hd][0] @ kvb[k][:2]
                                 + q[p][hd][1] @ kpe[k]) / math.sqrt(4)
                                for k in range(p + 1)])
                w = np.exp(s - s.max())
                w /= w.sum()
                heads.append(sum(w[k] * kvb[k][2:] for k in range(p + 1)))
            out.append(np.concatenate(heads) @ lw["wo"])
        x = [a + b for a, b in zip(x, out)]
        u = [_rms(v, lw["ffn_norm"]) for v in x]
        if sb % 2 == 0:                    # the shortcut opens
            carried = []
            for uu in u:
                logits = lw["router"] @ uu
                g = np.exp(logits - logits.max())
                g /= g.sum()
                top = np.argsort(-(g + lw["router_bias"]))[:3]
                mm = np.zeros(8)
                for e in top:
                    mm = mm + 6 * g[e] * (
                        uu if e >= 4 else _swiglu(
                            uu, lw["experts_gate"][e], lw["experts_up"][e],
                            lw["experts_down"][e]))
                carried.append(mm)
        x = [v + _swiglu(uu, lw["gate"], lw["up"], lw["down"])
             for v, uu in zip(x, u)]
        if sb % 2 == 1:                    # the shortcut closes
            x = [v + mm for v, mm in zip(x, carried)]
    g, head = _f64(m.final_norm()), _f64(m.head())
    return np.stack([_rms(v, g) @ head for v in x])


def test_three_tokens_against_the_equations_by_hand():
    ids = np.asarray([[3, 11, 7]], np.int32)
    want = _by_hand(ids[0])
    got = R.logits(Make(MODEL), MODEL, ids)[0]
    # float32 against float64 over a few dozen terms
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert np.abs(want).max() > 0.1
    # an identity expert was chosen somewhere, and a real one: both terms
    d = R.Dims.of(MODEL)
    lw = Make(MODEL).layer(0)
    u = jnp.asarray(np.random.default_rng(1).standard_normal((32, 8)),
                    jnp.float32)
    idx = np.asarray(R.route(d, lw["router"], lw["router_bias"], u)[0])
    assert (idx >= 4).any() and (idx < 4).any()


def test_the_shares_add_up_on_the_reference_alone():
    """Two chips of two real experts: each share's value less the identity
    term, summed, plus the identity term once = the uncut layer's."""
    d = R.Dims.of(MODEL)
    u = jnp.asarray(np.random.default_rng(0).standard_normal((9, 8)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = Make(MODEL).layer(0)
        want, _ = R.moe(d, whole, u)
        idx, w, _ = R.route(d, whole["router"], whole["router_bias"], u)
        identity = jnp.sum(jnp.where(idx >= 4, w, 0.0), -1)[:, None] * u
        total = identity
        for lo in (0, 2):
            share = dict(MODEL, experts_held=[lo, lo + 2])
            got, _ = R.moe(R.Dims.of(share), Make(share).layer(0), u)
            total = total + (got - identity)
            # a cap the rows exceed is reported, and the uncapped repeat
            # is the same value
            capped, overflow = R.moe(R.Dims.of(share), Make(share).layer(0),
                                     u, cap=1)
            assert int(overflow) > 0 or np.allclose(capped, got, atol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_the_int8_control_moves_the_logits():
    ids = np.asarray([[3, 11, 7, 2]], np.int32)
    plain = R.logits(Make(MODEL), MODEL, ids)
    low = R.logits(Make(MODEL), MODEL, ids, quant="int8")
    assert np.abs(plain - low).max() > 1e-3
