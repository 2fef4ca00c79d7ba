"""The plain reference of MiMo-V2-Flash (``model_type: mimo_v2_flash``,
source ``https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/
config.json``) in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
paging, no batching policy, nothing imported from the program.

**The layers** (from the source's ``config``; ``x`` is ``[T, E]``,
positions absolute; ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``, ``eps =
layernorm_epsilon``). A layer is GLOBAL (``hybrid_layer_pattern`` 0:
``num_key_value_heads`` KV heads, ``rope_theta``) or WINDOW (1:
``swa_num_key_value_heads`` KV heads, ``sliding_window`` W,
``swa_rope_theta``, a learned sink logit a head)::

    h = rms(x; g1);  q = h W_q -> H heads of Dk;  k = h W_k -> Hkv heads of Dk
    v = attention_value_scale * (h W_v) -> Hkv heads of Dv    (no bias, no q/k norm)
    rotary on the FIRST int(Dk * partial_rotary_factor) lanes of q and k,
        half-split (lane i with lane i + rot/2); the rest not rotated
    query head j reads KV head j // (H / Hkv);  s = q . k / sqrt(Dk)
    GLOBAL: row i sees j <= i;            p = softmax(s)
    WINDOW: row i sees i - W + 1 <= j <= i (W keys, its own included);
            p_j = exp(s_j - m) / (sum_j' exp(s_j' - m) + exp(sink_h - m)),
            m = max(max_j s_j, sink_h): the sink takes mass, adds no value
    x = x + concat_heads(p v) W_o

    h2 = rms(x; g2)
    layer < first_k_dense_replace:  x = x + (silu(h2 W_g) * (h2 W_u)) W_d
    else: s = sigmoid(h2 W_r^T) over all n_routed_experts, float32;
          T = the num_experts_per_tok largest of s + b (b: the correction
          bias of topk_method noaux_tc; n_group 1: no group limit);
          w_e = s_e / sum_{T} s  (norm_topk_prob; routed_scaling_factor
          null read as 1);  x = x + sum_{e in T} w_e expert_e(h2)

final ``rms``, logits ``= x W_head``. No shared expert.

**Departures, each stated.**

* **The share.** With ``held = (lo, hi)`` the expert layer routes over
  all experts and adds only ``sum_{e in T, lo <= e < hi} w_e
  expert_e(x)`` (``w_e`` normalised over all ``k`` chosen); what the
  absent experts would add is left out and that partial sum goes on to
  the next layer (a row none of whose experts is held gets 0). ``lw``
  then holds the held experts only. The vocabulary slice is simply a
  smaller ``vocab_size``; the layers served are the first
  ``num_hidden_layers`` of the published pattern.
* The three multi-token-prediction layers the model card mentions are
  not in ``config`` and are not here.
* Read from the source's key names, not from its code (``assumed`` in the
  configuration file says each): which lanes rotate and how they pair;
  the value scale applied to ``v`` (linear: only rounding depends on
  where); the window's convention; the sink's form.
* Memory, not mathematics: queries go through attention in blocks of
  ``q_block`` rows (9,216 positions x 64 heads of scores would not fit
  otherwise), a block of a WINDOW layer's rows against the keys it can
  see at all (its own and the ``W - 1`` before it: the mask is the same,
  the columns that it would set to zero weight are not computed), and an
  expert is applied to the rows that chose it (at most
  ``cap``, and if one ever has more the caller repeats the layer with
  every row); ``served_margins`` runs LAYER BY LAYER: one layer's weights
  are made, every sampled row goes through it, then the next.

``quant="int8"`` computes every linear layer of the blocks and the head
(not the router, which a W8A8 deployment keeps in float32 too) with
weights rounded per output channel and activations per row to symmetric
8-bit integers: the CONTROL that a cell's limits must reject. The
benchmark's own runs never set it.

``Dims.of(model, **depart)`` replaces single facts of the mathematics
(``sinks=False``, ``window=127``, ``value_scale=1.0``, ``rotary=None``
for all lanes, ``select_bias=False``): the tests' controls, each of which
must fail the comparison with the program.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Dims:
    hidden: int
    pattern: tuple          # 1: window layer, 0: global, a served layer
    glob: tuple             # (H, Hkv, Dk, Dv) of a global layer
    swa: tuple              # ... of a window layer
    window: int
    theta: float
    swa_theta: float
    rotary_factor: float
    value_scale: float
    experts: int
    top_k: int
    routed_scale: float
    norm_topk: bool
    eps: float
    first_dense: int
    layers: int
    held: tuple
    sinks: bool = True
    select_bias: bool = True
    rotary: object = "partial"      # None: every lane

    @classmethod
    def of(cls, model: dict, **depart) -> "Dims":
        held = model.get("experts_held", (0, int(model["n_routed_experts"])))
        att = lambda p: tuple(int(model[p + k]) for k in (
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "v_head_dim"))
        L = int(model["num_hidden_layers"])
        d = cls(
            hidden=int(model["hidden_size"]),
            pattern=tuple(int(v) for v in model["hybrid_layer_pattern"][:L]),
            glob=att(""), swa=att("swa_"),
            window=int(model["sliding_window"]),
            theta=float(model["rope_theta"]),
            swa_theta=float(model["swa_rope_theta"]),
            rotary_factor=float(model["partial_rotary_factor"]),
            value_scale=float(model["attention_value_scale"]),
            experts=int(model["n_routed_experts"]),
            top_k=int(model["num_experts_per_tok"]),
            routed_scale=float(model.get("routed_scaling_factor") or 1.0),
            norm_topk=bool(model["norm_topk_prob"]),
            eps=float(model["layernorm_epsilon"]),
            first_dense=int(model["first_k_dense_replace"]), layers=L,
            held=(int(held[0]), int(held[1])))
        return replace(d, **depart) if depart else d


# -- pieces -------------------------------------------------------------------

def _round_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _linear(x, w, quant=None):
    """``x @ w`` with ``w`` [in, out]."""
    if quant == "int8":
        x = _round_int8(x, axis=-1)          # per row (token)
        w = _round_int8(w, axis=0)           # per output channel
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(x, w)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _swiglu(x, gate, up, down, quant=None):
    return _linear(jax.nn.silu(_linear(x, gate, quant))
                   * _linear(x, up, quant), down, quant)


def _rope(x, positions, theta, lanes):
    """The first ``lanes`` lanes of ``x [S, heads, D]`` turned half-split
    (lane ``i`` with lane ``i + lanes / 2``) by ``positions *
    theta^(-2i / lanes)``; the rest as they are."""
    inv = 1.0 / theta ** (np.arange(0, lanes, 2, dtype=np.float64) / lanes)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :lanes // 2], x[..., lanes // 2:lanes]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., lanes:]], axis=-1)


def attention(d: Dims, lw: dict, h, positions, *, window: bool, quant=None,
              q_block=None):
    """Attention of ONE sequence: ``h [S, E]`` (already normed),
    ``positions [S]`` -> ``[S, E]``, a global or a window layer."""
    S = h.shape[0]
    H, Hkv, Dk, Dv = d.swa if window else d.glob
    g = H // Hkv
    theta = d.swa_theta if window else d.theta
    lanes = Dk if d.rotary is None else int(Dk * d.rotary_factor)
    q = _rope(_linear(h, lw["wq"], quant).reshape(S, H, Dk), positions,
              theta, lanes)
    k = _rope(_linear(h, lw["wk"], quant).reshape(S, Hkv, Dk), positions,
              theta, lanes)
    v = d.value_scale * _linear(h, lw["wv"], quant).reshape(S, Hkv, Dv)
    sink = lw["sink"].reshape(Hkv, g) if window and d.sinks else None
    qb = S if not q_block else int(q_block)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")
    # memory and time, not mathematics: a block of a window layer's query
    # rows is scored against the keys its rows can see at all — the
    # block's own and the W - 1 before it — and not against all S
    band = window and d.window - 1 + qb < S
    if band:
        front = d.window - 1
        k_all = jnp.pad(k, ((front, 0), (0, 0), (0, 0)))
        v_all = jnp.pad(v, ((front, 0), (0, 0), (0, 0)))
        p_all = jnp.pad(positions, (front, 0), constant_values=-2 ** 30)

    def rows(args):
        qrows, pos, first = args                 # [n, H, Dk], [n], row index
        if band:
            take = lambda a: jax.lax.dynamic_slice_in_dim(a, first,
                                                          front + qb)
            kb, vb, kpos = take(k_all), take(v_all), take(p_all)
        else:
            kb, vb, kpos = k, v, positions
        s = jnp.einsum("qngd,knd->ngqk", qrows.reshape(-1, Hkv, g, Dk), kb) \
            * Dk ** -0.5
        seen = kpos[None, :] <= pos[:, None]
        if window:
            seen = seen & (kpos[None, :] > pos[:, None] - d.window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        more = 0.0
        if sink is not None:
            m = jnp.maximum(m, sink[:, :, None, None])
            more = jnp.exp(sink[:, :, None, None] - m)
        e = jnp.exp(s - m)
        p = e / (jnp.sum(e, axis=-1, keepdims=True) + more)
        return jnp.einsum("ngqk,knd->qngd", p, vb).reshape(-1, H * Dv)

    blocks = lambda a: a.reshape((S // qb, qb) + a.shape[1:])
    o = jax.lax.map(rows, (blocks(q), blocks(positions),
                           jnp.arange(0, S, qb, dtype=jnp.int32)))
    return _linear(o.reshape(S, H * Dv), lw["wo"], quant)


def route(d: Dims, lw: dict, h):
    """``(idx [N, k], w [N, k], scores [N, experts])`` of rows ``h``: the
    choice by ``scores + bias``, the weights from the scores."""
    scores = jax.nn.sigmoid(jnp.matmul(h, lw["router"].T))
    pick = scores + lw["router_bias"][None, :] if d.select_bias else scores
    _, idx = jax.lax.top_k(pick, d.top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    w = top / jnp.sum(top, axis=-1, keepdims=True) if d.norm_topk else top
    return idx, w * d.routed_scale, scores


def expert_ffn(d: Dims, lw: dict, h, *, quant=None, cap=None):
    """Expert layer on rows ``h [N, E]`` (already normed): ``(y [N, E],
    overflow)``. ``lw`` holds experts ``d.held`` only. ``cap`` bounds the
    rows one expert is applied to; ``overflow`` counts experts that more
    rows chose (the result is then wrong and the caller repeats with
    ``cap=None``: every row through every held expert, masked)."""
    N = h.shape[0]
    idx, w, _ = route(d, lw, h)
    y = jnp.zeros_like(h)
    overflow = jnp.int32(0)
    for j, e in enumerate(range(*d.held)):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # [N]
        apply = lambda rows: _swiglu(
            rows, lw["experts_gate"][j], lw["experts_up"][j],
            lw["experts_down"][j], quant)
        if cap is None or cap >= N:
            y = y + w_e[:, None] * apply(h)
            continue
        chose = w_e > 0
        n = jnp.sum(chose, dtype=jnp.int32)
        overflow = overflow + (n > cap).astype(jnp.int32)
        rows = jnp.nonzero(chose, size=int(cap), fill_value=0)[0]
        live = jnp.arange(int(cap)) < n
        out = jnp.where(live[:, None], w_e[rows][:, None] * apply(h[rows]),
                        0.0)
        y = y.at[rows].add(out)
    return y, overflow


@partial(jax.jit, static_argnames=("d", "window", "routed", "quant", "cap",
                                   "q_block"))
def layer(d: Dims, lw: dict, x, *, window: bool, routed: bool, quant=None,
          cap=None, q_block=None):
    """One layer on ``x [B, S, E]`` (float32): ``(x, overflow)``."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        B, S, E = x.shape
        pos = jnp.arange(S, dtype=jnp.int32)
        att = lambda row: attention(
            d, lw, _rms_norm(row, lw["attn_norm"], d.eps), pos,
            window=window, quant=quant, q_block=q_block)
        x = x + jax.lax.map(att, x)
        h = _rms_norm(x, lw["ffn_norm"], d.eps).reshape(B * S, E)
        if routed:
            y, overflow = expert_ffn(d, lw, h, quant=quant, cap=cap)
        else:
            y = _swiglu(h, lw["gate"], lw["up"], lw["down"], quant)
            overflow = jnp.int32(0)
        return x + y.reshape(B, S, E), overflow


@partial(jax.jit, static_argnames=("eps", "quant"))
def _margins(hidden, norm_g, head_w, served, *, eps, quant=None):
    """``hidden [B, n, E]`` -> per served token: the gap of its logit
    under the row's best, the row's logit spread, the row's argmax."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        logits = _linear(_rms_norm(hidden, f32(norm_g), eps), f32(head_w),
                         quant)
        top = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
        return {"gap": top - got, "std": jnp.std(logits, axis=-1),
                "argmax": jnp.argmax(logits, axis=-1), "logits_top": top}


def hidden_states(make, model: dict, ids, *, rows_per_call: int, quant=None,
                  q_block=None, cap_share=None, depart=None):
    """Hidden states before the final norm, ``[B, S, E]`` float32 as a list
    of ``rows_per_call``-sequence blocks, LAYER BY LAYER: ``make.embed()``,
    ``make.layer(i)`` (one layer's leaves, dropped before the next is
    made), every block through that layer, then the next layer.
    ``cap_share`` bounds the rows an expert is applied to, as a share of a
    block's rows (``None``: every row through every held expert)."""
    d = Dims.of(model, **(depart or {}))
    ids = np.asarray(ids)
    B, S = ids.shape
    r = int(rows_per_call)
    if B % r:
        raise ValueError(f"{B} sequences are not a multiple of "
                         f"rows_per_call {r}")
    table = make.embed().astype(jnp.float32)
    blocks = [table[jnp.asarray(ids[b:b + r])] for b in range(0, B, r)]
    del table
    cap = None if cap_share is None else max(1, int(r * S * cap_share))
    for i in range(d.layers):
        lw = make.layer(i)
        kw = dict(window=bool(d.pattern[i]), routed=i >= d.first_dense,
                  quant=quant, q_block=q_block)
        for j, x in enumerate(blocks):
            out, overflow = layer(d, lw, x, cap=cap, **kw)
            if cap is not None and int(overflow):
                out, _ = layer(d, lw, x, cap=None, **kw)
            blocks[j] = out
        del lw
    return blocks


def served_margins(make, model: dict, ids, positions, served, *,
                   rows_per_call: int, quant=None, q_block=None,
                   cap_share=None) -> dict:
    """Teacher-forced margins of served text, as
    ``reference_gpt2.served_margins`` gives them: ``ids [B, S]`` holds
    prompt + served tokens right-padded; ``positions [B, n]`` the
    positions whose logits PREDICT each served token and ``served [B, n]``
    those tokens. Returns numpy ``gap``, ``std``, ``argmax`` ``[B, n]``
    and, with ``quant``, ``control_gap``: the reference's gap for the token
    the LOWER precision puts first."""
    positions = np.asarray(positions)
    served = np.asarray(served)
    r = int(rows_per_call)
    d = Dims.of(model)
    norm_g, head_w = make.final_norm(), make.head()

    def read(blocks, tokens, q=None):
        outs = []
        for j, x in enumerate(blocks):
            rows = jnp.arange(r)[:, None]
            hid = x[rows, jnp.asarray(positions[j * r:(j + 1) * r])]
            outs.append(_margins(hid, norm_g, head_w,
                                 jnp.asarray(tokens[j * r:(j + 1) * r]),
                                 eps=d.eps, quant=q))
        return {k: np.concatenate([np.asarray(o[k]) for o in outs])
                for k in outs[0]}

    kw = dict(rows_per_call=r, q_block=q_block, cap_share=cap_share)
    plain = hidden_states(make, model, ids, **kw)
    out = read(plain, served)
    if quant is not None:
        first = read(hidden_states(make, model, ids, quant=quant, **kw),
                     served, quant)["argmax"]
        out["control_gap"] = read(plain, first)["gap"]
    return out


def logits(make, model: dict, ids, *, quant=None, q_block=None,
           depart=None) -> np.ndarray:
    """Float32 logits ``[B, S, V]`` of token ids ``[B, S]`` (tests)."""
    d = Dims.of(model)
    blocks = hidden_states(make, model, ids, rows_per_call=len(ids),
                           quant=quant, q_block=q_block, depart=depart)
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        return np.asarray(_linear(
            _rms_norm(blocks[0], f32(make.final_norm()), d.eps),
            f32(make.head()), quant))
