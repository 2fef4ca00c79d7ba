"""The plain reference of A.X-K1 (``model_type: axk1``, source
``https://huggingface.co/skt/A.X-K1/blob/main/config.json``) in
straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
paging, no batching policy, nothing imported from the program.

**The layers** (from the source's ``config``). Hidden ``E``; RMSNorm
(eps ``rms_norm_eps``) before attention and before the FFN; residual
adds; no biases; untied embedding and head; final RMSNorm.

*MLA attention* (``H`` heads; ranks ``q_lora_rank``, ``kv_lora_rank``;
``nope = qk_nope_head_dim``, ``rope = qk_rope_head_dim``, ``v =
v_head_dim``)::

    c_q = RMSNorm(x W_qa)                      q = c_q W_qb -> per head [q_nope | q_pe]
    [c_kv | k_pe] = x W_kva                    c_kv = RMSNorm(c_kv)
    q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)        (one k_pe for all heads)
    [k_nope | v] = c_kv W_kvb  per head
    s = (q_nope . k_nope + q_pe . k_pe) * scale, causal softmax, o = sum p v
    out = concat(o) W_o

``scale = (nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim *
ln(factor) + 1`` (1.3466 for ``factor`` 32). RoPE: ``rope_theta`` on
``rope`` dims with YaRN (``factor``, ``original_max_position_embeddings``,
``beta_fast``, ``beta_slow``): per frequency a linear ramp between the
interpolated (``f / factor``) and the unscaled frequency over the
correction range of ``beta_fast`` / ``beta_slow``; cos and sin multiplied
by ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``
(1 here).

*Absorbed form* (``form="absorbed"``; what a serving step runs against a
cache of ``[c_kv | k_pe]`` rows): ``q_lat = q_nope W_UK^T``, ``s = q_lat .
c_kv + q_pe . k_pe``, ``o_lat = sum p c_kv``, ``o = o_lat W_UV``, with
``W_UK``, ``W_UV`` the two halves of ``W_kvb``. The same function of the
same weights; a test holds the two forms equal.

*Dense layer* (the first ``first_k_dense_replace``): SwiGLU,
``down(silu(gate(x)) * up(x))``, width ``intermediate_size``.

*Expert layer*: ``g = sigmoid(x W_g^T)`` over all ``n_routed_experts``;
``T = top-k(g)``, ``k = num_experts_per_tok``; ``w_e =
routed_scaling_factor * g_e / sum_{j in T} g_j``; ``y = shared(x) +
sum_{e in T} w_e expert_e(x)``, each expert and the shared one a SwiGLU of
width ``moe_intermediate_size``.

**Departures, each stated.**

* ``topk_method`` is ``"none"`` in the source: read as plain top-k over
  the scores, no group limit (``n_group``/``topk_group`` unused) and no
  score-correction bias.
* RoPE rotates the pairs ``(2i, 2i + 1)`` and leaves them in place. The
  source's family de-interleaves first and writes the halves side by
  side: the same rotation under one fixed permutation of ``q_pe`` and
  ``k_pe`` alike, which no dot product sees.
* **The share.** With ``held = (lo, hi)`` the expert layer routes over
  all experts and adds only ``sum_{e in T, lo <= e < hi} w_e expert_e(x)``
  (``w_e`` normalised over all ``k`` chosen) to ``shared(x)``; what the
  absent experts would add is left out and that partial sum goes on to
  the next layer. ``lw`` then holds the held experts only. The
  vocabulary slice is simply a smaller ``vocab_size``.
* Memory, not mathematics: queries go through attention in blocks of
  ``q_block`` rows, and an expert is applied to the rows that chose it
  (at most ``cap``, and if one ever has more the caller repeats the layer
  with every row) — the float32 weights of the cell's size are 19.4 GB,
  so ``served_margins`` runs LAYER BY LAYER: one layer's weights are
  made, every sampled row goes through that layer in blocks of
  ``rows_per_call`` sequences, then the next.

``quant="int8"`` computes every linear layer of the blocks and the head
(not the router, which a W8A8 deployment keeps in float32 too) with
weights rounded per output channel and activations per row to symmetric
8-bit integers: the CONTROL that a cell's limits must reject. The
benchmark's own runs never set it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Dims:
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    experts: int
    top_k: int
    routed_scale: float
    norm_topk: bool
    eps: float
    theta: float
    yarn: tuple            # sorted items of rope_scaling
    first_dense: int
    layers: int
    held: tuple

    @classmethod
    def of(cls, model: dict) -> "Dims":
        held = model.get("experts_held", (0, int(model["n_routed_experts"])))
        return cls(
            hidden=int(model["hidden_size"]),
            heads=int(model["num_attention_heads"]),
            q_rank=int(model["q_lora_rank"]),
            kv_rank=int(model["kv_lora_rank"]),
            nope=int(model["qk_nope_head_dim"]),
            rope=int(model["qk_rope_head_dim"]), v=int(model["v_head_dim"]),
            experts=int(model["n_routed_experts"]),
            top_k=int(model["num_experts_per_tok"]),
            routed_scale=float(model["routed_scaling_factor"]),
            norm_topk=bool(model["norm_topk_prob"]),
            eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
            yarn=tuple(sorted(model["rope_scaling"].items())),
            first_dense=int(model["first_k_dense_replace"]),
            layers=int(model["num_hidden_layers"]),
            held=(int(held[0]), int(held[1])))


# -- YaRN ---------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: Dims) -> np.ndarray:
    y = dict(d.yarn)
    dim, base = d.rope, d.theta
    factor = float(y["factor"])
    orig = float(y["original_max_position_embeddings"])
    i = np.arange(dim // 2, dtype=np.float64)
    unscaled = base ** (-2.0 * i / dim)
    interpolated = unscaled / factor

    def dim_of(rotations):      # the dim whose wavelength turns that often
        return dim * math.log(orig / (rotations * 2.0 * math.pi)) \
            / (2.0 * math.log(base))

    low = max(math.floor(dim_of(float(y["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(y["beta_slow"]))), dim - 1)
    span = (high - low) if high != low else 0.001
    ramp = np.clip((i - low) / span, 0.0, 1.0)   # 0: unscaled, 1: interpolated
    return (interpolated * ramp + unscaled * (1.0 - ramp)).astype(np.float32)


def attention_scale(d: Dims) -> float:
    y = dict(d.yarn)
    m = _yarn_mscale(float(y["factor"]), float(y["mscale_all_dim"]))
    return (d.nope + d.rope) ** -0.5 * m * m


def _cos_sin(d: Dims, positions):
    y = dict(d.yarn)
    mult = _yarn_mscale(float(y["factor"]), float(y["mscale"])) \
        / _yarn_mscale(float(y["factor"]), float(y["mscale_all_dim"]))
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(d))[None, :]
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def _rope(x, cos, sin):
    """Pairs ``(2i, 2i + 1)`` of the last axis turned by the angle of the
    row: ``cos``/``sin`` broadcast against ``x[..., 0::2]``."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


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


def attention(d: Dims, lw: dict, h, positions, *, form="naive", quant=None,
              q_block=None):
    """Causal MLA of ONE sequence: ``h [S, E]`` (already normed),
    ``positions [S]`` -> ``[S, E]``. ``form`` is ``"naive"`` (per-head K
    and V decompressed from ``c_kv``) or ``"absorbed"`` (scores and values
    against ``[c_kv | k_pe]`` itself)."""
    S, H = h.shape[0], d.heads
    cos, sin = _cos_sin(d, positions)
    c_q = _rms_norm(_linear(h, lw["wq_a"], quant), lw["q_norm"], d.eps)
    q = _linear(c_q, lw["wq_b"], quant).reshape(S, H, d.nope + d.rope)
    q_nope = q[..., :d.nope]
    q_pe = _rope(q[..., d.nope:], cos[:, None], sin[:, None])
    kv = _linear(h, lw["wkv_a"], quant)
    c_kv = _rms_norm(kv[:, :d.kv_rank], lw["kv_norm"], d.eps)
    k_pe = _rope(kv[:, d.kv_rank:], cos, sin)
    scale = attention_scale(d)
    if form == "naive":
        kvb = _linear(c_kv, lw["wkv_b"], quant).reshape(S, H, d.nope + d.v)
        k_nope, val = kvb[..., :d.nope], kvb[..., d.nope:]
        q_a, k_a = q_nope, k_nope                      # [S, H, nope]
        score = lambda qa, qp: (jnp.einsum("qhd,khd->hqk", qa, k_a)
                                + jnp.einsum("qhd,kd->hqk", qp, k_pe))
        gather = lambda p: jnp.einsum("hqk,khd->qhd", p, val)
        finish = lambda o: o
    elif form == "absorbed":
        w = lw["wkv_b"].reshape(d.kv_rank, H, d.nope + d.v)
        w_uk, w_uv = w[..., :d.nope], w[..., d.nope:]
        q_a = jnp.einsum("qhd,chd->qhc", q_nope, w_uk)  # [S, H, rank]
        score = lambda qa, qp: (jnp.einsum("qhc,kc->hqk", qa, c_kv)
                                + jnp.einsum("qhd,kd->hqk", qp, k_pe))
        gather = lambda p: jnp.einsum("hqk,kc->qhc", p, c_kv)
        finish = lambda o: jnp.einsum("qhc,chd->qhd", o, w_uv)
    else:
        raise ValueError(f"unknown form {form!r}")

    def rows(args):
        qa, qp, pos = args
        s = score(qa, qp) * scale
        s = jnp.where(positions[None, None, :] <= pos[None, :, None], s,
                      -jnp.inf)
        return finish(gather(jax.nn.softmax(s, axis=-1)))

    qb = S if not q_block else int(q_block)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")
    blocks = lambda a: a.reshape((S // qb, qb) + a.shape[1:])
    o = jax.lax.map(rows, (blocks(q_a), blocks(q_pe), blocks(positions)))
    return _linear(o.reshape(S, H * d.v), lw["wo"], quant)


def route(d: Dims, router_w, h):
    """``(idx [N, k], w [N, k], scores [N, experts])`` of rows ``h``."""
    scores = jax.nn.sigmoid(jnp.matmul(h, router_w.T))
    top, idx = jax.lax.top_k(scores, d.top_k)
    w = top / jnp.sum(top, axis=-1, keepdims=True) if d.norm_topk else top
    return idx, w * d.routed_scale, scores


def expert_ffn(d: Dims, lw: dict, h, *, quant=None, cap=None):
    """Expert layer on rows ``h [N, E]`` (already normed): ``(y [N, E],
    overflow)``. ``lw`` holds experts ``d.held`` only. ``cap`` bounds the
    rows one expert is applied to; ``overflow`` counts experts that more
    rows chose (the result is then wrong and the caller repeats with
    ``cap=None``: every row through every held expert, masked)."""
    N = h.shape[0]
    idx, w, _ = route(d, lw["router"], h)
    y = _swiglu(h, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
                quant)
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


@partial(jax.jit, static_argnames=("d", "routed", "form", "quant", "cap",
                                   "q_block"))
def layer(d: Dims, lw: dict, x, *, routed: bool, form="naive", quant=None,
          cap=None, q_block=None):
    """One layer on ``x [B, S, E]`` (float32): ``(x, overflow)``."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        B, S, E = x.shape
        pos = jnp.arange(S, dtype=jnp.int32)
        att = lambda row: attention(
            d, lw, _rms_norm(row, lw["attn_norm"], d.eps), pos, form=form,
            quant=quant, q_block=q_block)
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
                  form="naive", q_block=None, cap_share=None):
    """Hidden states before the final norm, ``[B, S, E]`` float32 as a list
    of ``rows_per_call``-sequence blocks, LAYER BY LAYER: ``make.embed()``,
    ``make.layer(i)`` (one layer's leaves, dropped before the next is
    made), every block through that layer, then the next layer.
    ``cap_share`` bounds the rows an expert is applied to, as a share of a
    block's rows (``None``: every row through every held expert)."""
    d = Dims.of(model)
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
        kw = dict(routed=i >= d.first_dense, form=form, quant=quant,
                  q_block=q_block)
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


def logits(make, model: dict, ids, *, form="naive", quant=None,
           q_block=None) -> np.ndarray:
    """Float32 logits ``[B, S, V]`` of token ids ``[B, S]`` (tests)."""
    d = Dims.of(model)
    blocks = hidden_states(make, model, ids, rows_per_call=len(ids),
                           quant=quant, form=form, q_block=q_block)
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        return np.asarray(_linear(
            _rms_norm(blocks[0], f32(make.final_norm()), d.eps),
            f32(make.head()), quant))
