"""The plain reference of LongCat-Flash (source
``https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json``)
in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
paging, no batching policy, nothing imported from the program.

**The layer** (from the source's ``config`` and its description:
"shortcut-connected MoE", "zero-computation experts"). Hidden ``E``;
pre-norm RMSNorm (eps ``rms_norm_eps``); no biases; untied embedding and
head; final RMSNorm. Published layer ``l``, sub-block ``s``::

    for s in (0, 1):
        x = x + MLA[l,s]( RMSNorm_in[l,s](x) )
        u = RMSNorm_post[l,s](x)
        if s == 0:  m = MoE[l](u)          # the shortcut opens: m is carried
        x = x + Dense[l,s](u)              # SwiGLU: down(silu(gate u) * up u)
        if s == 1:  x = x + m              # the shortcut closes
    logits = RMSNorm(x) W_head

*MLA* (``h`` the normed input; ``H`` heads; ranks ``q_lora_rank``,
``kv_lora_rank``; ``nope``, ``rope``, ``v`` the head's three sizes)::

    c_q = RMSNorm(h W_qa)             q = (c_q W_qb) * q_scale -> a head [q_nope | q_pe]
    [c | k_pe] = h W_kva              c_kv = RMSNorm(c) * kv_scale
    q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)         (one k_pe for all heads)
    [k_nope | v] = c_kv W_kvb  a head
    s = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope), causal softmax, o = sum p v
    out = concat(o) W_o

``q_scale = sqrt(E / q_lora_rank)`` where ``mla_scale_q_lora``,
``kv_scale = sqrt(E / kv_lora_rank)`` where ``mla_scale_kv_lora`` (2 and
sqrt(12) at the published sizes); ``k_pe`` takes neither. RoPE:
``rope_theta`` on the ``rope`` dims, no scaling. The multipliers are
applied where these lines apply them: the reference neither stores a
scaled row nor folds a multiplier into a matrix.

*MoE* (``u [N, E]``)::

    g = softmax(u W_r^T)  over n_routed_experts = real + zero_expert_num outputs
    T = the moe_topk largest of g + b           (b: the score-correction bias)
    w_e = routed_scaling_factor * g_e           (NOT normalised over T)
    m = sum_{e in T, e < real} w_e SwiGLU_e(u)  +  (sum_{e in T, e >= real} w_e) u

an expert a SwiGLU of width ``moe_intermediate_size``; an identity expert
is the row itself.

**Departures and assumptions, each stated.**

* ``model["n_routed_experts"]`` is the ROUTER's width (768); the source's
  key of that name counts the 512 experts with weights, and
  ``zero_expert_num`` the 256 identity outputs after them.
* The values of the two multipliers are the public modeling code's; the
  config carries two booleans.
* ``norm_topk_prob`` false, a bias-free router and ``hidden_act`` silu:
  keys the source's config does not carry, read as its family's defaults.
* RoPE rotates the pairs ``(2i, 2i + 1)`` and leaves them in place, as
  ``reference_axk1`` does (its family de-interleaves first: one fixed
  permutation of ``q_pe`` and ``k_pe`` alike, which no dot product sees).
* No multi-token-prediction head.
* **The share.** With ``held = (lo, hi)`` the first sum of ``m`` runs over
  ``lo <= e < hi`` only; the identity term is whole (a token's identity
  experts need no dispatch). What the absent experts would add is left
  out and that partial ``m`` goes on. ``lw`` then holds the held experts
  only. The vocabulary slice is simply a smaller ``vocab_size``.
* Memory, not mathematics: queries go through attention in blocks of
  ``q_block`` rows, and an expert is applied to the rows that chose it (at
  most ``cap``, and if one ever has more the caller repeats the sub-block
  with every row). A published layer in float32 is 5 GB, so
  ``served_margins`` runs SUB-BLOCK BY SUB-BLOCK: one sub-block's weights
  are made, every sampled row goes through it in blocks of
  ``rows_per_call`` sequences — ``x`` and the open shortcut's ``m`` are
  kept a block — then the next.

``quant="int8"`` computes every linear layer of the blocks and the head
(not the router, which a W8A8 deployment keeps in float32 too; the
identity term has no linear layer) with weights rounded per output
channel and activations per row to symmetric 8-bit integers: the CONTROL
that a cell's limits must reject. The benchmark's own runs never set it.
"""
from __future__ import annotations

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
    outputs: int           # the router's width: real + identity experts
    real: int              # experts with weights
    top_k: int
    routed_scale: float
    q_scale: float
    kv_scale: float
    eps: float
    theta: float
    sub_blocks: int
    held: tuple

    @classmethod
    def of(cls, model: dict) -> "Dims":
        outputs = int(model["n_routed_experts"])
        real = outputs - int(model["zero_expert_num"])
        held = model.get("experts_held", (0, real))
        E = int(model["hidden_size"])
        return cls(
            hidden=E, heads=int(model["num_attention_heads"]),
            q_rank=int(model["q_lora_rank"]),
            kv_rank=int(model["kv_lora_rank"]),
            nope=int(model["qk_nope_head_dim"]),
            rope=int(model["qk_rope_head_dim"]), v=int(model["v_head_dim"]),
            outputs=outputs, real=real, top_k=int(model["moe_topk"]),
            routed_scale=float(model["routed_scaling_factor"]),
            q_scale=(E / int(model["q_lora_rank"])) ** 0.5
            if model["mla_scale_q_lora"] else 1.0,
            kv_scale=(E / int(model["kv_lora_rank"])) ** 0.5
            if model["mla_scale_kv_lora"] else 1.0,
            eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
            sub_blocks=2 * int(model["num_hidden_layers"]),
            held=(int(held[0]), int(held[1])))


def _cos_sin(d: Dims, positions):
    inv = d.theta ** (-2.0 * np.arange(d.rope // 2, dtype=np.float64)
                      / d.rope)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None, :]
    return jnp.cos(ang), jnp.sin(ang)


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


def attention(d: Dims, lw: dict, h, positions, *, quant=None, q_block=None):
    """Causal MLA of ONE sequence: ``h [S, E]`` (already normed),
    ``positions [S]`` -> ``[S, E]``, every head's K and V decompressed
    from ``c_kv``."""
    S, H = h.shape[0], d.heads
    cos, sin = _cos_sin(d, positions)
    c_q = _rms_norm(_linear(h, lw["wq_a"], quant), lw["q_norm"], d.eps)
    q = (_linear(c_q, lw["wq_b"], quant) * d.q_scale).reshape(
        S, H, d.nope + d.rope)
    q_nope = q[..., :d.nope]
    q_pe = _rope(q[..., d.nope:], cos[:, None], sin[:, None])
    kv = _linear(h, lw["wkv_a"], quant)
    c_kv = _rms_norm(kv[:, :d.kv_rank], lw["kv_norm"], d.eps) * d.kv_scale
    k_pe = _rope(kv[:, d.kv_rank:], cos, sin)
    kvb = _linear(c_kv, lw["wkv_b"], quant).reshape(S, H, d.nope + d.v)
    k_nope, val = kvb[..., :d.nope], kvb[..., d.nope:]
    scale = (d.nope + d.rope) ** -0.5

    def rows(args):
        qa, qp, pos = args
        s = (jnp.einsum("qhd,khd->hqk", qa, k_nope)
             + jnp.einsum("qhd,kd->hqk", qp, k_pe)) * scale
        s = jnp.where(positions[None, None, :] <= pos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), val)

    qb = S if not q_block else int(q_block)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")
    blocks = lambda a: a.reshape((S // qb, qb) + a.shape[1:])
    o = jax.lax.map(rows, (blocks(q_nope), blocks(q_pe), blocks(positions)))
    return _linear(o.reshape(S, H * d.v), lw["wo"], quant)


def route(d: Dims, router_w, bias, u, select_bias=True):
    """``(idx [N, k], w [N, k], g [N, outputs])`` of rows ``u``: the choice
    by ``g + b`` (``select_bias`` False: by ``g``, a test's control), the
    weights ``routed_scale * g`` of the chosen, not normalised."""
    g = jax.nn.softmax(jnp.matmul(u, router_w.T), axis=-1)
    _, idx = jax.lax.top_k(g + bias[None, :] if select_bias else g, d.top_k)
    return idx, jnp.take_along_axis(g, idx, axis=-1) * d.routed_scale, g


def moe(d: Dims, lw: dict, u, *, quant=None, cap=None):
    """The shortcut's value on rows ``u [N, E]`` (already normed): ``(m [N,
    E], overflow)`` — the held experts' part of the routed sum plus the
    identity experts' whole term. ``lw`` holds experts ``d.held`` only.
    ``cap`` bounds the rows one expert is applied to; ``overflow`` counts
    experts that more rows chose (the result is then wrong and the caller
    repeats with ``cap=None``: every row through every held expert,
    masked)."""
    N = u.shape[0]
    idx, w, _ = route(d, lw["router"], lw["router_bias"], u)
    m = jnp.sum(jnp.where(idx >= d.real, w, 0.0), axis=-1)[:, None] * u
    overflow = jnp.int32(0)
    for j, e in enumerate(range(*d.held)):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # [N]
        apply = lambda rows: _swiglu(
            rows, lw["experts_gate"][j], lw["experts_up"][j],
            lw["experts_down"][j], quant)
        if cap is None or cap >= N:
            m = m + w_e[:, None] * apply(u)
            continue
        chose = w_e > 0
        n = jnp.sum(chose, dtype=jnp.int32)
        overflow = overflow + (n > cap).astype(jnp.int32)
        rows = jnp.nonzero(chose, size=int(cap), fill_value=0)[0]
        live = jnp.arange(int(cap)) < n
        out = jnp.where(live[:, None], w_e[rows][:, None] * apply(u[rows]),
                        0.0)
        m = m.at[rows].add(out)
    return m, overflow


@partial(jax.jit, static_argnames=("d", "opens", "quant", "cap", "q_block"))
def sub_block(d: Dims, lw: dict, x, m, *, opens: bool, quant=None, cap=None,
              q_block=None):
    """One sub-block on ``x [B, S, E]`` (float32): ``(x, m, overflow)``.
    ``opens``: the sub-block is a published layer's first — ``m`` in is
    ignored and the shortcut's value comes out; else its second — ``m`` in
    is added after the dense FFN and zeros come out."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        B, S, E = x.shape
        pos = jnp.arange(S, dtype=jnp.int32)
        att = lambda row: attention(
            d, lw, _rms_norm(row, lw["attn_norm"], d.eps), pos, quant=quant,
            q_block=q_block)
        x = x + jax.lax.map(att, x)
        u = _rms_norm(x, lw["ffn_norm"], d.eps).reshape(B * S, E)
        overflow = jnp.int32(0)
        if opens:
            m, overflow = moe(d, lw, u, quant=quant, cap=cap)
            m = m.reshape(B, S, E)
        x = x + _swiglu(u, lw["gate"], lw["up"], lw["down"],
                        quant).reshape(B, S, E)
        if not opens:
            x, m = x + m, jnp.zeros_like(m)
        return x, m, overflow


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
                  q_block=None, cap_share=None):
    """Hidden states before the final norm, ``[B, S, E]`` float32 as a list
    of ``rows_per_call``-sequence blocks, SUB-BLOCK BY SUB-BLOCK:
    ``make.embed()``, ``make.layer(i)`` (one sub-block's leaves, dropped
    before the next is made), every block through it, then the next.
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
    carried = [jnp.zeros_like(x) for x in blocks]
    cap = None if cap_share is None else max(1, int(r * S * cap_share))
    for i in range(d.sub_blocks):
        lw = make.layer(i)
        kw = dict(opens=i % 2 == 0, quant=quant, q_block=q_block)
        for j, (x, m) in enumerate(zip(blocks, carried)):
            out = sub_block(d, lw, x, m, cap=cap, **kw)
            if cap is not None and int(out[2]):
                out = sub_block(d, lw, x, m, cap=None, **kw)
            blocks[j], carried[j] = out[0], out[1]
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


def logits(make, model: dict, ids, *, quant=None, q_block=None) -> np.ndarray:
    """Float32 logits ``[B, S, V]`` of token ids ``[B, S]`` (tests)."""
    d = Dims.of(model)
    blocks = hidden_states(make, model, ids, rows_per_call=len(ids),
                           quant=quant, q_block=q_block)
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        return np.asarray(_linear(
            _rms_norm(blocks[0], f32(make.final_norm()), d.eps),
            f32(make.head()), quant))
