"""The plain reference of LFM2-24B-A2B (``model_type: lfm2_moe``, source
``https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json``) in
straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
chunks, no paging, no grouped product, no batching policy, nothing
imported from the program (not its model, not its ``ops``).

**The layers** (from the source's ``config``; ``x`` is ``[T, E]``,
positions absolute; ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``, ``eps =
norm_eps``). ``layer_types[i]`` names layer ``i``'s OPERATOR::

    u = rms(x; operator_norm)

    conv:  [B | C | z] = u W_in            (E -> 3 E, split in that order)
           g = B * z
           c_t = sum_{j=0..K-1} w_j * g_{t-K+1+j}      K = conv_L_cache 3,
               depthwise, causal (g before the sequence is 0), no bias
               (conv_bias false), no activation: as K shifted products
           op = (C * c) W_out

    full_attention:
           q = u W_q -> H heads of Dh = E / H;  k = u W_k, v = u W_v -> Hkv
           q, k: rms over the Dh lanes of every head with a learned gain
               (q_layernorm, k_layernorm), THEN rotary on all Dh lanes,
               half-split (lane i with lane i + Dh/2), rope_theta, no
               scaling
           query head j reads KV head j // (H / Hkv); row i sees j <= i
           op = softmax(q k^T / sqrt(Dh)) v  W_o

    h = x + op;   v = rms(h; ffn_norm)
    layer < num_dense_layers:  x = h + (silu(v W_1) * (v W_3)) W_2
    else: s = sigmoid(v W_r^T) over all num_experts, float32;
          T = the num_experts_per_tok largest of s + expert_bias
              (use_expert_bias);
          w_e = s_e / (sum_{T} s + 1e-6) (norm_topk_prob) *
              routed_scaling_factor;   x = h + sum_{e in T} w_e expert_e(v)

embedding; after the last layer ``rms(.; embedding_norm)``; logits ``= x
embed^T`` (the head is the embedding's array). No shared expert.

**Conventions read from the family's code, each stated** (``assumed`` in
the configuration file says the same): the split order ``B | C | z`` and
that ``B`` and ``z`` are multiplied BEFORE the convolution and ``C``
after it; tap ``j`` reads ``K - 1 - j`` positions back; the 1e-6 in the
renormalisation; q/k norm before rotary; the rotary pairing;
``embedding_norm`` as the FINAL norm; the tied head.

**Departures, each stated.**

* **The share.** With ``held = (lo, hi)`` the expert layer routes over
  all experts and adds only the held experts' part (the one configuration
  of the benchmark holds all 64, so nothing is left out there). The
  layers served are the first ``num_hidden_layers`` of the published
  ``layer_types``.
* Memory, not mathematics: queries go through attention in blocks of
  ``q_block`` rows; an expert is applied to the rows that chose it (at
  most ``cap``, and if one ever has more the caller repeats the layer
  with twice the cap); the head is applied to blocks of the vocabulary;
  ``served_margins`` runs LAYER BY LAYER: one layer's weights are made,
  every sampled row goes through it, then the next.

``quant="int8"`` computes every linear layer of the blocks and the head
(not the router, which a W8A8 deployment keeps in float32 too, and not
the depthwise taps) with weights rounded per output channel and
activations per row to symmetric 8-bit integers: the CONTROL that a
cell's limits must reject. The benchmark's own runs never set it.

``Dims.of(model, **depart)`` replaces single facts of the mathematics
(``select_bias=False``, ``router_eps=0.0``, ``qk_norm=False``,
``gate_first=False``: C z before the convolution, B after): the tests'
controls.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CONV = "conv"


@dataclass(frozen=True)
class Dims:
    hidden: int
    heads: tuple            # (H, Hkv, Dh)
    theta: float
    taps: int
    types: tuple            # the served layers' operators
    dense_layers: int
    experts: int
    top_k: int
    routed_scale: float
    norm_topk: bool
    eps: float
    layers: int
    held: tuple
    select_bias: bool = True
    router_eps: float = 1e-6
    qk_norm: bool = True
    gate_first: bool = True

    @classmethod
    def of(cls, model: dict, **depart) -> "Dims":
        held = model.get("experts_held", (0, int(model["num_experts"])))
        L = int(model["num_hidden_layers"])
        H = int(model["num_attention_heads"])
        d = cls(
            hidden=int(model["hidden_size"]),
            heads=(H, int(model["num_key_value_heads"]),
                   int(model["hidden_size"]) // H),
            theta=float(model["rope_theta"]),
            taps=int(model["conv_L_cache"]),
            types=tuple(model["layer_types"][:L]),
            dense_layers=int(model["num_dense_layers"]),
            experts=int(model["num_experts"]),
            top_k=int(model["num_experts_per_tok"]),
            routed_scale=float(model.get("routed_scaling_factor") or 1.0),
            norm_topk=bool(model["norm_topk_prob"]),
            eps=float(model["norm_eps"]), layers=L,
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


def _rope(x, positions, theta):
    """``x [T, heads, D]`` turned half-split (lane ``i`` with lane ``i +
    D / 2``) by ``positions * theta^(-2i / D)``."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(d: Dims, lw: dict, u, quant=None, state_after=None):
    """The gated short convolution of ONE sequence from nothing before
    it: ``u [T, E]`` (normed) -> ``[T, E]``, the convolution as K shifted
    products over the whole sequence. With ``state_after`` (a number of
    tokens n <= T) also what the sequence's first n tokens leave behind:
    ``g`` at positions ``n - K + 1 .. n - 1`` ``[K - 1, E]`` (tests)."""
    T, E, K = u.shape[0], d.hidden, d.taps
    bcz = _linear(u, lw["conv_in"], quant)
    B, C, z = bcz[:, :E], bcz[:, E:2 * E], bcz[:, 2 * E:]
    before, after = (B, C) if d.gate_first else (C, B)
    g = before * z
    c = jnp.zeros_like(g)
    for j in range(K):
        back = K - 1 - j
        c = c + lw["conv_w"][j][None, :] * jnp.pad(
            g, ((back, 0), (0, 0)))[:T]
    out = _linear(after * c, lw["conv_out"], quant)
    if state_after is not None:
        return out, jax.lax.dynamic_slice_in_dim(
            jnp.pad(g, ((K - 1, 0), (0, 0))), state_after, K - 1, 0)
    return out


def attention(d: Dims, lw: dict, u, positions, quant=None, q_block=None):
    """Attention of ONE sequence: ``u [T, E]`` (normed) -> ``[T, E]``, a
    masked softmax over all T columns, ``q_block`` query rows at a time."""
    T = u.shape[0]
    H, Hkv, Dh = d.heads
    grp = H // Hkv
    q = _linear(u, lw["wq"], quant).reshape(T, H, Dh)
    k = _linear(u, lw["wk"], quant).reshape(T, Hkv, Dh)
    v = _linear(u, lw["wv"], quant).reshape(T, Hkv, Dh)
    if d.qk_norm:
        q = _rms_norm(q, lw["q_norm"], d.eps)
        k = _rms_norm(k, lw["k_norm"], d.eps)
    q, k = _rope(q, positions, d.theta), _rope(k, positions, d.theta)
    qb = T if not q_block else int(q_block)
    if T % qb:
        raise ValueError(f"sequence {T} is not a multiple of q_block {qb}")

    def rows(args):
        qrows, pos = args
        s = jnp.einsum("qngd,knd->ngqk", qrows.reshape(-1, Hkv, grp, Dh), k) \
            * Dh ** -0.5
        seen = positions[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("ngqk,knd->qngd", p, v).reshape(-1, H * Dh)

    blocks = lambda a: a.reshape((T // qb, qb) + a.shape[1:])
    o = jax.lax.map(rows, (blocks(q), blocks(positions)))
    return _linear(o.reshape(T, H * Dh), lw["wo"], quant)


def route(d: Dims, lw: dict, v):
    """``(idx [N, k], w [N, k], scores [N, experts])`` of rows ``v``: the
    choice by ``scores + expert_bias``, the weights from the scores."""
    scores = jax.nn.sigmoid(jnp.matmul(v, lw["router"].T))
    pick = scores + lw["expert_bias"][None, :] if d.select_bias else scores
    _, idx = jax.lax.top_k(pick, d.top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if d.norm_topk:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + d.router_eps)
    return idx, top * d.routed_scale, scores


def expert_ffn(d: Dims, lw: dict, v, *, quant=None, cap=None):
    """Expert layer on rows ``v [N, E]`` (already normed): ``(y [N, E],
    overflow)``. ``lw`` holds experts ``d.held`` only, in the weights'
    dtype: one expert at a time is made float32. ``cap`` bounds the rows
    one expert is applied to; ``overflow`` counts the experts that more
    rows chose (the result is then wrong and the caller repeats with a
    larger cap; ``None``: every row through every held expert, masked)."""
    N = v.shape[0]
    idx, w, _ = route(d, lw, v)
    f32 = lambda a: a.astype(jnp.float32)
    n_cap = N if cap is None else min(int(cap), N)

    def one(carry, j):
        y, overflow = carry
        w_e = jnp.sum(jnp.where(idx == j + d.held[0], w, 0.0), axis=-1)
        gate, up, down = (f32(lw[name][j]) for name in (
            "experts_gate", "experts_up", "experts_down"))
        if n_cap == N:
            return (y + w_e[:, None] * _swiglu(v, gate, up, down, quant),
                    overflow), None
        chose = w_e > 0
        n = jnp.sum(chose, dtype=jnp.int32)
        # the rows that chose it, first (a stable sort, not jnp.nonzero:
        # its cumsum over 36,864 rows ran XLA's TPU compiler out of scoped
        # VMEM, my chip run, PR 42)
        rows = jnp.argsort(~chose, stable=True)[:n_cap]
        live = jnp.arange(n_cap) < n
        out = jnp.where(live[:, None], w_e[rows][:, None]
                        * _swiglu(v[rows], gate, up, down, quant), 0.0)
        return (y.at[rows].add(out),
                overflow + (n > n_cap).astype(jnp.int32)), None

    (y, overflow), _ = jax.lax.scan(
        one, (jnp.zeros_like(v), jnp.int32(0)),
        jnp.arange(d.held[1] - d.held[0], dtype=jnp.int32))
    return y, overflow


_MATRICES = ("conv_in", "conv_w", "conv_out", "wq", "wk", "wv", "wo",
             "q_norm", "k_norm", "operator_norm", "ffn_norm", "router",
             "expert_bias", "gate", "up", "down")


@partial(jax.jit, static_argnames=("d", "conv", "routed", "quant", "cap",
                                   "q_block"))
def layer(d: Dims, lw: dict, x, *, conv: bool, routed: bool, quant=None,
          cap=None, q_block=None):
    """One layer on ``x [B, T, E]`` (float32): ``(x, overflow)``."""
    with jax.default_matmul_precision("highest"):
        # the expert stacks stay in the weights' dtype (expert_ffn)
        lw = {k: a.astype(jnp.float32) if k in _MATRICES else a
              for k, a in lw.items()}
        B, T, E = x.shape
        pos = jnp.arange(T, dtype=jnp.int32)

        def operator(row):
            u = _rms_norm(row, lw["operator_norm"], d.eps)
            if conv:
                return short_conv(d, lw, u, quant)
            return attention(d, lw, u, pos, quant, q_block)

        x = x + jax.lax.map(operator, x)
        v = _rms_norm(x, lw["ffn_norm"], d.eps).reshape(B * T, E)
        if routed:
            y, overflow = expert_ffn(d, lw, v, quant=quant, cap=cap)
        else:
            y = _swiglu(v, lw["gate"], lw["up"], lw["down"], quant)
            overflow = jnp.int32(0)
        return x + y.reshape(B, T, E), overflow


@partial(jax.jit, static_argnames=("eps", "quant", "blocks"))
def _margins(hidden, norm_g, table, served, *, eps, quant=None, blocks=1):
    """``hidden [M, E]`` -> per row: the gap of the served token's logit
    under the row's best, the row's logit spread, its argmax — the tied
    head (``table [V, E]``, the embedding) applied to ``blocks`` row
    blocks of the vocabulary in turn."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        M, V = hidden.shape[0], table.shape[0]
        h = _rms_norm(hidden, f32(norm_g), eps)
        width = V // blocks

        def one(carry, i):
            top, arg, s1, s2, got = carry
            w = f32(jax.lax.dynamic_slice_in_dim(table, i * width, width, 0))
            lg = _linear(h, w.T, quant)                       # [M, width]
            best = jnp.max(lg, axis=-1)
            where = jnp.argmax(lg, axis=-1).astype(jnp.int32) + i * width
            arg = jnp.where(best > top, where, arg)
            local = served - i * width
            mine = (local >= 0) & (local < width)
            got = jnp.where(mine, jnp.take_along_axis(
                lg, jnp.clip(local, 0, width - 1)[:, None], axis=-1)[:, 0],
                got)
            return (jnp.maximum(top, best), arg, s1 + jnp.sum(lg, axis=-1),
                    s2 + jnp.sum(lg * lg, axis=-1), got), None

        z = jnp.zeros((M,), jnp.float32)
        (top, arg, s1, s2, got), _ = jax.lax.scan(
            one, (jnp.full((M,), -jnp.inf, jnp.float32),
                  jnp.zeros((M,), jnp.int32), z, z, z),
            jnp.arange(blocks, dtype=jnp.int32))
        mean = s1 / V
        return {"gap": top - got,
                "std": jnp.sqrt(jnp.maximum(s2 / V - mean * mean, 0.0)),
                "argmax": arg, "logits_top": top}


def head_blocks(vocab: int) -> int:
    """Row blocks of the vocabulary the head is applied in: of at most
    ~8 k rows."""
    for n in (8, 4, 2):
        if vocab % n == 0 and vocab // n >= 1024:
            return n
    return 1


def _through(d: Dims, lw: dict, x, i: int, *, quant, q_block, cap):
    """Layer ``i`` on one block, the expert cap doubled until it holds."""
    kw = dict(conv=d.types[i] == CONV, routed=i >= d.dense_layers,
              quant=quant, q_block=q_block)
    rows = x.shape[0] * x.shape[1]
    while True:
        out, overflow = layer(d, lw, x, cap=cap, **kw)
        if cap is None or cap >= rows or not int(overflow):
            return out
        cap = min(2 * cap, rows)


def hidden_states(make, model: dict, ids, *, rows_per_call: int, quant=None,
                  q_block=None, cap_share=None, depart=None):
    """Hidden states before the final norm, ``[B, T, E]`` float32 as a list
    of ``rows_per_call``-sequence blocks, LAYER BY LAYER: ``make.embed()``,
    ``make.layer(i)`` (one layer's leaves, dropped before the next is
    made), every block through that layer, then the next layer.
    ``cap_share`` bounds the rows an expert is applied to at first, as a
    share of a block's rows (``None``: every row through every held
    expert)."""
    d = Dims.of(model, **(depart or {}))
    ids = np.asarray(ids)
    B, T = ids.shape
    r = int(rows_per_call)
    if B % r:
        raise ValueError(f"{B} sequences are not a multiple of "
                         f"rows_per_call {r}")
    table = make.embed()
    blocks = [table[jnp.asarray(ids[b:b + r])].astype(jnp.float32)
              for b in range(0, B, r)]
    del table
    cap = None if cap_share is None else max(1, int(r * T * cap_share))
    for i in range(d.layers):
        lw = make.layer(i)
        for j, x in enumerate(blocks):
            blocks[j] = _through(d, lw, x, i, quant=quant, q_block=q_block,
                                 cap=cap)
        del lw
    return blocks


@partial(jax.jit, static_argnames=("d",))
def _conv_layer_with_state(d: Dims, lw: dict, x, n):
    """What the first ``n`` rows of ``x [T, E]`` leave in a ``conv`` layer."""
    with jax.default_matmul_precision("highest"):
        lw = {k: a.astype(jnp.float32) if k in _MATRICES else a
              for k, a in lw.items()}
        return short_conv(d, lw, _rms_norm(x, lw["operator_norm"], d.eps),
                          state_after=n)[1]


def final_states(make, model: dict, ids, n: int) -> list:
    """What the first ``n`` tokens of ONE sequence ``ids [T]`` leave behind
    in every ``conv`` layer, in layer order: ``[g at the last K - 1
    positions before n  [K - 1, E]]`` float32 (tests: what the program's
    pool must hold for the slot once those tokens are in)."""
    d = Dims.of(model)
    x = make.embed()[jnp.asarray(ids)].astype(jnp.float32)[None]
    out = []
    for i in range(d.layers):
        lw = make.layer(i)
        if d.types[i] == CONV:
            out.append(np.asarray(_conv_layer_with_state(
                d, lw, x[0], jnp.int32(n))))
        x = _through(d, lw, x, i, quant=None, q_block=None, cap=None)
    return out


def served_margins(make, model: dict, ids, positions, served, *,
                   rows_per_call: int, quant=None, q_block=None,
                   cap_share=None) -> dict:
    """Teacher-forced margins of served text, as
    ``reference_mimo.served_margins`` gives them: ``ids [B, T]`` holds
    prompt + served tokens right-padded; ``positions [B, n]`` the
    positions whose logits PREDICT each served token and ``served [B, n]``
    those tokens. Returns numpy ``gap``, ``std``, ``argmax`` ``[B, n]``
    and, with ``quant``, ``control_gap``: the reference's gap for the token
    the LOWER precision puts first."""
    positions = np.asarray(positions)
    served = np.asarray(served)
    r = int(rows_per_call)
    d = Dims.of(model)
    norm_g, table = make.final_norm(), make.embed()
    nb = head_blocks(int(table.shape[0]))

    def read(blocks, tokens, q=None):
        outs = []
        for j, x in enumerate(blocks):
            for b in range(r):                 # a sequence's rows a call
                row = j * r + b
                outs.append(_margins(
                    x[b][jnp.asarray(positions[row])], norm_g, table,
                    jnp.asarray(tokens[row]), eps=d.eps, quant=q,
                    blocks=nb))
        return {k: np.stack([np.asarray(o[k]) for o in outs])
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
    """Float32 logits ``[B, T, V]`` of token ids ``[B, T]`` (tests, at
    sizes where the whole head fits)."""
    d = Dims.of(model)
    blocks = hidden_states(make, model, ids, rows_per_call=len(ids),
                           quant=quant, q_block=q_block, depart=depart)
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        return np.asarray(_linear(
            _rms_norm(blocks[0], f32(make.final_norm()), d.eps),
            f32(make.embed()).T, quant))
