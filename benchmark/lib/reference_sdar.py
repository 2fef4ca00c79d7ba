"""The plain reference of SDAR-MoE (``model_type: sdar_moe``, source
``https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json``)
in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
paging, no batching policy, nothing imported from the program.

**The layers** (``x`` is ``[T, E]``, positions absolute, ``B`` = block
length). RMSNorm (eps ``rms_norm_eps``) before attention and before the
FFN; residual adds; no biases; untied embedding and head; final RMSNorm.

*Attention* (``H`` query heads on ``Hkv`` KV heads of ``Dh``)::

    q = h W_q -> [T, H, Dh]     k = h W_k, v = h W_v -> [T, Hkv, Dh]
    q = RMSNorm(q; q_norm), k = RMSNorm(k; k_norm)      over the Dh lanes
    q, k = RoPE(q), RoPE(k)     theta = rope_theta, half-split (lane i with i + Dh/2)
    query head j reads KV head j // (H / Hkv);  s = q . k / sqrt(Dh)
    row at position i sees column j  iff  j // B <= i // B
    out = concat(softmax(s) v) W_o

*Expert layer* (every layer)::

    s = softmax(h W_r^T) over all experts, float32
    T = the num_experts_per_tok largest;  w_e = s_e / sum_{j in T} s_j
    y = sum_{e in T} w_e down_e(silu(gate_e(h)) * up_e(h))

*Head*: logits = RMSNorm(x) W_head; **no shift** — the logits at position
``i`` are for position ``i``.

**Generation** (:func:`generate`; the source's ``generate.py``,
``block_diffusion_generate``, as written). The prompt fills ``x``; every
other position holds the mask id. Blocks the prompt fills whole are the
prefill. Then block after block: while a position of the block is
unfixed, a DENOISING pass runs the text up to the block's end, takes at
every unfixed position the argmax ``x0`` and its softmax probability (the
confidence), and fixes the ``num_transfer_tokens[step]`` most confident
(``low_confidence_static``); when nothing is unfixed the source runs one
more pass that stores the block's K/V — a COMMIT — which is no
computation here (nothing is cached: every pass is the whole forward).

**Departures, each stated.**

* ``q_norm``/``k_norm``: ``sdar_moe`` follows the Qwen3-MoE block; the
  catalog's ``config`` has no key for them.
* Whether a position is fixed is carried by a flag, not by ``x ==
  mask_id`` as the source has it: a prompt may hold that id.
* The mask id is never chosen: its logit is ``-inf`` before the argmax
  AND the softmax (the source does not exclude it; a trained model never
  picks it, a random one might).
* Ties of confidence go to the LOWEST position (``torch.topk`` does not
  say); a pass with fewer unfixed positions than it may fix takes them
  all.
* Temperature 0: ``x0`` is the argmax, its confidence the argmax's
  probability (the source's ``sample_with_temperature_topk_topp`` at
  temperature 0 gives the same).
* **The share.** With ``experts_held = (lo, hi)`` the expert layer routes
  over all experts and adds only the held ones' part; ``lw`` then holds
  the held experts only.
* Memory, not mathematics: an expert is applied to the rows that chose it
  (at most ``cap``, and if one ever has more the caller repeats the layer
  with every row), and :func:`served_margins` runs LAYER BY LAYER, one
  request at a time.

**The teacher-forced check** (:func:`served_margins`): the reference is
run on the STATES THE PROGRAM SAW. For a request of prompt ``p`` and
served tokens with the pass of their block each was fixed in, the state
of block ``b`` at pass ``k`` is: the committed text before the block, and
the block with the prompt's leftovers and the tokens fixed in passes
``< k``, the mask id elsewhere. Under the block mask the text before a
block does not see the block, so the committed text goes through a layer
ONCE and each state adds only its ``B`` rows, which attend to the
committed K/V before the block and to their own.

``quant="int8"`` computes every linear layer of the blocks and the head
(not the router) with weights rounded per output channel and activations
per row to symmetric 8-bit integers: the CONTROL that a cell's limits
must reject. The benchmark's own runs never set it.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

UNFIXED, GIVEN = -2, -1


@dataclass(frozen=True)
class Dims:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    norm_topk: bool
    eps: float
    theta: float
    layers: int
    held: tuple
    block: int
    steps: int
    mask_id: int

    @classmethod
    def of(cls, model: dict) -> "Dims":
        held = model.get("experts_held", (0, int(model["n_routed_experts"])))
        return cls(
            hidden=int(model["hidden_size"]),
            heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model["head_dim"]),
            experts=int(model["n_routed_experts"]),
            top_k=int(model["num_experts_per_tok"]),
            norm_topk=bool(model["norm_topk_prob"]),
            eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
            layers=int(model["num_hidden_layers"]),
            held=(int(held[0]), int(held[1])),
            block=int(model["block_length"]),
            steps=int(model["denoising_steps"]),
            mask_id=int(model["mask_token_id"]))


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


def _rope(d: Dims, x, positions):
    """Half-split rotary: lane ``i`` of the last axis turns with lane ``i
    + Dh / 2``. ``x [..., rows, heads, Dh]``, ``positions [..., rows]``."""
    half = d.head_dim // 2
    inv = d.theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = positions.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv.astype(np.float32))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _qkv(d: Dims, lw: dict, h, positions, quant=None):
    """``h [..., rows, E]`` (normed) -> ``q [..., rows, H, Dh]``, ``k``,
    ``v [..., rows, Hkv, Dh]``, ``q`` and ``k`` normed and rotated."""
    shape = h.shape[:-1]
    q = _linear(h, lw["wq"], quant).reshape(shape + (d.heads, d.head_dim))
    k = _linear(h, lw["wk"], quant).reshape(shape + (d.kv_heads, d.head_dim))
    v = _linear(h, lw["wv"], quant).reshape(shape + (d.kv_heads, d.head_dim))
    q = _rope(d, _rms_norm(q, lw["q_norm"], d.eps), positions)
    k = _rope(d, _rms_norm(k, lw["k_norm"], d.eps), positions)
    return q, k, v


def _grouped(d: Dims, q):
    """``[..., H, Dh]`` -> ``[..., Hkv, g, Dh]``: query head ``j`` is head
    ``j % g`` of KV head ``j // g``'s group."""
    return q.reshape(q.shape[:-2] + (d.kv_heads, d.heads // d.kv_heads,
                                     d.head_dim))


def attention(d: Dims, lw: dict, h, positions, *, quant=None, q_block=None):
    """Attention of ONE sequence under the block mask: ``h [S, E]``
    (already normed), ``positions [S]`` -> ``(out [S, E], k, v)``."""
    S = h.shape[0]
    q, k, v = _qkv(d, lw, h, positions, quant)
    q = _grouped(d, q)                                    # [S, Hkv, g, Dh]
    blk = positions // d.block

    def rows(args):
        qb, bq = args
        s = jnp.einsum("qngd,knd->ngqk", qb, k) * d.head_dim ** -0.5
        s = jnp.where((blk[None, :] <= bq[:, None])[None, None], s, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), v)

    qb = S if not q_block else int(q_block)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")
    cut = lambda a: a.reshape((S // qb, qb) + a.shape[1:])
    o = jax.lax.map(rows, (cut(q), cut(blk)))
    return _linear(o.reshape(S, d.heads * d.head_dim), lw["wo"], quant), k, v


def state_attention(d: Dims, lw: dict, hv, pos_v, b0, k_c, v_c, *,
                    quant=None, states_per_call=64):
    """Attention of the STATES' rows: ``hv [NS, B, E]`` (normed) at
    positions ``pos_v [NS, B]``; state ``s`` sees the committed columns
    ``< b0[s]`` (``k_c``/``v_c [S, Hkv, Dh]`` of the committed text) and
    its own ``B`` rows. Returns ``[NS, B, E]``."""
    NS, B = hv.shape[:2]
    S = k_c.shape[0]
    q, k, v = _qkv(d, lw, hv, pos_v, quant)
    q = _grouped(d, q)                                 # [NS, B, Hkv, g, Dh]
    cols = jnp.arange(S)

    def some(args):
        qs, ks, vs, b0s = args
        sc = jnp.einsum("sqngd,knd->sngqk", qs, k_c)
        sc = jnp.where((cols[None, :] < b0s[:, None])[:, None, None, None, :],
                       sc, -jnp.inf)
        so = jnp.einsum("sqngd,sknd->sngqk", qs, ks)
        p = jax.nn.softmax(
            jnp.concatenate([sc, so], axis=-1) * d.head_dim ** -0.5, axis=-1)
        return jnp.einsum("sngqk,knd->sqngd", p[..., :S], v_c) \
            + jnp.einsum("sngqk,sknd->sqngd", p[..., S:], vs)

    n = min(int(states_per_call), NS)
    if NS % n:
        raise ValueError(f"{NS} states are not a multiple of {n}")
    cut = lambda a: a.reshape((NS // n, n) + a.shape[1:])
    o = jax.lax.map(some, (cut(q), cut(k), cut(v), cut(b0)))
    return _linear(o.reshape(NS, B, d.heads * d.head_dim), lw["wo"], quant)


def route(d: Dims, router_w, h):
    """``(idx [N, k], w [N, k], scores [N, experts])`` of rows ``h``."""
    scores = jax.nn.softmax(jnp.matmul(h, router_w.T), axis=-1)
    top, idx = jax.lax.top_k(scores, d.top_k)
    w = top / jnp.sum(top, axis=-1, keepdims=True) if d.norm_topk else top
    return idx, w, scores


def expert_ffn(d: Dims, lw: dict, h, *, quant=None, cap=None):
    """Expert layer on rows ``h [N, E]`` (already normed): ``(y [N, E],
    overflow)``. ``lw`` holds experts ``d.held`` only. ``cap`` bounds the
    rows one expert is applied to; ``overflow`` counts experts that more
    rows chose (the result is then wrong and the caller repeats with
    ``cap=None``: every row through every held expert, masked)."""
    N = h.shape[0]
    idx, w, _ = route(d, lw["router"], h)
    dense = cap is None or cap >= N

    def one(carry, ex):
        y, overflow = carry
        e, gate, up, down = ex
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # [N]
        if dense:
            return (y + w_e[:, None] * _swiglu(h, gate, up, down, quant),
                    overflow), None
        chose = w_e > 0
        n = jnp.sum(chose, dtype=jnp.int32)
        rows = jnp.nonzero(chose, size=int(cap), fill_value=0)[0]
        live = jnp.arange(int(cap)) < n
        out = jnp.where(live[:, None], w_e[rows][:, None]
                        * _swiglu(h[rows], gate, up, down, quant), 0.0)
        return (y.at[rows].add(out),
                overflow + (n > cap).astype(jnp.int32)), None

    (y, overflow), _ = jax.lax.scan(
        one, (jnp.zeros_like(h), jnp.int32(0)),
        (jnp.arange(d.held[0], d.held[1]), lw["experts_gate"],
         lw["experts_up"], lw["experts_down"]))
    return y, overflow


@partial(jax.jit, static_argnames=("d", "quant", "cap", "q_block",
                                   "states_per_call"))
def layer(d: Dims, lw: dict, x, xv=None, pos_v=None, b0=None, *, quant=None,
          cap=None, q_block=None, states_per_call=64):
    """One layer on ONE sequence ``x [S, E]`` (float32) and, if given, on
    the states' rows ``xv [NS, B, E]`` beside it: ``(x, xv, overflow)``."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        S, E = x.shape
        pos = jnp.arange(S, dtype=jnp.int32)
        a, k_c, v_c = attention(d, lw, _rms_norm(x, lw["attn_norm"], d.eps),
                                pos, quant=quant, q_block=q_block)
        x = x + a
        rows = x
        if xv is not None:
            xv = xv + state_attention(
                d, lw, _rms_norm(xv, lw["attn_norm"], d.eps), pos_v, b0,
                k_c, v_c, quant=quant, states_per_call=states_per_call)
            rows = jnp.concatenate([x, xv.reshape(-1, E)])
        y, overflow = expert_ffn(
            d, lw, _rms_norm(rows, lw["ffn_norm"], d.eps), quant=quant,
            cap=cap)
        rows = rows + y
        if xv is not None:
            xv = rows[S:].reshape(xv.shape)
        return rows[:S], xv, overflow


def _through_layers(make, d: Dims, seqs: list, *, quant=None, q_block=None,
                    cap_share=None, states_per_call=64) -> list:
    """Every ``(x, xv, pos_v, b0)`` of ``seqs`` through every layer, LAYER
    BY LAYER: one layer's leaves are made, every sequence goes through
    it, then the next (``xv`` may be ``None``). Returns ``[(x, xv)]``."""
    out = [(x, xv) for x, xv, _, _ in seqs]
    for i in range(d.layers):
        lw = make.layer(i)
        for j, (_, _, pos_v, b0) in enumerate(seqs):
            x, xv = out[j]
            n = x.shape[0] + (0 if xv is None else xv.shape[0] * xv.shape[1])
            cap = None if cap_share is None else max(1, int(n * cap_share))
            kw = dict(quant=quant, q_block=q_block,
                      states_per_call=states_per_call)
            nx, nxv, overflow = layer(d, lw, x, xv, pos_v, b0, cap=cap, **kw)
            if cap is not None and int(overflow):
                nx, nxv, _ = layer(d, lw, x, xv, pos_v, b0, cap=None, **kw)
            out[j] = (nx, nxv)
        del lw
    return out


@partial(jax.jit, static_argnames=("d", "quant"))
def _head(d: Dims, hidden, norm_g, head_w, *, quant=None):
    """Float32 logits of rows ``hidden [N, E]``, the mask id's at
    ``-inf``."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        logits = _linear(_rms_norm(hidden, f32(norm_g), d.eps), f32(head_w),
                         quant)
        return logits.at[:, d.mask_id].set(-jnp.inf)


@partial(jax.jit, static_argnames=("d", "quant"))
def _head_stats(d: Dims, hidden, norm_g, head_w, tokens, *, quant=None):
    """Of rows ``hidden [N, E]``: the best logit ``top``, the spread of
    the row's finite logits ``std``, ``argmax``, its softmax probability
    ``conf``, and the logits of ``tokens [2, N]`` (``served``, ``also``)
    — reduced where the logits are, so that 0.6 MB a row never leaves
    the device."""
    lg = _head(d, hidden, norm_g, head_w, quant=quant)
    top = jnp.max(lg, axis=-1)
    finite = jnp.isfinite(lg)
    n = jnp.sum(finite, axis=-1)
    mean = jnp.sum(jnp.where(finite, lg, 0.0), axis=-1) / n
    var = jnp.sum(jnp.where(finite, (lg - mean[:, None]) ** 2, 0.0),
                  axis=-1) / n
    pick = lambda t: jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0]
    return {"top": top, "std": jnp.sqrt(var),
            "argmax": jnp.argmax(lg, axis=-1),
            "conf": 1.0 / jnp.sum(jnp.exp(lg - top[:, None]), axis=-1),
            "served": pick(tokens[0]), "also": pick(tokens[1])}


def logits(make, model: dict, ids, *, quant=None, q_block=None) -> np.ndarray:
    """Float32 logits ``[B, S, V]`` of token ids ``[B, S]`` under the
    block mask, the mask id's column at ``-inf`` (tests, and
    :func:`generate`)."""
    d = Dims.of(model)
    table = make.embed().astype(jnp.float32)
    seqs = [(table[jnp.asarray(row)], None, None, None)
            for row in np.asarray(ids)]
    del table
    out = _through_layers(make, d, seqs, quant=quant, q_block=q_block)
    norm_g, head_w = make.final_norm(), make.head()
    return np.stack([np.asarray(_head(d, x, norm_g, head_w, quant=quant))
                     for x, _ in out])


def num_transfer_tokens(block: int, steps: int) -> list:
    """The source's ``get_num_transfer_tokens``: ``block`` positions over
    ``steps`` passes, the remainder on the first ones."""
    return [block // steps + (i < block % steps) for i in range(steps)]


def confidence(row_logits: np.ndarray) -> tuple:
    """``(argmax [N], its softmax probability [N])`` of float32 logits
    ``[N, V]`` (the mask id's column already ``-inf``)."""
    top = row_logits.max(axis=-1)
    return row_logits.argmax(axis=-1), \
        1.0 / np.exp(row_logits - top[:, None]).sum(axis=-1)


def generate(make, model: dict, prompt, max_new_tokens: int) -> dict:
    """The source's ``block_diffusion_generate`` (module doc) for one
    prompt, greedy, ``low_confidence_static``. Returns ``tokens`` (the
    ``max_new_tokens`` generated), ``passes`` (for each, the pass of its
    block in which it was fixed) and ``logits``: per denoising pass
    ``(block start, pass, float32 logits of the block's rows [B, V])``."""
    d = Dims.of(model)
    B = d.block
    prompt = [int(t) for t in prompt]
    p, n = len(prompt), int(max_new_tokens)
    total = -(-(p + n) // B) * B
    x = np.full(total, d.mask_id, np.int64)
    x[:p] = prompt
    fixed_in = np.full(total, UNFIXED, np.int64)
    fixed_in[:p] = GIVEN
    transfer = num_transfer_tokens(B, d.steps)
    seen = []
    for b0 in range(p // B * B, total, B):
        for step in range(d.steps + 1):
            unfixed = fixed_in[b0:b0 + B] == UNFIXED
            if not unfixed.any():
                break                       # the source's commit pass
            shown = np.where(fixed_in[:b0 + B] == UNFIXED, d.mask_id,
                             x[:b0 + B])
            rows = logits(make, model, shown[None])[0, b0:]
            seen.append((b0, step, rows))
            x0, conf = confidence(rows)
            conf = np.where(unfixed, conf, -np.inf)
            order = np.argsort(-conf, kind="stable")
            for j in order[:min(transfer[step], int(unfixed.sum()))]:
                x[b0 + j], fixed_in[b0 + j] = x0[j], step
    return {"tokens": x[p:p + n].tolist(),
            "passes": fixed_in[p:p + n].tolist(), "logits": seen}


# -- the teacher-forced check ---------------------------------------------------

def request_states(d: Dims, prompt, tokens, passes) -> dict:
    """The states the program saw while it generated ``tokens`` after
    ``prompt`` (``passes[i]``: the pass of its block in which token ``i``
    was fixed). Only blocks the record holds whole are taken: a last
    block that ``max_tokens`` cuts short showed surplus tokens the
    record does not have. Returns numpy arrays over the ``NS`` states:
    ``b0 [NS]`` the block's start, ``ids [NS, B]`` what the block showed,
    ``unfixed [NS, B]``, ``chosen [NS, B]`` the positions the program
    fixed in that pass, and ``served [NS, B]`` their tokens."""
    B = d.block
    p, n = len(prompt), len(tokens)
    text = np.asarray(list(prompt) + list(tokens), np.int64)
    fixed_in = np.asarray([GIVEN] * p + [int(k) for k in passes], np.int64)
    out = {"b0": [], "ids": [], "unfixed": [], "chosen": [], "served": []}
    for b0 in range(p // B * B, (p + n) // B * B, B):
        tok, fin = text[b0:b0 + B], fixed_in[b0:b0 + B]
        for k in range(int(fin.max()) + 1):
            out["b0"].append(b0)
            out["ids"].append(np.where(fin < k, tok, d.mask_id))
            out["unfixed"].append(fin >= k)
            out["chosen"].append(fin == k)
            out["served"].append(tok)
    return {k: np.asarray(v) for k, v in out.items()}


def served_margins(make, model: dict, requests: list, *, width: int,
                   states: int, quant=None, q_block=None, cap_share=None,
                   states_per_call=64, head_rows=512) -> dict:
    """Teacher-forced margins of served text. ``requests`` is a list of
    ``(prompt, tokens, passes)``; each is padded to ``width`` committed
    positions and ``states`` states (one compiled shape). Returns flat
    numpy arrays: per served token of a whole block ``gap`` (the
    reference's best logit less its logit of the served token, at the
    pass in which the token was fixed) and ``std`` (that row's logit
    spread); per pass ``order_gap`` (the reference's confidence at its own
    most confident unfixed position less its confidence at the position
    the program fixed, over the former). With ``quant`` also
    ``control_gap`` and ``control_order_gap``: the same two numbers for
    what the LOWER precision, run on the same states, would have served
    and fixed."""
    d = Dims.of(model)
    B = d.block
    table = make.embed().astype(jnp.float32)
    seqs, metas = [], []
    for prompt, tokens, passes in requests:
        st = request_states(d, prompt, tokens, passes)
        NS = len(st["b0"])
        text = list(prompt) + list(tokens)
        if len(text) > width or NS > states:
            raise ValueError(
                f"a request of {len(text)} tokens and {NS} states exceeds "
                f"the reference's width {width} / states {states}")
        ids = np.zeros(width, np.int64)
        ids[:len(text)] = text
        ids_v = np.zeros((states, B), np.int64)
        b0 = np.zeros(states, np.int64)
        ids_v[:NS], b0[:NS] = st["ids"], st["b0"]
        pos_v = b0[:, None] + np.arange(B)[None, :]
        seqs.append((table[jnp.asarray(ids)], table[jnp.asarray(ids_v)],
                     jnp.asarray(pos_v, jnp.int32), jnp.asarray(b0, jnp.int32)))
        metas.append((st, NS))
    del table
    norm_g, head_w = make.final_norm(), make.head()

    def read(q, also=None):
        """Per request the reductions of its states' rows' float32 logits
        under precision ``q`` (:func:`_head_stats`, ``head_rows`` rows a
        call): the best logit, the row's spread, the argmax and its
        confidence, the logit of the served token and, with ``also`` (a
        token a row, per request), of that token."""
        done = _through_layers(make, d, seqs, quant=q, q_block=q_block,
                               cap_share=cap_share,
                               states_per_call=states_per_call)
        outs = []
        for r, ((_, xv), (st, NS)) in enumerate(zip(done, metas)):
            # whole calls of head_rows rows (one compiled shape), over
            # the request's own states only
            pad = -(states * B) % head_rows
            rows = jnp.pad(xv.reshape(states * B, -1), ((0, pad), (0, 0)))
            tokens = np.zeros((2, states * B + pad), np.int32)
            tokens[0, :NS * B] = st["served"].reshape(-1)
            if also is not None:
                tokens[1, :NS * B] = also[r]
            parts = [_head_stats(d, rows[a:a + head_rows], norm_g, head_w,
                                 jnp.asarray(tokens[:, a:a + head_rows]),
                                 quant=q)
                     for a in range(0, NS * B, head_rows)]
            outs.append({k: np.concatenate([np.asarray(p[k]) for p in parts]
                                           )[:NS * B] for k in parts[0]})
        return outs

    def order_gap(conf, st, pick):
        """Per state: the best confidence among the unfixed positions
        less the least confidence among the positions ``pick`` fixed,
        over the former."""
        c = np.where(st["unfixed"], conf.reshape(-1, B), -np.inf)
        best = c.max(axis=-1)
        mine = np.where(pick, c, np.inf).min(axis=-1)
        return (best - mine) / best

    lower = read(quant) if quant is not None else None
    plain = read(None, None if lower is None
                 else [lo["argmax"] for lo in lower])
    res = {"gap": [], "std": [], "order_gap": [], "control_gap": [],
           "control_order_gap": []}
    for o, lo, (st, NS) in zip(plain, lower or [None] * len(plain), metas):
        chosen = st["chosen"].reshape(-1)
        res["gap"].append((o["top"] - o["served"])[chosen])
        res["std"].append(o["std"][chosen])
        res["order_gap"].append(order_gap(o["conf"], st, st["chosen"]))
        if lo is not None:
            res["control_gap"].append((o["top"] - o["also"])[chosen])
            # the positions the lower precision would have fixed: as many
            # as the program did, its own most confident unfixed ones
            c = np.where(st["unfixed"], lo["conf"].reshape(-1, B), -np.inf)
            rank = np.argsort(np.argsort(-c, axis=-1, kind="stable"),
                              axis=-1, kind="stable")
            pick = rank < st["chosen"].sum(axis=-1, keepdims=True)
            res["control_order_gap"].append(order_gap(o["conf"], st, pick))
    return {k: np.concatenate(v) for k, v in res.items() if v}
