"""The plain reference of Falcon-H1 (``model_type: falcon_h1``, source
``https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/
config.json``) in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
chunks, no paging, no batching policy, nothing imported from the program
(not its model, not its ``ops``).

**The layer** (from the source's ``config``; ``x`` is ``[T, E]``,
positions absolute; ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``, ``eps =
rms_norm_eps``). EVERY layer runs attention and a Mamba-2 mixer on the
same normed input and adds both::

    u = rms(x; g1);   u' = u * attention_in_multiplier
    q = u' W_q -> H heads of Dh;   k = (u' W_k) * key_multiplier,
    v = u' W_v -> Hkv heads of Dh  (no bias); rotary on ALL Dh lanes,
        half-split (lane i with lane i + Dh/2), rope_theta, no scaling
    query head j reads KV head j // (H / Hkv); row i sees j <= i
    a = (softmax(q k^T / sqrt(Dh)) v) W_o * attention_out_multiplier

    [z | xBC | dt] = ((u * ssm_in_multiplier) W_in) * m
        widths d_ssm | d_ssm + 2 G N | heads; m the muP vector: the five
        ssm_multipliers on the segments z, x, B, C, dt
    xBC_t = silu(sum_{j=0..K-1} w_j * xBC_{t-K+1+j} + b)   depthwise,
        causal (inputs before the sequence are 0), as K shifted products
    x_t [heads, P], B_t, C_t [G, N] = split(xBC_t); head h reads group
        h // (heads / G)
    dt_t = softplus(dt_t + dt_bias_h)      (time_step_limit (0, inf))
    A_h = -exp(A_log_h)
    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t (x) B_t      H [P, N], H_{-1} = 0
    y_t = H_t C_t + D_h x_t                a SEQUENTIAL lax.scan over t
    g = rms_grouped(y * silu(z); G groups of d_ssm / G lanes; gain)
        (mamba_norm_before_gate false: the gate first, then the norm)
    s = (g W_out) * ssm_out_multiplier

    x = x + a + s
    v = rms(x; g2)
    x = x + ((silu((v W_gate) * mlp_multipliers[0]) * (v W_up)) W_down)
            * mlp_multipliers[1]

Embedding ``* embedding_multiplier``; final ``rms``; logits ``= (x W_head)
* lm_head_multiplier``.

**Departures and conventions, each stated** (``assumed`` in the
configuration file says the same):

* The muP vector's segment order z, x, B, C, dt and the split order ``z |
  xBC | dt`` are the family's code's; ``attention_in_multiplier`` scales
  the input of all three projections (it is 1 as published).
* Memory, not mathematics: the sampled sequences go through a layer one
  at a time (``lax.map``), and the 261,120-row head is applied in blocks
  of the vocabulary, the largest logit, the sums of the spread and the
  served token's logit carried from block to block
  (``_margins``); ``served_margins`` runs LAYER BY LAYER: one layer's
  weights are made, every sampled sequence goes through it, then the
  next layer.

Two CONTROLS that a cell's limits must reject, never set by the
benchmark's own runs: ``quant="int8"`` computes every linear layer of the
blocks and the head with weights rounded per output channel and
activations per row to symmetric 8-bit integers (W8A8);
``quant="bf16_state"`` rounds the recurrent state ``H`` to bfloat16 after
every step — what a deployment that keeps the state in the model's dtype
does, and what the configuration says this one does not.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INT8, BF16_STATE = "int8", "bf16_state"


@dataclass(frozen=True)
class Dims:
    hidden: int
    heads: tuple            # (H, Hkv, Dh)
    theta: float
    ssm: tuple              # (d_ssm, heads, P, N, G, K)
    eps: float
    layers: int
    mult: tuple             # sorted (name, value) of the multipliers

    @classmethod
    def of(cls, model: dict) -> "Dims":
        Hs, P = int(model["mamba_n_heads"]), int(model["mamba_d_head"])
        mult = {k: float(model[k]) for k in (
            "embedding_multiplier", "lm_head_multiplier",
            "attention_in_multiplier", "attention_out_multiplier",
            "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")}
        mult["ssm_multipliers"] = tuple(float(v) for v in
                                        model["ssm_multipliers"])
        mult["mlp_multipliers"] = tuple(float(v) for v in
                                        model["mlp_multipliers"])
        return cls(
            hidden=int(model["hidden_size"]),
            heads=(int(model["num_attention_heads"]),
                   int(model["num_key_value_heads"]), int(model["head_dim"])),
            theta=float(model["rope_theta"]),
            ssm=(int(model["mamba_d_ssm"]), Hs, P,
                 int(model["mamba_d_state"]), int(model["mamba_n_groups"]),
                 int(model["mamba_d_conv"])),
            eps=float(model["rms_norm_eps"]),
            layers=int(model["num_hidden_layers"]),
            mult=tuple(sorted(mult.items())))

    def m(self, name):
        return dict(self.mult)[name]


# -- pieces -------------------------------------------------------------------

def _round_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _linear(x, w, quant=None):
    """``x @ w`` with ``w`` [in, out]."""
    if quant == INT8:
        x = _round_int8(x, axis=-1)          # per row (token)
        w = _round_int8(w, axis=0)           # per output channel
    elif quant not in (None, BF16_STATE):
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(x, w)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


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


def attention(d: Dims, lw: dict, u, positions, quant=None):
    """Attention of ONE sequence: ``u [T, E]`` (normed) -> ``[T, E]``, a
    masked softmax over all T columns."""
    T = u.shape[0]
    H, Hkv, Dh = d.heads
    g = H // Hkv
    u = u * d.m("attention_in_multiplier")
    q = _rope(_linear(u, lw["wq"], quant).reshape(T, H, Dh), positions,
              d.theta)
    k = _rope((_linear(u, lw["wk"], quant) * d.m("key_multiplier")
               ).reshape(T, Hkv, Dh), positions, d.theta)
    v = _linear(u, lw["wv"], quant).reshape(T, Hkv, Dh)
    s = jnp.einsum("qngd,knd->ngqk", q.reshape(T, Hkv, g, Dh), k) \
        * Dh ** -0.5
    seen = positions[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("ngqk,knd->qngd", p, v).reshape(T, H * Dh)
    return _linear(o, lw["wo"], quant) * d.m("attention_out_multiplier")


def mup_vector(d: Dims):
    D, Hs, _, N, G, _ = d.ssm
    widths = (D, D, G * N, G * N, Hs)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                            zip(widths, d.m("ssm_multipliers"))])


def mixer(d: Dims, lw: dict, u, quant=None, state_after=None):
    """The Mamba-2 branch of ONE sequence from a zero state: ``u [T, E]``
    (normed) -> ``[T, E]``. The convolution as K shifted products, the
    recurrence a sequential scan over the T tokens. With ``state_after``
    (a number of tokens n <= T) also what the sequence's first n tokens
    leave behind: ``(the convolution's last K - 1 inputs before position
    n [K - 1, channels], H after token n - 1)`` (tests)."""
    T = u.shape[0]
    D, Hs, P, N, G, K = d.ssm
    zxbcdt = _linear(u * d.m("ssm_in_multiplier"), lw["ssm_in"], quant) \
        * mup_vector(d)
    z, xbc, dt = (zxbcdt[:, :D], zxbcdt[:, D:2 * D + 2 * G * N],
                  zxbcdt[:, 2 * D + 2 * G * N:])
    inputs = xbc
    conv = lw["conv_b"][None, :]
    for j in range(K):
        back = K - 1 - j
        conv = conv + lw["conv_w"][j][None, :] * jnp.pad(
            xbc, ((back, 0), (0, 0)))[:T]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :D].reshape(T, Hs, P)
    B = jnp.repeat(xbc[:, D:D + G * N].reshape(T, G, N), Hs // G, axis=1)
    C = jnp.repeat(xbc[:, D + G * N:].reshape(T, G, N), Hs // G, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"][None, :])       # [T, Hs]
    A = -jnp.exp(lw["A_log"])

    n_keep = -1 if state_after is None else state_after

    def step(carry, row):
        h, kept = carry
        xt, bt, ct, dtt, t = row
        h = jnp.exp(dtt * A)[:, None, None] * h \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        if quant == BF16_STATE:
            # reduce_precision, not a pair of converts: XLA may drop a
            # convert to bfloat16 and back as excess precision it is
            # allowed to keep (it does on the chip: the control then read
            # a gap of exactly 0 on 18 k tokens, my chip run, PR 40)
            h = jax.lax.reduce_precision(h, exponent_bits=8,
                                         mantissa_bits=7)
        return (h, jnp.where(t == n_keep - 1, h, kept)), \
            jnp.sum(h * ct[:, None, :], axis=-1)

    zero = jnp.zeros((Hs, P, N), jnp.float32)
    (_, h_kept), y = jax.lax.scan(
        step, (zero, zero), (x, B, C, dt, jnp.arange(T, dtype=jnp.int32)))
    y = y + lw["D"][None, :, None] * x
    g = (y.reshape(T, D) * jax.nn.silu(z)).reshape(T, G, D // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + d.eps)
    g = g.reshape(T, D) * lw["ssm_norm"][None, :]
    s = _linear(g, lw["ssm_out"], quant) * d.m("ssm_out_multiplier")
    if state_after is not None:
        tail = jax.lax.dynamic_slice_in_dim(
            jnp.pad(inputs, ((K - 1, 0), (0, 0))), state_after, K - 1, 0)
        return s, (tail, h_kept)
    return s


def ffn(d: Dims, lw: dict, v, quant=None):
    gm, dm = d.m("mlp_multipliers")
    h = jax.nn.silu(_linear(v, lw["gate"], quant) * gm) \
        * _linear(v, lw["up"], quant)
    return _linear(h, lw["down"], quant) * dm


@partial(jax.jit, static_argnames=("d", "quant"))
def layer(d: Dims, lw: dict, x, *, quant=None):
    """One layer on ``x [B, T, E]`` (float32), a sequence at a time."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)

        def one(row):
            u = _rms_norm(row, lw["attn_norm"], d.eps)
            row = row + attention(d, lw, u, pos, quant) \
                + mixer(d, lw, u, quant)
            return row + ffn(d, lw, _rms_norm(row, lw["ffn_norm"], d.eps),
                             quant)

        return jax.lax.map(one, x)


@partial(jax.jit, static_argnames=("eps", "mult", "quant", "blocks"))
def _margins(hidden, norm_g, head_w, served, *, eps, mult, quant=None,
             blocks=1):
    """``hidden [M, E]`` -> per row: the gap of the served token's logit
    under the row's best, the row's logit spread, its argmax — the head
    applied to ``blocks`` column blocks of the vocabulary in turn."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        M, V = hidden.shape[0], head_w.shape[1]
        h = _rms_norm(hidden, f32(norm_g), eps)
        width = V // blocks

        def one(carry, i):
            top, arg, s1, s2, got = carry
            w = f32(jax.lax.dynamic_slice_in_dim(head_w, i * width, width, 1))
            lg = _linear(h, w, quant) * mult                  # [M, width]
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
    """Column blocks the head is applied in: of at most ~33 k columns."""
    for n in (8, 4, 2):
        if vocab % n == 0 and vocab // n >= 1024:
            return n
    return 1


def hidden_states(make, model: dict, ids, *, rows_per_call: int, quant=None):
    """Hidden states before the final norm, ``[B, T, E]`` float32 as a list
    of ``rows_per_call``-sequence blocks, LAYER BY LAYER: the embedding's
    rows, then ``make.layer(i)`` (one layer's leaves, dropped before the
    next is made), every block through that layer, then the next."""
    d = Dims.of(model)
    ids = np.asarray(ids)
    B = ids.shape[0]
    r = int(rows_per_call)
    if B % r:
        raise ValueError(f"{B} sequences are not a multiple of "
                         f"rows_per_call {r}")
    table = make.embed()
    blocks = [table[jnp.asarray(ids[b:b + r])].astype(jnp.float32)
              * d.m("embedding_multiplier") for b in range(0, B, r)]
    del table
    for i in range(d.layers):
        lw = make.layer(i)
        for j, x in enumerate(blocks):
            blocks[j] = layer(d, lw, x, quant=quant)
        del lw
    return blocks


@partial(jax.jit, static_argnames=("d",))
def _layer_with_state(d: Dims, lw: dict, x, n):
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)
        u = _rms_norm(x, lw["attn_norm"], d.eps)
        s, left = mixer(d, lw, u, state_after=n)
        x = x + attention(d, lw, u, pos) + s
        return x + ffn(d, lw, _rms_norm(x, lw["ffn_norm"], d.eps)), left


def final_states(make, model: dict, ids, n: int) -> list:
    """What the first ``n`` tokens of ONE sequence ``ids [T]`` leave behind
    in every layer: ``[(conv tail [K - 1, channels], H [heads, P, N])]``
    float32 (tests: what the program's pool must hold for the slot once
    those tokens are in; the tokens after them change nothing before
    them, so a test pads ``ids`` to one compiled width)."""
    d = Dims.of(model)
    x = make.embed()[jnp.asarray(ids)].astype(jnp.float32) \
        * d.m("embedding_multiplier")
    out = []
    for i in range(d.layers):
        x, left = _layer_with_state(d, make.layer(i), x, jnp.int32(n))
        out.append(tuple(np.asarray(a) for a in left))
    return out


def served_margins(make, model: dict, ids, positions, served, *,
                   rows_per_call: int, quant=None) -> dict:
    """Teacher-forced margins of served text, as
    ``reference_mimo.served_margins`` gives them: ``ids [B, T]`` holds
    prompt + served tokens right-padded; ``positions [B, n]`` the
    positions whose logits PREDICT each served token and ``served [B, n]``
    those tokens. Returns numpy ``gap``, ``std``, ``argmax`` ``[B, n]``
    and, with ``quant``, ``control_gap``: the reference's gap for the token
    the control puts first."""
    positions = np.asarray(positions)
    served = np.asarray(served)
    r = int(rows_per_call)
    d = Dims.of(model)
    norm_g, head_w = make.final_norm(), make.head()
    nb = head_blocks(int(head_w.shape[1]))

    def read(blocks, tokens, q=None):
        outs = []
        for j, x in enumerate(blocks):
            for b in range(r):                 # a sequence's rows a call
                row = j * r + b
                outs.append(_margins(
                    x[b][jnp.asarray(positions[row])], norm_g, head_w,
                    jnp.asarray(tokens[row]), eps=d.eps,
                    mult=d.m("lm_head_multiplier"), quant=q, blocks=nb))
        return {k: np.stack([np.asarray(o[k]) for o in outs])
                for k in outs[0]}

    plain = hidden_states(make, model, ids, rows_per_call=r)
    out = read(plain, served)
    if quant is not None:
        first = read(hidden_states(make, model, ids, rows_per_call=r,
                                   quant=quant), served, quant)["argmax"]
        out["control_gap"] = read(plain, first)["gap"]
    return out


def logits(make, model: dict, ids, *, quant=None) -> np.ndarray:
    """Float32 logits ``[B, T, V]`` of token ids ``[B, T]`` (tests, at
    sizes where the whole head fits)."""
    d = Dims.of(model)
    blocks = hidden_states(make, model, ids, rows_per_call=len(ids),
                           quant=quant)
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        return np.asarray(_linear(
            _rms_norm(blocks[0], f32(make.final_norm()), d.eps),
            f32(make.head()), quant) * d.m("lm_head_multiplier"))
