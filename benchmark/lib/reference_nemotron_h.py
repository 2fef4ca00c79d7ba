"""The plain reference of Nemotron-H (``model_type: nemotron_h``, source
``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/
blob/main/config.json``) in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
chunks, no paging, no batching policy, nothing imported from the program
(not its model, not its ``ops``).

**The block** (from the source's ``config``; ``x`` is ``[T, E]``;
``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``, ``eps =
layer_norm_epsilon``). Block ``i`` is of the kind
``hybrid_override_pattern[i]`` and is ``x <- x + f_i(rms(x; g_i))``, ``u``
the normed input, no bias but the convolution's::

    M   [z | xBC | dt] = u W_in
            widths d_ssm | d_ssm + 2 G N | heads (d_ssm = heads x P)
        xBC_t = silu(sum_{j=0..K-1} w_j * xBC_{t-K+1+j} + b)   depthwise,
            causal (inputs before the sequence are 0), as K shifted
            products
        x_t [heads, P], B_t, C_t [G, N] = split(xBC_t); head h reads
            group h // (heads / G)
        dt_t = softplus(dt_t + dt_bias_h)          not clamped
        A_h = -exp(A_log_h)
        H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t (x) B_t   H [P, N], H_{-1} = 0
        y_t = H_t C_t + D_h x_t                a SEQUENTIAL lax.scan over t
        g = rms_grouped(y * silu(z); G groups of d_ssm / G lanes; gain)
        f = g W_out

    *   q = u W_q -> H heads of Dh;  k = u W_k, v = u W_v -> Hkv heads
        NO position embedding; query head j reads KV head j // (H / Hkv);
        row i sees j <= i;  f = (softmax(q k^T / sqrt(Dh)) v) W_o

    E   s = sigmoid(u W_g^T)                       [experts], float32
        T = the top_k largest of s + b   (b: e_score_correction_bias)
        w_e = routed_scaling_factor * s_e / sum_{j in T} s_j
        v = u W_down                               hidden -> latent
        expert_e(v) = relu(v W1_e)^2 W2_e          latent -> I -> latent
        f = (sum_{e in T, held} w_e expert_e(v)) W_up
            + relu(u W1_s)^2 W2_s                  the shared expert

Embedding unscaled; after the last block ``rms(.; norm_f)``; logits ``= x
W_head`` (untied).

**Departures and conventions, each stated** (``assumed`` in the
configuration file says the same):

* No rotary in attention: the family's code gives its attention no
  position embedding (the Mamba blocks carry position); ``rope_theta`` and
  ``partial_rotary_factor`` of the row select nothing there.
* The multi-token-prediction head is not part of the forward pass.
* The share: ``model["experts_held"] = [lo, hi)`` — the sum over the
  chosen experts runs over the held ones only, ``w_e`` normalised over
  ALL ``top_k`` chosen; absent: every expert.
* Memory, not mathematics: the sampled sequences go through a mixer and
  an attention one at a time (``lax.map``), attention ``q_block`` query
  rows at a time, the held experts one after another (``lax.scan``; every
  row through every held expert, weighted 0 where it did not choose it:
  5.5 MB an expert makes that cheap — the form that gathers the rows
  that chose an expert did not compile on the chip, PERF.md, PR 50), and
  the head is applied
  in blocks of the vocabulary (``_margins``); ``served_margins`` runs
  BLOCK BY BLOCK: one block's weights are made, every sampled sequence
  goes through it, then the next block.

Two CONTROLS that a cell's limits must reject, never set by the
benchmark's own runs: ``quant="int8"`` computes every linear layer of the
blocks and the head with weights rounded per output channel and
activations per row to symmetric 8-bit integers (W8A8; the router stays
float32, as the program's); ``quant="bf16_state"`` rounds the recurrent
state ``H`` to bfloat16 after every step — what a deployment that keeps
the state in the model's dtype does, and what the configuration says
this one does not.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INT8, BF16_STATE = "int8", "bf16_state"
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


@dataclass(frozen=True)
class Dims:
    hidden: int
    pattern: str            # the kinds of the blocks held
    heads: tuple            # (H, Hkv, Dh)
    ssm: tuple              # (d_ssm, heads, P, N, G, K)
    experts: int            # the router's width
    top_k: int
    routed_scale: float
    norm_topk: bool
    eps: float
    held: tuple

    @classmethod
    def of(cls, model: dict) -> "Dims":
        Hs, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
        n = int(model["n_routed_experts"])
        held = model.get("experts_held", (0, n))
        return cls(
            hidden=int(model["hidden_size"]),
            pattern=str(model["hybrid_override_pattern"])[
                :int(model["num_hidden_layers"])],
            heads=(int(model["num_attention_heads"]),
                   int(model["num_key_value_heads"]), int(model["head_dim"])),
            ssm=(Hs * P, Hs, P, int(model["ssm_state_size"]),
                 int(model["n_groups"]), int(model["conv_kernel"])),
            experts=n, top_k=int(model["num_experts_per_tok"]),
            routed_scale=float(model["routed_scaling_factor"]),
            norm_topk=bool(model["norm_topk_prob"]),
            eps=float(model["layer_norm_epsilon"]),
            held=(int(held[0]), int(held[1])))

    @property
    def layers(self) -> int:
        return len(self.pattern)


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


def _relu2(x, up, down, quant=None):
    return _linear(jnp.square(jax.nn.relu(_linear(x, up, quant))), down,
                   quant)


def attention(d: Dims, lw: dict, u, *, quant=None, q_block=None):
    """Attention of ONE sequence: ``u [T, E]`` (normed) -> ``[T, E]``, a
    masked softmax over all T columns, ``q_block`` query rows at a time.
    No position embedding."""
    T = u.shape[0]
    H, Hkv, Dh = d.heads
    g = H // Hkv
    q = _linear(u, lw["wq"], quant).reshape(T, Hkv, g, Dh)
    k = _linear(u, lw["wk"], quant).reshape(T, Hkv, Dh)
    v = _linear(u, lw["wv"], quant).reshape(T, Hkv, Dh)
    cols = jnp.arange(T, dtype=jnp.int32)

    def rows(args):
        qa, pos = args
        s = jnp.einsum("qngd,knd->ngqk", qa, k) * Dh ** -0.5
        s = jnp.where(cols[None, None, None, :] <= pos[None, None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), v)

    qb = T if not q_block else min(int(q_block), T)
    if T % qb:
        raise ValueError(f"sequence {T} is not a multiple of q_block {qb}")
    o = jax.lax.map(rows, (q.reshape((T // qb, qb) + q.shape[1:]),
                           cols.reshape(T // qb, qb)))
    return _linear(o.reshape(T, H * Dh), lw["wo"], quant)


def mixer(d: Dims, lw: dict, u, quant=None, state_after=None):
    """The Mamba-2 mixer of ONE sequence from a zero state: ``u [T, E]``
    (normed) -> ``[T, E]``. The convolution as K shifted products, the
    recurrence a sequential scan over the T tokens. With ``state_after``
    (a number of tokens n <= T) also what the sequence's first n tokens
    leave behind: ``(the convolution's last K - 1 inputs before position
    n [K - 1, channels], H after token n - 1)`` (tests)."""
    T = u.shape[0]
    D, Hs, P, N, G, K = d.ssm
    zxbcdt = _linear(u, lw["ssm_in"], quant)
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
            # allowed to keep (PERF.md 40.6: the control then read 0)
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
    f = _linear(g, lw["ssm_out"], quant)
    if state_after is not None:
        tail = jax.lax.dynamic_slice_in_dim(
            jnp.pad(inputs, ((K - 1, 0), (0, 0))), state_after, K - 1, 0)
        return f, (tail, h_kept)
    return f


def route(d: Dims, router_w, bias, u, select_bias=True):
    """``(idx [N, k], w [N, k], s [N, experts])`` of rows ``u``: the choice
    by ``s + b`` (``select_bias`` False: by ``s``, a test's control), the
    weights the chosen ``s`` over their sum, times ``routed_scale``."""
    s = jax.nn.sigmoid(jnp.matmul(u, router_w.T))
    _, idx = jax.lax.top_k(s + bias[None, :] if select_bias else s, d.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if d.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * d.routed_scale, s


def moe(d: Dims, lw: dict, u, *, quant=None):
    """An ``E`` block's two terms on rows ``u [N, E]`` (normed): ``(routed
    [N, E], shared [N, E])`` — the held experts' part of the routed sum,
    projected back up, and the shared expert. ``lw`` holds experts
    ``d.held`` only; every row goes through every held expert, weighted
    by the row's weight for it (0 where the row did not choose it)."""
    lo, hi = d.held
    idx, w, _ = route(d, lw["router"], lw["router_bias"], u)
    v = _linear(u, lw["latent_down"], quant)                  # [N, L]

    def one(m, xs):
        up, down, e = xs
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)   # [N]
        return m + w_e[:, None] * _relu2(v, up, down, quant), None

    m, _ = jax.lax.scan(
        one, jnp.zeros_like(v),
        (lw["experts_up"], lw["experts_down"],
         jnp.arange(lo, hi, dtype=jnp.int32)))
    return (_linear(m, lw["latent_up"], quant),
            _relu2(u, lw["shared_up"], lw["shared_down"], quant))


@partial(jax.jit, static_argnames=("d", "kind", "quant", "q_block"))
def block(d: Dims, kind: str, lw: dict, x, *, quant=None, q_block=None):
    """One block of kind ``kind`` on ``x [B, T, E]`` (float32)."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        B, T, E = x.shape
        u = _rms_norm(x, lw["norm"], d.eps)
        if kind == MAMBA:
            return x + jax.lax.map(lambda r: mixer(d, lw, r, quant), u)
        if kind == ATTENTION:
            return x + jax.lax.map(lambda r: attention(
                d, lw, r, quant=quant, q_block=q_block), u)
        routed, shared = moe(d, lw, u.reshape(B * T, E), quant=quant)
        return x + (routed + shared).reshape(B, T, E)


@partial(jax.jit, static_argnames=("eps", "quant", "blocks"))
def _margins(hidden, norm_g, head_w, served, *, eps, quant=None, blocks=1):
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
            lg = _linear(h, w, quant)                         # [M, width]
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
    """Column blocks the head is applied in: of at most ~16 k columns."""
    for n in (8, 4, 2):
        if vocab % n == 0 and vocab // n >= 1024:
            return n
    return 1


def hidden_states(make, model: dict, ids, *, rows_per_call: int, quant=None,
                  q_block=None):
    """Hidden states before the final norm, ``[B, T, E]`` float32 as a list
    of ``rows_per_call``-sequence blocks, BLOCK BY BLOCK of the model: the
    embedding's rows, then ``make.layer(i)`` (one block's leaves, dropped
    before the next is made), every group of sequences through it, then
    the next."""
    d = Dims.of(model)
    ids = np.asarray(ids)
    B = ids.shape[0]
    r = int(rows_per_call)
    if B % r:
        raise ValueError(f"{B} sequences are not a multiple of "
                         f"rows_per_call {r}")
    table = make.embed()
    groups = [table[jnp.asarray(ids[b:b + r])].astype(jnp.float32)
              for b in range(0, B, r)]
    del table
    for i, kind in enumerate(d.pattern):
        lw = make.layer(i)
        for j, x in enumerate(groups):
            groups[j] = block(d, kind, lw, x, quant=quant, q_block=q_block)
        del lw
    return groups


@partial(jax.jit, static_argnames=("d",))
def _mixer_with_state(d: Dims, lw: dict, x, n):
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        f, left = mixer(d, lw, _rms_norm(x, lw["norm"], d.eps),
                        state_after=n)
        return x + f, left


def final_states(make, model: dict, ids, n: int) -> list:
    """What the first ``n`` tokens of ONE sequence ``ids [T]`` leave behind
    in every ``M`` block, in order: ``[(conv tail [K - 1, channels], H
    [heads, P, N])]`` float32 (tests: what the program's pool must hold
    for the slot once those tokens are in; the tokens after them change
    nothing before them, so a test pads ``ids`` to one compiled width)."""
    d = Dims.of(model)
    x = make.embed()[jnp.asarray(ids)].astype(jnp.float32)
    out = []
    for i, kind in enumerate(d.pattern):
        if kind == MAMBA:
            x, left = _mixer_with_state(d, make.layer(i), x, jnp.int32(n))
            out.append(tuple(np.asarray(a) for a in left))
        else:
            x = block(d, kind, make.layer(i), x[None])[0]
    return out


def served_margins(make, model: dict, ids, positions, served, *,
                   rows_per_call: int, quant=None, q_block=None) -> dict:
    """Teacher-forced margins of served text, as
    ``reference_falcon_h1.served_margins`` gives them: ``ids [B, T]`` holds
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

    def read(groups, tokens, q=None):
        outs = []
        for j, x in enumerate(groups):
            for b in range(r):                 # a sequence's rows a call
                row = j * r + b
                outs.append(_margins(
                    x[b][jnp.asarray(positions[row])], norm_g, head_w,
                    jnp.asarray(tokens[row]), eps=d.eps, quant=q, blocks=nb))
        return {k: np.stack([np.asarray(o[k]) for o in outs])
                for k in outs[0]}

    kw = dict(rows_per_call=r, q_block=q_block)
    plain = hidden_states(make, model, ids, **kw)
    out = read(plain, served)
    if quant is not None:
        first = read(hidden_states(make, model, ids, quant=quant, **kw),
                     served, quant)["argmax"]
        out["control_gap"] = read(plain, first)["gap"]
    return out


def logits(make, model: dict, ids, *, quant=None, q_block=None) -> np.ndarray:
    """Float32 logits ``[B, T, V]`` of token ids ``[B, T]`` (tests, at
    sizes where the whole head fits)."""
    d = Dims.of(model)
    groups = hidden_states(make, model, ids, rows_per_call=len(ids),
                           quant=quant, q_block=q_block)
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        return np.asarray(_linear(
            _rms_norm(groups[0], f32(make.final_norm()), d.eps),
            f32(make.head()), quant))
