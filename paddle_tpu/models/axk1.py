"""A.X-K1 (``model_type: axk1``, skt/A.X-K1 ``config.json``): a decoder
of latent attention (MLA) and routed experts — the second caller of the
decoder spec (``models/decoder_spec.py``) beside GPT-2.

Per layer, pre-norm with RMSNorm and residual adds, no biases:

* **MLA.** ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> per head
  ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c_kv =
  RMSNorm(c_kv)``; rotary positions (YaRN) on ``q_pe`` and on the ONE
  ``k_pe`` all heads share; ``[k_nope | v] = c_kv W_kvb`` per head;
  softmax of ``(q_nope k_nope + q_pe k_pe) * scale`` over the causal
  prefix, ``out = concat(p v) W_o``. ``scale = (nope + rope)^-1/2 * m^2``,
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
  What a token leaves in the cache is ``[c_kv | k_pe]`` (512 + 64 values
  at the published sizes), one row for all heads. The serving step runs
  the ABSORBED form against it: ``q_lat = q_nope W_UK^T``, ``s = q_lat
  c_kv + q_pe k_pe``, ``o_lat = sum p c_kv``, ``o = o_lat W_UV`` (``W_UK``
  and ``W_UV`` the two halves of ``W_kvb``), so K and V are the same
  stored bytes. ``forward`` (no cache) runs the naive form.
* **Dense layer** (the first ``first_k_dense_replace``): SwiGLU,
  ``down(silu(gate(x)) * up(x))``.
* **Expert layer**: ``g = sigmoid(x W_g^T)`` in float32 over ALL
  ``n_routed_experts``; ``T = top-k(g)``; ``w_e = routed_scaling_factor *
  g_e / sum_{j in T} g_j``; ``y = shared(x) + sum_{e in T} w_e
  expert_e(x)``. ``topk_method: "none"`` is read as plain top-k: no group
  limit, no score-correction bias.

**Serving a share** (``experts_held=(lo, hi)``): the layer holds experts
``lo .. hi - 1`` of an expert-parallel deployment, routes over all of
them, and adds only ``sum_{e in T, lo <= e < hi} w_e expert_e(x)`` (``w_e``
normalised over all ``k`` chosen, held or not) to ``shared(x)``. What the
absent experts would add is left out; nothing stands in for the other
chips or their exchange. Dropless: the (row, expert) pairs that fall on
held experts are sorted by expert, every expert's rows laid out from a
multiple of the grouped product's row tile on, and go through
``jax.lax.ragged_dot`` in trips of whole tiles, as many trips as the rows
need (:func:`routed_experts`; tile and trip from the shapes:
:func:`routed_plan`), so no pair is ever dropped and the work follows the
pairs, not a capacity.

Parameters are made by ``param_init(name, shape, dtype)``, one call a
parameter in the order of ``named_parameters()``: a 10 GB model is built
on a 16 GB chip by making every array ONCE, in its serving dtype.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

import numpy as np

from .. import nn
from ..framework.tensor import Parameter, Tensor
from . import decoder_spec as DS

__all__ = ["AXK1Config", "AXK1ForCausalLM", "yarn_inv_freq",
           "yarn_attention_scale", "route_top_k", "routed_experts",
           "routed_plan"]

# Where the grouped products' time goes on a TPU (PERF.md 44, measured on
# a v5e): ``jax.lax.ragged_dot`` walks its rows in tiles of ``tm`` rows,
# ``tm`` the largest power of two up to 512 that divides the row count it
# is handed, and VISITS every (tile, group) pair that meet; a visit
# streams the group's whole ``[K, N]`` weights and computes the whole
# tile. A visit of the three products costs about ``1 + tm /
# TILE_BALANCE_ROWS`` times what its weights take to stream.
ROW_TILES = (8, 16, 32, 64, 128, 256)
TILE_BALANCE_ROWS = 400
REAL_ROW_SHARES = (0.5, 0.625, 0.75, 0.875, 1.0)
TRIP_BYTES = 80 << 20       # a trip's xs, gate/up, h, o and way back, at most
GATHER_ROWS_PER_CHOICE = 1024


def routed_plan(n: int, num_experts: int, rows: int, k: int, E: int,
                I: int, matrices: int = 3) -> Tuple[int, int, bool]:
    """``(T, M, by_gather)`` of :func:`routed_experts` from the shapes and
    nothing else: ``n`` held of ``num_experts`` experts, ``rows`` rows
    choosing ``k`` each, experts ``[E, I]`` of ``matrices`` matrices each
    — 3: gated, ``down(silu(gate x) * up x)``; 2: ungated, ``down(relu(up
    x)^2)`` (``E`` is the width the experts READ, the stream's or a
    latent's).

    ``T``, the rows an expert's group is padded to a multiple of, is the
    row tile that makes the walk cheapest: a held expert expects ``each =
    rows k / num_experts`` pairs (binomial: about normal with that
    variance), so ``tiles(T) = sum_j P(pairs > j T)`` tiles of ``T`` rows,
    each costing its weights' stream once and, per row, the FLOPs that do
    not hide behind it (``T / TILE_BALANCE_ROWS``) and the rows' way in
    and out (``xs``, two f32 products, ``h``, ``o``: ``12 E + 20 I`` bytes
    against ``6 E I`` of weights; an ungated expert has one f32 product
    less and a matrix less: ``12 E + 12 I`` against ``4 E I``). How many
    of ``rows`` are REAL cannot
    be seen from here (a ragged batch's pad rows, a block-generation
    slot's half-filled q block): the tile taken is the one whose cost is
    least far from the best tile's at every share of ``REAL_ROW_SHARES``.

    ``M``, the rows of a trip, is ``T`` times an ODD number — the
    compiler's tile is then ``T`` itself, so no tile holds two experts'
    rows (41.2's rule, a trip ends on a tile, is the case ``M = 128 x
    odd``) — the smallest that holds the rows a launch of real rows is
    expected to walk and a spare tile, within ``TRIP_BYTES`` of
    temporaries (half of them where the way back keeps a buffer of the
    layout). More rows take more trips.

    ``by_gather``: the outputs go back through a buffer of the whole
    layout and one gather of ``k`` rows a query row where the layout is
    long (every expert held: the 0/1 product's FLOPs grow with query rows
    x rows walked), through the 0/1 product a trip where it is short."""
    if matrices not in (2, 3):
        raise ValueError(f"an expert is 2 or 3 matrices, not {matrices}")
    gated = matrices == 3
    per_row = 1.0 / TILE_BALANCE_ROWS \
        + (12 * E + (20 if gated else 12) * I) / (2.0 * matrices * E * I)

    def tiles(T, each):
        """Tiles of ``T`` rows an expert that expects ``each`` pairs takes."""
        over = lambda v: 0.5 * math.erfc((v - each) / math.sqrt(2.0 * each))
        return 1.0 + sum(over(j * T) for j in range(1, -(-rows // T) + 1))

    full = max(rows * k / float(num_experts), 1e-9)
    cost = {f: {T: tiles(T, f * full) * (1.0 + T * per_row)
                for T in ROW_TILES} for f in REAL_ROW_SHARES}
    T = min(ROW_TILES, key=lambda T: max(
        cost[f][T] / min(cost[f].values()) for f in REAL_ROW_SHARES))
    walked = n * tiles(T, full) * T
    by_gather = walked > GATHER_ROWS_PER_CHOICE * k
    bound = TRIP_BYTES // (2 if by_gather else 1)
    # tiles a trip
    most = max(1, bound // ((8 * E + (10 if gated else 6) * I) * T))
    want = int(walked // T) + 2                            # and a spare
    odd = lambda v: v if v % 2 else v - 1
    return T, T * max(1, min(odd(most), odd(want + 1))), by_gather


def latent_lanes(kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """Lanes of a cached latent row: ``kv_lora_rank + qk_rope_head_dim``
    rounded up to whole 128-lane tiles (576 -> 640; the pad lanes are
    zeros and nothing reads them into a score)."""
    return -(-(kv_lora_rank + qk_rope_head_dim) // 128) * 128


def _default_rope_scaling() -> dict:
    return {"type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class AXK1Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=_default_rope_scaling)
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    # the share of an expert-parallel deployment this model holds:
    # experts lo .. hi - 1 of every expert layer (None = all of them)
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, int(self.n_routed_experts))
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range inside "
                f"[0, {self.n_routed_experts})")
        self.experts_held = (lo, hi)
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what this layer builds "
                             f"(n_shared_experts={self.n_shared_experts})")

    @property
    def latent_lanes(self) -> int:
        return latent_lanes(self.kv_lora_rank, self.qk_rope_head_dim)

    @classmethod
    def tiny(cls, **over):  # tests
        kw = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
            n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=128,
            rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 32})
        kw.update(over)
        return cls(**kw)


# ---------------------------------------------------------------------------
# rotary positions with YaRN (Peng et al. 2023), as the source's family
# computes them
# ---------------------------------------------------------------------------

def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """``[dim / 2]`` float32 rotary frequencies: per frequency a linear
    ramp between the interpolated (``f / factor``) and the unscaled
    frequency over the correction range of ``beta_fast`` / ``beta_slow``."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    base = float(theta)
    half = dim // 2
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(n_rot):
        return dim * math.log(orig / (n_rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp                     # 1: unscaled, 0: interpolated
    return (freq / factor * (1.0 - keep) + freq * keep).astype(np.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_attention_scale(cfg: AXK1Config) -> float:
    """``(nope + rope)^-1/2 * m^2`` with ``m`` from ``mscale_all_dim``."""
    s = cfg.rope_scaling
    m = _yarn_mscale(float(s["factor"]), float(s["mscale_all_dim"])) \
        if s.get("mscale_all_dim") else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def yarn_cos_sin_scale(scaling: dict) -> float:
    f = float(scaling["factor"])
    return _yarn_mscale(f, float(scaling["mscale"])) \
        / _yarn_mscale(f, float(scaling["mscale_all_dim"]))


def _rope(x, cos, sin):
    """Rotate the pairs ``(2i, 2i + 1)`` of the last axis by the row's
    angle: ``x [..., rows, (heads,) dim]`` against ``cos``/``sin`` ``[rows,
    dim / 2]`` broadcast over heads. float32 inside."""
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    xr, xi = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([xr * cos - xi * sin, xr * sin + xi * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rms_norm(x, w, eps, scale: float = 1.0):
    """RMSNorm in float32, times ``scale`` there too: rounded once."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * w.astype(jnp.float32)
    return (y if scale == 1.0 else y * scale).astype(x.dtype)


def _mm(x, w, scale: float = 1.0):
    """``x @ w`` in the weights' dtype, float32 accumulation on the MXU
    (times ``scale`` before the one rounding)."""
    import jax.numpy as jnp
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return (y if scale == 1.0 else y * scale).astype(x.dtype)


def _swiglu(x, gate, up, down, gate_scale: float = 1.0):
    """``down(silu(gate(x) * gate_scale) * up(x))``: operands in the
    weights' dtype, float32 accumulation, the product of the two branches
    taken in float32 and rounded once. Returns float32."""
    import jax
    import jax.numpy as jnp
    f32 = lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
    g = f32(x, gate)
    if gate_scale != 1.0:
        g = g * gate_scale
    h = (jax.nn.silu(g) * f32(x, up)).astype(x.dtype)
    return f32(h, down)


# ---------------------------------------------------------------------------
# the routed expert layer
# ---------------------------------------------------------------------------

def route_top_k(x, router_w, top_k: int, scale: float, norm: bool = True,
                scoring: str = "sigmoid", select_bias=None,
                eps: float = 0.0):
    """Scores, choice and weights of the router: ``x [Q, E]``, ``router_w
    [n_experts, E]`` -> ``(idx [Q, k] int32, w [Q, k] float32, scores [Q,
    n_experts] float32)``. ``scoring`` is ``"sigmoid"`` (A.X-K1) or
    ``"softmax"`` over all experts (SDAR). ``select_bias [n_experts]``
    (``topk_method`` ``noaux_tc``, MiMo-V2-Flash): the choice is the top-k
    of ``scores + select_bias``, the weights are the chosen experts'
    SCORES (None: the choice is the top-k of the scores, as it was).
    ``eps`` joins the sum the chosen scores are divided by (``norm``;
    LFM2's 1e-6, 0 elsewhere). The scores are float32:
    activations and router weights are exact in bfloat16, every product
    of two of them is exact in float32, and the MXU accumulates in
    float32, so this IS the float32 score up to the order of the sum."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(x, router_w.T, preferred_element_type=jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"router scoring {scoring!r}: sigmoid or softmax")
    if select_bias is None:
        top, idx = jax.lax.top_k(scores, int(top_k))
    else:
        _, idx = jax.lax.top_k(
            scores + select_bias.astype(jnp.float32)[None, :], int(top_k))
        top = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        total = jnp.sum(top, axis=-1, keepdims=True)
        top = top / (total + jnp.float32(eps) if eps else total)
    return idx.astype(jnp.int32), top * jnp.float32(scale), scores


def routed_experts(x, valid, idx, w, experts, held, num_experts: int):
    """The held experts' part of the routed sum, dropless.

    ``x [Q, E]``; ``valid [Q]`` bool (pad rows of a ragged batch are not
    routed); ``idx``/``w [Q, k]`` from :func:`route_top_k`, which chose
    among ``num_experts``; ``experts`` the ``n = hi - lo`` held experts,
    whose FORM is read from what is handed over: three matrices ``(gate
    [n, E, I], up [n, E, I], down [n, I, E])`` are GATED experts,
    ``down(silu(gate x) * up x)`` (every family until Nemotron-H); two,
    ``(up [n, E, I], down [n, I, E])``, are UNGATED ones, ``down(relu(up
    x)^2)`` (``mlp_hidden_act: relu2``). ``E`` is whatever width ``x``
    has: the stream's, or a latent's the LAYER projected ``x`` into and
    will project ``y`` out of (LatentMoE: both projections are the
    layer's, outside the grouped products). ``held = (lo, hi)``.
    Returns ``(y [Q, E] float32, (pairs, experts_hit, rows, rows_walked,
    zero_pairs))`` with ``y = sum over the row's chosen experts that are
    held of w_e expert_e(x)`` and the ``decoder_spec.ROUTED_COUNTERS``
    int32 counters: the first three of REAL rows only, the fourth the
    rows the grouped products were handed (real + pad), the fifth — pairs
    on identity experts — 0 here: a layer whose router has such experts
    counts them where it adds their term (``models/longcat.py``).

    The layout (:func:`routed_plan`: ``T``, ``M`` and the combine, from
    the shapes alone): pairs on held experts are sorted by expert
    (stable, so by row inside an expert) and every expert's rows BEGIN ON
    A MULTIPLE OF ``T``, its group padded to whole tiles with zero rows
    (``silu(0) * 0 = relu(0)^2 = 0`` and a zero row of ``down`` is 0: the
    same sum, for either form — what the padded layout rests on: an
    activation with ``f(0) != 0`` would need its pad rows masked);
    an expert without a pair gets no tile. Trip ``c`` takes rows ``[c M,
    (c + 1) M)`` of that layout, ``M = T x odd``, so the grouped products
    walk it in tiles of ``T`` and no tile holds two experts' rows: an
    expert's weights are streamed once a tile of its own, whichever trip
    the tile falls in. ``ceil(rows walked / M)`` trips run. A trip's
    outputs go back to the query rows either through a 0/1 row-selection
    product on the MXU (a short layout: a scatter-add of ``M`` rows of
    ``E`` lanes is the slow way to the same sum) or, where the layout is
    long, into a buffer of the whole layout from which each query row
    gathers its ``k`` rows once, after the last trip."""
    import jax
    import jax.numpy as jnp
    if len(experts) not in (2, 3):
        raise ValueError(
            f"an expert is (gate, up, down) or (up, down): got "
            f"{len(experts)} arrays")
    up, down = experts[-2:]
    gate = experts[0] if len(experts) == 3 else None
    lo, hi = held
    n = hi - lo
    Q, k = idx.shape
    P = Q * k
    T, M, by_gather = routed_plan(n, int(num_experts), Q, k, x.shape[1],
                                  up.shape[2], len(experts))
    i32 = jnp.int32
    on = (idx >= lo) & (idx < hi) & valid[:, None]            # [Q, k]
    flat_e = jnp.where(on, idx - lo, n).reshape(-1)           # n = "not here"
    order = jnp.argsort(flat_e, stable=True).astype(i32)
    is_e = flat_e[:, None] == jnp.arange(n, dtype=i32)        # [P, n]
    counts = jnp.sum(is_e, axis=0, dtype=i32)                 # [n]
    ends = jnp.cumsum(counts)
    padded = -(-counts // T) * T
    pend = jnp.cumsum(padded)                                 # [n]
    pstart = pend - padded
    plive = pstart + counts             # an expert's real rows end here
    shift = pstart - (ends - counts)    # layout position - sorted position
    pairs, walked = ends[-1], pend[-1]
    iota_m = jnp.arange(M, dtype=i32)

    def trip_rows(c):
        """Of trip ``c``: the layout row it starts at, its group sizes,
        which of its ``M`` rows hold a pair, and those pairs."""
        a = jnp.asarray(c, i32) * M
        sizes = jnp.clip(pend, a, a + M) - jnp.clip(pstart, a, a + M)
        r = (a + iota_m)[:, None]
        mine = (r >= pstart) & (r < plive)                    # [M, n]
        live = jnp.any(mine, axis=1)
        at = a + iota_m - jnp.sum(jnp.where(mine, shift, 0), axis=1)
        sel = order[jnp.where(live, at, 0)]
        return a, sizes, live, sel

    def products(xs, sizes):
        rd = lambda l, r: jax.lax.ragged_dot(
            l, r, sizes, preferred_element_type=jnp.float32)
        if gate is not None:
            h = jax.nn.silu(rd(xs, gate)) * rd(xs, up)
        else:
            h = jnp.square(jax.nn.relu(rd(xs, up)))
        return rd(h.astype(x.dtype), down)                    # [M, E] f32

    trips = (walked + M - 1) // M
    if by_gather:
        # where a pair's row lies in the layout: its place in the sorted
        # order (the inverse of ``order``) moved by its expert's shift
        pos = jnp.argsort(order).astype(i32) \
            + jnp.sum(jnp.where(is_e, shift, 0), axis=1)              # [P]
        pos = jnp.where(on.reshape(-1), pos, 0)

        def trip(c, buf):
            a, sizes, live, sel = trip_rows(c)
            xs = jnp.where(live[:, None], x[sel // k], 0)
            o = products(xs, sizes).astype(x.dtype)
            return jax.lax.dynamic_update_slice(buf, o, (a, i32(0)))

        # the longest layout: every pair held, a tile less a row of pad a
        # group; whole trips of it. Never cleared: the rows read below
        # are rows a trip wrote, or masked
        room = -(-((P + min(n, P) * (T - 1)) // T * T) // M) * M
        buf = jax.lax.fori_loop(
            i32(0), trips, trip, jax.lax.empty((room, x.shape[1]), x.dtype))
        got = buf[pos].reshape(Q, k, -1).astype(jnp.float32)
        y = jnp.sum(jnp.where(on[:, :, None], got * w[:, :, None], 0.0),
                    axis=1)
    else:
        flat_w = jnp.where(on, w, 0.0).reshape(-1)
        rows_iota = jnp.arange(Q, dtype=i32)[:, None]

        def trip(c, y):
            _, sizes, live, sel = trip_rows(c)
            rows = sel // k
            xs = jnp.where(live[:, None], x[rows], 0)
            # a row that holds no pair belongs to no sum: whatever the
            # grouped product left in it is zeroed, not weighted
            o = jnp.where(live[:, None],
                          products(xs, sizes) * flat_w[sel][:, None], 0.0)
            pick = (rows_iota == rows[None, :]) & live[None, :]   # [Q, M] 0/1
            return y + jnp.dot(pick.astype(x.dtype), o.astype(x.dtype),
                               preferred_element_type=jnp.float32)

        y = jax.lax.fori_loop(i32(0), trips, trip,
                              jnp.zeros(x.shape, jnp.float32))
    counters = (pairs, jnp.sum(counts > 0, dtype=i32),
                jnp.sum(valid, dtype=i32), walked, i32(0))
    return y, counters


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _params(make, prefix):
    """``p(name, shape)`` -> the Parameter the model's ``param_init`` makes
    for ``prefix + name`` (a layer calls it while it is built and keeps
    nothing of it)."""
    return lambda name, shape: Parameter(make(prefix + name, tuple(shape)))


def _param_maker(dtype, param_init: Optional[Callable],
                 initializer_range: float):
    """``make(name, shape)`` for a model built ONE parameter at a time in
    its serving dtype: ``param_init(name, shape, dtype)`` if given (its
    result checked), else norm gains of 1 and ``N(0, initializer_range^2)``
    elsewhere."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    if param_init is None:
        keys = iter(jax.random.split(jax.random.PRNGKey(0), 4096))

        def param_init(name, shape, dtype):
            if name.endswith("norm"):
                return jnp.ones(shape, dtype)
            return (initializer_range * jax.random.normal(
                next(keys), shape, jnp.float32)).astype(dtype)

    def make(name, shape):
        arr = param_init(name, shape, dt)
        if tuple(arr.shape) != tuple(shape) or arr.dtype != dt:
            raise ValueError(
                f"param_init({name!r}) gave {arr.dtype}{tuple(arr.shape)}"
                f", the model needs {dt}{tuple(shape)}")
        return arr

    return make


class AXK1Attention(nn.Layer):
    """MLA as the module doc has it. Two things a sibling switches on
    (``models/longcat.py``): ``q_scale`` multiplies ``q = c_q W_qb`` and
    ``kv_scale`` the normed ``c_kv`` — so the STORED row is ``[kv_scale
    c_kv | k_pe]`` (``k_pe`` takes neither) and the absorbed and the
    naive form read it as they read any ``c_kv`` — and a
    ``cfg.rope_scaling`` of ``None`` is plain rotary positions: no
    YaRN ramp, cos and sin unscaled, ``scale = (nope + rope)^-1/2``."""

    def __init__(self, cfg, make, prefix, q_scale: float = 1.0,
                 kv_scale: float = 1.0):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        self.q_scale, self.kv_scale = float(q_scale), float(kv_scale)
        E, H = cfg.hidden_size, cfg.num_attention_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.wq_a = p("wq_a", (E, cfg.q_lora_rank))
        self.q_norm = p("q_norm", (cfg.q_lora_rank,))
        self.wq_b = p("wq_b", (cfg.q_lora_rank, H * qk))
        self.wkv_a = p(
            "wkv_a", (E, cfg.kv_lora_rank + cfg.qk_rope_head_dim))
        self.kv_norm = p("kv_norm", (cfg.kv_lora_rank,))
        self.wkv_b = p(
            "wkv_b", (cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim)))
        self.wo = p("wo", (H * cfg.v_head_dim, E))
        if cfg.rope_scaling is None:
            rope = cfg.qk_rope_head_dim
            self._inv_freq = (1.0 / float(cfg.rope_theta) ** (
                np.arange(0, rope, 2, dtype=np.float64) / rope)
                ).astype(np.float32)
            self._cs_scale = 1.0
            self.scale = qk ** -0.5
        else:
            self._inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim,
                                           cfg.rope_theta, cfg.rope_scaling)
            self._cs_scale = yarn_cos_sin_scale(cfg.rope_scaling)
            self.scale = yarn_attention_scale(cfg)

    def _cos_sin(self, positions):
        import jax.numpy as jnp
        ang = positions.astype(jnp.float32)[:, None] \
            * jnp.asarray(self._inv_freq)[None, :]
        return jnp.cos(ang) * self._cs_scale, jnp.sin(ang) * self._cs_scale

    def _project(self, h, positions):
        """``h [Q, E]`` (normed) -> ``q_nope [Q, H, nope]``, ``q_pe [Q, H,
        rope]`` (rotated), ``c_kv [Q, rank]`` (normed), ``k_pe [Q, rope]``
        (rotated)."""
        cfg = self.cfg
        H = cfg.num_attention_heads
        cos, sin = self._cos_sin(positions)
        c_q = _rms_norm(_mm(h, self.wq_a._data), self.q_norm._data,
                        cfg.rms_norm_eps)
        q = _mm(c_q, self.wq_b._data, self.q_scale).reshape(
            h.shape[0], H, -1)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_pe = _rope(q[..., cfg.qk_nope_head_dim:], cos[:, None], sin[:, None])
        kv = _mm(h, self.wkv_a._data)
        c_kv = _rms_norm(kv[:, :cfg.kv_lora_rank], self.kv_norm._data,
                         cfg.rms_norm_eps, self.kv_scale)
        k_pe = _rope(kv[:, cfg.kv_lora_rank:], cos, sin)
        return q_nope, q_pe, c_kv, k_pe

    def _w_uk_uv(self):
        cfg = self.cfg
        w = self.wkv_b._data.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def absorbed_in(self, h, positions):
        """What the serving step hands the kernel and the cache: ``q [Q,
        H, lanes]`` = ``[q_lat | q_pe | 0]`` and the row ``[Q, lanes]`` =
        ``[c_kv | k_pe | 0]``."""
        import jax.numpy as jnp
        cfg = self.cfg
        q_nope, q_pe, c_kv, k_pe = self._project(h, positions)
        w_uk, _ = self._w_uk_uv()                             # [rank,H,nope]
        q_lat = jnp.einsum("qhd,chd->qhc", q_nope, w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(h.dtype)
        pad = cfg.latent_lanes - cfg.kv_lora_rank - cfg.qk_rope_head_dim
        Q, H = q_pe.shape[:2]
        q = jnp.concatenate(
            [q_lat, q_pe, jnp.zeros((Q, H, pad), h.dtype)], axis=-1)
        row = jnp.concatenate(
            [c_kv, k_pe, jnp.zeros((Q, pad), h.dtype)], axis=-1)
        return q, row

    def absorbed_out(self, o_lat):
        """``o_lat [Q, H, rank]`` -> ``[Q, E]``: ``W_UV`` per head, then
        ``W_o``."""
        import jax.numpy as jnp
        _, w_uv = self._w_uk_uv()                             # [rank,H,v]
        o = jnp.einsum("qhc,chd->qhd", o_lat, w_uv,
                       preferred_element_type=jnp.float32).astype(o_lat.dtype)
        return _mm(o.reshape(o.shape[0], -1), self.wo._data)

    def naive(self, h, positions):
        """Causal attention of one whole sequence ``h [S, E]`` in the
        decompressed form (no cache): every head's K and V are made from
        ``c_kv``."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        q_nope, q_pe, c_kv, k_pe = self._project(h, positions)
        w_uk, w_uv = self._w_uk_uv()
        k_nope = jnp.einsum("sc,chd->shd", c_kv, w_uk,
                            preferred_element_type=jnp.float32)
        v = jnp.einsum("sc,chd->shd", c_kv, w_uv,
                       preferred_element_type=jnp.float32)
        s = (jnp.einsum("qhd,khd->hqk", q_nope.astype(jnp.float32), k_nope)
             + jnp.einsum("qhd,kd->hqk", q_pe.astype(jnp.float32),
                          k_pe.astype(jnp.float32))) * self.scale
        S = h.shape[0]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).astype(h.dtype)
        return _mm(o.reshape(S, -1), self.wo._data)


class AXK1DenseFFN(nn.Layer):
    kind = DS.DENSE

    def __init__(self, cfg, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        E, I = cfg.hidden_size, cfg.intermediate_size
        self.gate = p("gate", (E, I))
        self.up = p("up", (E, I))
        self.down = p("down", (I, E))

    def apply(self, x, valid):
        with DS.section(DS.MLP):
            return _swiglu(x, self.gate._data, self.up._data,
                           self.down._data).astype(x.dtype), None


class AXK1RoutedFFN(nn.Layer):
    kind = DS.ROUTED

    def __init__(self, cfg, make, prefix):
        super().__init__()
        p = _params(make, prefix)
        self.cfg = cfg
        E, I = cfg.hidden_size, cfg.moe_intermediate_size
        n = cfg.experts_held[1] - cfg.experts_held[0]
        self.router = p("router", (cfg.n_routed_experts, E))
        self.shared_gate = p("shared_gate", (E, I))
        self.shared_up = p("shared_up", (E, I))
        self.shared_down = p("shared_down", (I, E))
        self.experts_gate = p("experts_gate", (n, E, I))
        self.experts_up = p("experts_up", (n, E, I))
        self.experts_down = p("experts_down", (n, I, E))

    def apply(self, x, valid):
        """``x [Q, E]`` -> ``(shared(x) + the held experts' part, counters)``."""
        cfg = self.cfg
        # the whole expert layer is found by ``moe_experts``
        # (benchmark/layer_metrics/moe_*.py): router and shared expert
        # nest inside it
        with DS.section(DS.MOE_SCOPE):
            with DS.section(DS.ROUTER):
                idx, w, _ = route_top_k(
                    x, self.router._data, cfg.num_experts_per_tok,
                    cfg.routed_scaling_factor, cfg.norm_topk_prob)
            y, counters = routed_experts(
                x, valid, idx, w,
                (self.experts_gate._data, self.experts_up._data,
                 self.experts_down._data), cfg.experts_held,
                cfg.n_routed_experts)
            with DS.section(DS.SHARED_EXPERT):
                shared = _swiglu(x, self.shared_gate._data,
                                 self.shared_up._data,
                                 self.shared_down._data)
            out = (shared + y).astype(x.dtype)
        return out, counters


class AXK1Layer(nn.Layer):
    def __init__(self, cfg: AXK1Config, index: int, make):
        super().__init__()
        prefix = f"layers.{index}."
        p = _params(make, prefix)
        self.cfg = cfg
        self.attn_norm = p("attn_norm", (cfg.hidden_size,))
        self.attn = AXK1Attention(cfg, make, prefix + "attn.")
        self.ffn_norm = p("ffn_norm", (cfg.hidden_size,))
        ffn = AXK1DenseFFN if index < cfg.first_k_dense_replace \
            else AXK1RoutedFFN
        self.ffn = ffn(cfg, make, prefix + "ffn.")

    def _ffn(self, x, valid):
        with DS.section(DS.NORM):
            h = _rms_norm(x, self.ffn_norm._data, self.cfg.rms_norm_eps)
        y, counters = self.ffn.apply(h, valid)
        with DS.section(DS.MLP):          # the add that closes the layer
            return x + y, counters

    # -- the decoder spec's layer surface (x is a Tensor [1, Q, E]) --------
    def attn_in(self, x, positions):
        with DS.section(DS.NORM):
            h = _rms_norm(x._data[0], self.attn_norm._data,
                          self.cfg.rms_norm_eps)
        with DS.section(DS.QKV):
            return self.attn.absorbed_in(h, positions)

    def attn_out(self, x, o_lat, row_valid):
        with DS.section(DS.O_PROJ):
            x = x._data[0] + self.attn.absorbed_out(o_lat)
        y, counters = self._ffn(x, row_valid)
        with DS.section(DS.MLP):
            return Tensor(y[None], stop_gradient=True), counters

    # -- no cache: one whole sequence [S, E] -------------------------------
    def full(self, x, positions):
        import jax.numpy as jnp
        h = _rms_norm(x, self.attn_norm._data, self.cfg.rms_norm_eps)
        x = x + self.attn.naive(h, positions)
        return self._ffn(x, jnp.ones(x.shape[0], bool))[0]


class AXK1ForCausalLM(nn.Layer):
    """A.X-K1 with its untied head. ``forward(input_ids [B, S])`` ->
    float32 logits ``[B, S, V]`` (no cache, naive attention);
    ``serving_decoder()`` is what ``GenerationEngine`` consumes."""

    def __init__(self, cfg: AXK1Config, dtype="float32",
                 param_init: Optional[Callable] = None):
        super().__init__()
        self.cfg = cfg
        make = _param_maker(dtype, param_init, cfg.initializer_range)
        self.embed = Parameter(make("embed", (cfg.vocab_size, cfg.hidden_size)))
        self.layers = nn.LayerList(
            [AXK1Layer(cfg, i, make) for i in range(cfg.num_hidden_layers)])
        self.norm = Parameter(make("norm", (cfg.hidden_size,)))
        self.lm_head = Parameter(make("lm_head",
                                      (cfg.hidden_size, cfg.vocab_size)))
        cache = DS.CacheSpec(rows=1, lanes=cfg.latent_lanes,
                             v_aliases_k=True, v_lanes=cfg.kv_lora_rank)
        self.spec = DS.DecoderSpec(
            layers=tuple(DS.LayerSpec(DS.LATENT, cache, layer.ffn.kind)
                         for layer in self.layers),
            vocab_size=cfg.vocab_size,
            max_positions=cfg.max_position_embeddings)

    def serving_decoder(self):
        return self

    @property
    def attention_scale(self) -> float:
        return self.layers[0].attn.scale

    # -- the decoder spec's model surface ----------------------------------
    def embed_tokens(self, token_ids, positions):
        return Tensor(self.embed._data[token_ids][None], stop_gradient=True)

    def final_norm(self, x):
        return Tensor(_rms_norm(x._data, self.norm._data,
                                self.cfg.rms_norm_eps), stop_gradient=True)

    def logits(self, hidden):
        import jax.numpy as jnp
        return Tensor(jnp.dot(hidden._data, self.lm_head._data,
                              preferred_element_type=jnp.float32),
                      stop_gradient=True)

    def forward(self, input_ids):
        import jax.numpy as jnp
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        out = []
        for row in ids:
            x = self.embed._data[row]
            for layer in self.layers:
                x = layer.full(x, pos)
            out.append(self.logits(self.final_norm(
                Tensor(x, stop_gradient=True)))._data)
        return Tensor(jnp.stack(out), stop_gradient=True)
