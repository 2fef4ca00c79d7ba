"""The decoder spec: what the fused serving stack needs to know of a
decoder-only model, and nothing else (ROADMAP D2).

``GenerationEngine``, the fused
tower (``models/generation.py:_fused_tower``), ``PagedKVPool`` and the
engine's memory planner consume THIS, not ``model.gpt``: a model is
served by the fused paged path if ``serving_decoder()`` returns an
object with

* ``spec`` — a :class:`DecoderSpec`: per layer the MIXER — the attention
  kind with its cache descriptor, its window and its sinks, a recurrent
  state beside it, a state ALONE, or none — and the FFN kind (or none),
  plus the vocabulary and the most positions the model takes;
* ``embed_tokens(token_ids, positions)`` -> ``Tensor [1, Q, E]`` (positions
  that the model adds at the embedding, as GPT does, are added here;
  a rotary model ignores them here and reads them in ``attn_in``);
* ``layers`` — per layer an object with
  ``attn_in(x, positions) -> (q, cache_rows)`` (what the kernel reads and
  what is written to the layer's cache, one entry a row) and
  ``attn_out(x, a, row_valid) -> (x, counters)`` (output projection,
  residual, FFN; ``row_valid [Q]`` marks the rows of the ragged batch that
  are real, which a routed FFN must not route; ``counters`` is ``None``
  or the routed layer's ``ROUTED_COUNTERS`` int32 scalars); a layer whose
  spec OPENS A SHORTCUT returns ``(x, counters, carried)``, ``carried [Q,
  E]`` the sum of its routed experts, which the TOWER keeps and adds to
  the stream at the end of the layer the shortcut closes at; a layer whose
  spec has a recurrent STATE also
  ``mixer(x, layout, state, index) -> (s, state)``
  (the rows in, the rows' sequence layout — ``ops/ssm.py:SeqLayout`` —
  and the slots' state arrays in, the branch's output and the new state
  out; ``index`` is the layer's place in the state arrays), and its
  ``attn_out`` takes the branch's output as a fourth argument; a layer
  whose mixer is a state ALONE has no ``attn_in`` at all, and its
  ``attn_out`` is handed ``None`` for the attention's output; a layer
  WITHOUT A MIXER has neither, and ``ffn_out(x, row_valid) -> (x,
  counters)`` (norm, FFN, residual) is all of it;
* ``final_norm(x)`` and ``logits(hidden)``.

Two attention kinds, two FFN kinds and a routed shortcut around dense
ones, three mixers (attention with its cache; attention with its cache
and a recurrent state beside it; a recurrent state alone), a layer that
is a mixer WITHOUT an FFN or an FFN WITHOUT a mixer, one generation
rule, eight callers (``models/gpt.py``, ``models/axk1.py``,
``models/sdar.py``, ``models/mimo.py``, ``models/falcon_h1.py``,
``models/lfm2.py``, ``models/longcat.py``, ``models/nemotron_h.py``).
Nothing else is described here.

**Half a layer** (Nemotron-H: ``hybrid_override_pattern`` makes every
published block ONE of a Mamba-2 mixer, attention, or an expert FFN, each
``x + f(norm(x))``). ``LayerSpec.ffn`` ``"none"``: the layer's
``attn_out`` ends with the mixer's output projection and its residual,
routes nothing and returns no counters. ``attention``, ``cache`` and
``state`` all ``None``: the layer has no mixer, holds no cache and no
state, and the tower calls its ``ffn_out`` and nothing else — no
``attn_in``, no ``mixer``, no cache write, no kernel, no ``attn_out``.
One or the other: a layer with neither computes nothing and is refused.
Neither form is built under block generation or around a routed
shortcut.

**A routed shortcut** (``LayerSpec.shortcut`` n > 0; LongCat-Flash's
shortcut-connected experts, n = 1): the layer's own FFN is dense, and
BESIDE it routed experts read the layer's post-attention norm; their sum
is no part of this layer's output — it joins the stream at the END of
layer ``li + n``, after that layer's own FFN. So a second value crosses
layer boundaries beside ``x``: the spec says where it opens and closes,
``__post_init__`` holds it to one open shortcut at a time that closes
inside the model, and the tower (``models/generation.py:_fused_tower``)
alone carries it — a layer object keeps nothing between calls. Such a
layer ROUTES (``LayerSpec.routes``: its launch counters ride the result
as a routed layer's do) though its ``ffn`` is dense.

**Recurrent state.** What a sequence leaves behind in a layer is its
cache entries, which grow with the context, or a STATE of fixed size —
:class:`StateSpec`: the parts one sequence holds (a convolution's tail, a
recurrence's state), their shapes and dtypes — or both: a layer may run
a state-space mixer BESIDE its attention (Falcon-H1: every layer), or
have the state-holding mixer INSTEAD of attention (LFM2: a gated short
convolution is three layers in four; ``attention`` and ``cache`` are then
``None`` and the layer has no query heads). The spec derives the layers
that have a state (``state_layers``), all of one descriptor, and the
paged pool holds one array a part, ``[those layers, slots + 1, *shape]``,
a row a slot (``serving/paging.py``). A state has no snapshot a block:
nothing that rolls a position back or reuses a prefix composes with it
(``serving/engine.py:_refuse_with_state``).

**Cache-less layers.** The layers that hold a cache (``cache_layers``)
are a SUBSET of the layers: the cache groups below are formed of them
only, so the pool's block arrays, a block's bytes and a token's bytes
count them and not ``len(layers)``; ``DecoderSpec.attention`` and
``.cache`` describe the FIRST of them; ``layer_group`` of a cache-less
layer is an error. The tower runs such a layer's ``mixer`` and
``attn_out`` and nothing else of a layer: no ``attn_in``, no cache
write, no attention kernel (``models/generation.py:_fused_tower``). A
spec with no cache-bearing layer at all is refused: positions, page
tables and the launch's row layout are the cache's.

**Cache groups.** The layers of one model need not share a cache
descriptor: the spec derives its CACHE GROUPS — the layers with equal
``(attention, cache, window)``, in order of first appearance — and the
paged pool holds one block array and one page table a request FOR EACH
(``serving/paging.py``). A layer's ``window`` W > 0 says its rows see the
last W positions only (a row at ``p`` sees ``[p - W + 1, p]``): the
group's blocks wholly behind the window are freed as a sequence moves
on. ``sinks`` says the layer's softmax has a learned logit a query head
in its denominator (the layer object's ``sinks`` attribute holds the
``[H]`` float32 array). GPT-2, A.X-K1 and SDAR have one group, window 0
and no sinks.

**KV heads apart from query heads.** ``CacheSpec.rows`` is the number of
KV heads a token leaves in a ``full`` cache; ``attn_in`` may return more
query heads than that (a multiple: grouped-query attention), and the
kernel folds a KV head's group of query heads into the rows of its
products (``ops/ragged_paged_attention.py``). ``LayerSpec.query_heads``
says how many (0: one a cache row), so that the engine can tell how wide
the kernel's grid steps are without asking the model.

**The generation rule** (:class:`GenerationRule`): ``block_length`` 1 is
one token a sequence a step, the next token from the last row's logits.
``block_length`` B > 1 is generation by diffusion over blocks: a
sequence's next B positions are generated together, a row at position
``p`` attends to every column up to the end of ``p``'s block, a DENOISING
pass runs the block's B rows (fixed ids, ``mask_token_id`` elsewhere),
fixes the ``B / denoising_steps`` most confident unfixed positions and
keeps nothing in the cache, and once nothing is unfixed a COMMIT pass
runs the block's final tokens, whose K/V the cache keeps.

**Sections.** :data:`SECTIONS` names the parts of a launch's device work
(``embed``, ``norm``, ``qkv`` … ``head``, ``sample``); the tower and the
models put their ops under them, layer by layer, and the profiler
reads a launch's device time by them (``profiler/xplane.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Optional, Tuple

__all__ = ["CacheSpec", "StateSpec", "LayerSpec", "DecoderSpec",
           "GenerationRule", "CacheGroup", "serving_decoder", "FULL",
           "LATENT", "DENSE", "ROUTED", "NO_FFN", "ROUTED_COUNTERS",
           "SECTIONS",
           "section", "layer_scope", "section_of"]

FULL, LATENT = "full", "latent"        # attention kinds
DENSE, ROUTED = "dense", "routed"      # FFN kinds
NO_FFN = "none"                        # the layer is its mixer alone
# the int32 scalars a routed layer's ``attn_out`` returns, summed over the
# layers into a launch's result after its sentinel: pairs on held experts,
# held experts hit, real rows routed, rows the grouped products walked,
# pairs that fell on identity ("zero-computation") experts — of a router
# wider than the experts with weights (LongCat-Flash); 0 elsewhere
ROUTED_COUNTERS = 5

# The SECTIONS of a launch: the one vocabulary of ``jax.named_scope``s the
# step programs put their device work under, so that every op of a launch
# says in a profiler trace (its ``tf_op``) which part of the model it is.
# An op belongs to the INNERMOST word of its scope path (:func:`section_of`):
# the router and the shared expert nest inside ``moe_experts``, whose name
# a reader may still find the whole expert layer by. Names only: a section
# changes no operand, no output and no instruction of the compiled program.
EMBED = "embed"                  # token ids (the block state) to rows
NORM = "norm"                    # the layer norms and the final one
QKV = "qkv"                      # q/k/v or latent projections, rotary
CACHE_WRITE = "cache_write"      # the rows' entries into the paged pool
ATTENTION = "attention"          # the paged attention kernel
O_PROJ = "o_proj"                # output projection and its residual
ROUTER = "router"                # expert scores and the choice
MOE_SCOPE = "moe_experts"        # the routed experts (router, shared inside)
SHARED_EXPERT = "shared_expert"
ZERO_EXPERTS = "zero_experts"    # identity experts: their weights' sum x u
MLP = "mlp"                      # a dense FFN; the add that closes a layer
SHORTCUT = "shortcut"            # the add that closes a routed shortcut
# the projections of a routed layer whose experts run in a LATENT narrower
# than the stream (Nemotron-H's LatentMoE): into it before the grouped
# products, out of it after them
LATENT_PROJ = "latent_proj"
HEAD = "head"                    # the logits of the rows that are read
SAMPLE = "sample"                # the pick of one token a slot
UNMASK_SCOPE = "unmask"          # a block pass's head, confidence, choice
# a state-holding mixer's parts. The prefix is historical (the first such
# mixer was a state-space one): a gated short convolution's projections
# and gates are ``ssm_proj`` and its convolution ``ssm_conv`` too
SSM_PROJ = "ssm_proj"            # a mixer's in/out projection, gates, norm
SSM_CONV = "ssm_conv"            # its causal convolution and the tail
SSM_SCAN = "ssm_scan"            # its recurrence and the D skip
SECTIONS = (EMBED, NORM, QKV, CACHE_WRITE, ATTENTION, O_PROJ, ROUTER,
            MOE_SCOPE, SHARED_EXPERT, MLP, HEAD, SAMPLE, UNMASK_SCOPE,
            SSM_PROJ, SSM_CONV, SSM_SCAN, ZERO_EXPERTS, SHORTCUT,
            LATENT_PROJ)


def section(name: str):
    """The ``jax.named_scope`` of one of :data:`SECTIONS`."""
    import jax
    if name not in SECTIONS:
        raise ValueError(f"{name!r} is no section: {SECTIONS}")
    return jax.named_scope(name)


def layer_scope(index: int):
    """The scope the tower's loop puts layer ``index``'s sections under."""
    import jax
    return jax.named_scope(f"layer{int(index)}")


def section_of(op_name: str) -> Optional[str]:
    """The section an op traced under the scope path ``op_name``
    (``jit(fused_step_q512_t64)/layer3/moe_experts/router/dot_general``)
    belongs to: the innermost word of the vocabulary in it, or None."""
    for part in reversed(op_name.split("/")):
        if part in SECTIONS:
            return part
    return None


@dataclass(frozen=True)
class CacheSpec:
    """What one token holds in one layer's paged cache: ``rows`` rows of
    ``lanes`` values (a block of the pool is ``[rows, block_size,
    lanes]``). ``full``: one row a KV head, K in the FIRST ``k_lanes``
    lanes and V in the LAST ``v_lanes`` (both 0: ``lanes / 2`` each, K in
    ``[0, Dh)`` and V in ``[Dh, 2 * Dh)``; stated apart where a K head is
    wider than a V head, and ``lanes`` may then hold padding between the
    two so that each side is whole 128-lane tiles: 192 | 128 is stored
    384 wide, V from lane 256). ``latent``: ONE row for every head, and V
    is the first ``v_lanes`` lanes of the same stored row
    (``v_aliases_k``)."""
    rows: int
    lanes: int
    v_aliases_k: bool = False
    v_lanes: int = 0
    k_lanes: int = 0

    def __post_init__(self):
        if self.k_lanes and not self.v_aliases_k and not (
                0 < self.v_lanes and self.k_lanes + self.v_lanes
                <= self.lanes):
            raise ValueError(
                f"K lanes {self.k_lanes} and V lanes {self.v_lanes} do not "
                f"fit a stored row of {self.lanes} lanes")

    @property
    def kv_lanes(self):
        """``(K lanes, V lanes)`` of a ``full`` row."""
        if self.k_lanes:
            return self.k_lanes, self.v_lanes
        return self.lanes // 2, self.lanes // 2


@dataclass(frozen=True)
class StateSpec:
    """What ONE sequence holds in one layer's recurrent state, whatever
    its context's length: ``parts``, each ``(name, shape, dtype)`` — a
    Mamba-2 mixer's are the convolution's tail ``("conv", (d_conv - 1,
    channels), ...)`` and the recurrence's state ``("ssm", (heads, P,
    N), ...)``."""
    parts: Tuple[Tuple[str, Tuple[int, ...], str], ...]

    def __post_init__(self):
        if not self.parts:
            raise ValueError("a state descriptor needs at least one part")
        for name, shape, _ in self.parts:
            if not shape or min(shape) < 1:
                raise ValueError(f"state part {name!r}: bad shape {shape}")

    @property
    def nbytes(self) -> int:
        """Bytes one sequence's state takes in one layer."""
        import numpy as np
        return sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
                   for _, shape, dtype in self.parts)


@dataclass(frozen=True)
class LayerSpec:
    """One layer's mixer and FFN. ``attention`` and ``cache`` both
    ``None``: the mixer is the ``state`` alone, or — ``state`` ``None``
    too — the layer has no mixer and is its FFN alone; ``ffn``
    :data:`NO_FFN`: the layer is its mixer alone (module doc)."""
    attention: Optional[str]
    cache: Optional[CacheSpec]
    ffn: str
    window: int = 0          # 0: a row sees all of the context
    sinks: bool = False      # a learned logit a query head in the softmax
    query_heads: int = 0     # 0: as many as the cache's rows (KV heads)
    state: Optional[StateSpec] = None   # beside the cache, or alone
    # n > 0: routed experts BESIDE the dense FFN read this layer's
    # post-attention norm, and their sum joins the stream at the end of
    # layer ``li + n`` (module doc)
    shortcut: int = 0

    @property
    def routes(self) -> bool:
        """The layer has a router: its FFN is routed, or it opens a
        routed shortcut around a dense one."""
        return self.ffn == ROUTED or self.shortcut > 0

    @property
    def has_mixer(self) -> bool:
        """The layer has attention, a recurrent state, or both."""
        return self.attention is not None or self.state is not None

    def __post_init__(self):
        if self.ffn not in (DENSE, ROUTED, NO_FFN):
            raise ValueError(f"FFN kind {self.ffn!r}: the fused path knows "
                             f"{DENSE!r}, {ROUTED!r} and {NO_FFN!r} (the "
                             f"layer is its mixer alone)")
        if self.shortcut < 0:
            raise ValueError(f"shortcut {self.shortcut} must be >= 0")
        if self.shortcut and self.ffn == ROUTED:
            raise ValueError(
                "a routed shortcut is built around a DENSE FFN: the "
                "layer's own FFN is routed already, and one router a layer "
                "is what the launch counters count")
        if self.shortcut and (self.ffn == NO_FFN or self.attention is None):
            raise ValueError(
                "a routed shortcut is built around a DENSE FFN of a layer "
                "with attention: its experts read that layer's "
                "post-attention norm, and a layer without an FFN or "
                "without attention has none")
        if (self.attention is None) != (self.cache is None):
            raise ValueError(
                "an attention kind and a cache descriptor come together: "
                "both, or neither for a layer whose mixer is a state alone")
        if self.attention is None:
            if self.state is None and self.ffn == NO_FFN:
                raise ValueError(
                    "a layer with neither a mixer (attention= with cache=, "
                    "state=, or both) nor an FFN computes nothing: give it "
                    "one or the other")
            if self.window or self.sinks or self.query_heads:
                raise ValueError(
                    "a layer without attention (its mixer a state alone, "
                    "or none) has no window, no sink logits and no query "
                    "heads: they are the attention's")
            return
        if self.window < 0:
            raise ValueError(f"window {self.window} must be >= 0")
        if self.query_heads % self.cache.rows:
            raise ValueError(
                f"query_heads {self.query_heads} is no multiple of the "
                f"cache's {self.cache.rows} rows a token")
        if (self.window or self.sinks) and self.attention != FULL:
            raise ValueError(
                "a sliding window and sink logits are built for the full "
                "attention kind only (the latent kernel has neither)")
        if self.attention not in (FULL, LATENT):
            raise ValueError(
                f"attention kind {self.attention!r}: the fused path knows "
                f"{FULL!r} and {LATENT!r} (a state-holding mixer is no "
                f"third kind: state= beside one of them, or state= alone "
                f"with attention=None, cache=None)")
        if self.state is not None and (self.attention != FULL
                                       or self.window):
            raise ValueError(
                "a recurrent state is built beside full attention over "
                "the whole context, or alone (no latent cache, no window)")


@dataclass(frozen=True)
class GenerationRule:
    """How a model's tokens are generated (module doc). The static
    schedule ``low_confidence_static`` fixes ``block_length /
    denoising_steps`` positions a pass whatever their confidence, so the
    number of passes a block takes is host arithmetic."""
    block_length: int = 1
    denoising_steps: int = 1
    mask_token_id: Optional[int] = None
    remasking: str = "low_confidence_static"

    def __post_init__(self):
        B, steps = int(self.block_length), int(self.denoising_steps)
        if B < 1 or steps < 1 or B % steps:
            raise ValueError(
                f"block_length {B} must be a positive multiple of "
                f"denoising_steps {steps}")
        if B > 1 and self.mask_token_id is None:
            raise ValueError("block generation needs a mask_token_id")
        if self.remasking != "low_confidence_static":
            raise ValueError(
                f"remasking {self.remasking!r}: only low_confidence_static "
                f"is built — a confidence threshold "
                f"(low_confidence_dynamic) makes the number of passes a "
                f"block takes depend on a fetch, and the scheduler plans "
                f"the next launch before it has fetched this one")

    @property
    def fixed_per_pass(self) -> int:
        return int(self.block_length) // int(self.denoising_steps)

    def passes(self, unfixed: int) -> int:
        """Denoising passes a block with ``unfixed`` open positions takes."""
        return -(-int(unfixed) // self.fixed_per_pass)


@dataclass(frozen=True)
class DecoderSpec:
    layers: Tuple[LayerSpec, ...]
    vocab_size: int
    max_positions: int
    generation: GenerationRule = GenerationRule()

    def __post_init__(self):
        if not self.layers:
            raise ValueError("a decoder spec needs at least one layer")
        if not self.cache_layers:
            raise ValueError(
                "no layer of the spec holds a cache: a model of state-"
                "alone layers only is not built (a sequence's positions, "
                "its page table and the launch's row layout are the "
                "cache's)")
        closes = -1              # the layer the open shortcut closes at
        for i, ls in enumerate(self.layers):
            if not ls.shortcut:
                continue
            if i <= closes:
                raise ValueError(
                    f"layer {i} opens a routed shortcut before the one "
                    f"that closes at layer {closes} has closed: the tower "
                    f"carries ONE value beside x (nested or overlapping "
                    f"shortcuts are not built)")
            closes = i + ls.shortcut
            if closes >= len(self.layers):
                raise ValueError(
                    f"layer {i}'s routed shortcut closes at layer "
                    f"{closes}, past the last layer "
                    f"{len(self.layers) - 1}: its experts' sum would "
                    f"join nothing")
        if self.generation.block_length > 1 and any(
                ls.ffn == NO_FFN or not ls.has_mixer for ls in self.layers):
            raise ValueError(
                "a layer without an FFN or without a mixer is not built "
                "under block generation: the block step's passes are "
                "written and tested for layers of a mixer AND an FFN")
        if self.generation.block_length > 1 and len(self.cache_layers) \
                < len(self.layers):
            raise ValueError(
                "a layer whose mixer is a state alone is not built under "
                "block generation: a denoising pass rewrites its block's "
                "rows, and a state cannot take a row back")
        if self.generation.block_length > 1 and self.attention != FULL:
            raise ValueError(
                "block generation is built for the full attention kind "
                "only (the latent kernel's mask is causal)")
        states = {ls.state for ls in self.layers if ls.state is not None}
        if len(states) > 1:
            raise ValueError(
                "the layers that have a recurrent state differ in its "
                "descriptor: the pool holds ONE array a part for all of "
                "them")
        if states and self.generation.block_length > 1:
            raise ValueError(
                "a recurrent state under block generation is not built: a "
                "denoising pass rewrites its block's rows, and a "
                "recurrence cannot take a row back")
        groups = self.cache_groups
        if len(groups) > 1 and (
                self.generation.block_length > 1
                or any(g.attention != FULL for g in groups)):
            raise ValueError(
                "more than one cache group is built for full attention "
                "generated one token a step only: a latent group beside "
                "another, and block generation over a window, are not")

    @cached_property
    def cache_layers(self) -> Tuple[int, ...]:
        """The layers that hold a cache, in order (all of them, but for a
        model with layers whose mixer is a state alone)."""
        return tuple(i for i, ls in enumerate(self.layers)
                     if ls.cache is not None)

    @property
    def attention(self) -> str:
        """The attention kind of the first layer that has one."""
        return self.layers[self.cache_layers[0]].attention

    @property
    def cache(self) -> CacheSpec:
        """The FIRST group's descriptor (the only one, for a model whose
        cache-bearing layers share it)."""
        return self.layers[self.cache_layers[0]].cache

    @cached_property
    def cache_groups(self) -> Tuple["CacheGroup", ...]:
        """The cache-bearing layers with equal ``(attention, cache,
        window)``, in order of first appearance: one pool array and one
        page table a request each (module doc)."""
        keys, members = [], {}
        for i in self.cache_layers:
            ls = self.layers[i]
            key = (ls.attention, ls.cache, ls.window)
            if key not in members:
                keys.append(key)
                members[key] = []
            members[key].append(i)
        groups = []
        for a, c, w in keys:
            layers = tuple(members[(a, c, w)])
            heads = {self.layers[i].query_heads for i in layers}
            if len(heads) > 1:
                raise ValueError(
                    f"layers {layers} share a cache group and differ in "
                    f"query heads {sorted(heads)}: one kernel form a group")
            groups.append(CacheGroup(a, c, w, layers,
                                     max(1, heads.pop() // c.rows)))
        return tuple(groups)

    @cached_property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that hold a recurrent state, in order: a layer's
        place here is its place in the pool's state arrays."""
        return tuple(i for i, ls in enumerate(self.layers)
                     if ls.state is not None)

    @property
    def state(self) -> Optional[StateSpec]:
        """The state descriptor those layers share (None: no layer has
        one)."""
        return self.layers[self.state_layers[0]].state \
            if self.state_layers else None

    def layer_group(self, layer: int) -> Tuple[int, int]:
        """``(group, index inside the group's pool array)`` of a layer."""
        for g, grp in enumerate(self.cache_groups):
            if layer in grp.layers:
                return g, grp.layers.index(layer)
        if 0 <= layer < len(self.layers):
            raise ValueError(
                f"layer {layer} holds no cache (its mixer is a state "
                f"alone, or it has none): it belongs to no cache group")
        raise IndexError(f"layer {layer} out of range")


@dataclass(frozen=True)
class CacheGroup:
    """One cache group of a spec: its layers (indices into
    ``DecoderSpec.layers``) share the attention kind, the cache
    descriptor and the window."""
    attention: str
    cache: CacheSpec
    window: int
    layers: Tuple[int, ...]
    q_group: int = 1         # query heads a KV head, of its layers


def serving_decoder(model):
    """The decoder a model serves through the fused paged path."""
    make = getattr(model, "serving_decoder", None)
    if make is None:
        raise TypeError(
            f"{type(model).__name__} exposes no serving_decoder(): the "
            f"fused serving stack consumes a decoder spec "
            f"(models/decoder_spec.py), which models/gpt.py, "
            f"models/axk1.py, models/sdar.py, models/mimo.py, "
            f"models/falcon_h1.py, models/lfm2.py, models/longcat.py and "
            f"models/nemotron_h.py provide")
    return make()
