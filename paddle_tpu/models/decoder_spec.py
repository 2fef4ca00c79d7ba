"""The decoder spec: what the fused serving stack needs to know of a
decoder-only model, and nothing else (ROADMAP D2).

``GenerationEngine``, the fused
tower (``models/generation.py:_fused_tower``), ``PagedKVPool`` and the
engine's memory planner consume THIS, not ``model.gpt``: a model is
served by the fused paged path if ``serving_decoder()`` returns an
object with

* ``spec`` — a :class:`DecoderSpec`: per layer the attention kind with
  its cache descriptor and the FFN kind, plus the vocabulary and the
  most positions the model takes;
* ``embed_tokens(token_ids, positions)`` -> ``Tensor [1, Q, E]`` (positions
  that the model adds at the embedding, as GPT does, are added here;
  a rotary model ignores them here and reads them in ``attn_in``);
* ``layers`` — per layer an object with
  ``attn_in(x, positions) -> (q, cache_rows)`` (what the kernel reads and
  what is written to the layer's cache, one entry a row) and
  ``attn_out(x, a, row_valid) -> (x, counters)`` (output projection,
  residual, FFN; ``row_valid [Q]`` marks the rows of the ragged batch that
  are real, which a routed FFN must not route; ``counters`` is ``None``
  or the routed layer's three int32 scalars);
* ``final_norm(x)`` and ``logits(hidden)``.

Two attention kinds, two FFN kinds, one generation rule, three callers
(``models/gpt.py``, ``models/axk1.py``, ``models/sdar.py``). Nothing else
is described here.

**KV heads apart from query heads.** ``CacheSpec.rows`` is the number of
KV heads a token leaves in a ``full`` cache; ``attn_in`` may return more
query heads than that (a multiple: grouped-query attention), and the
kernel folds a KV head's group of query heads into the rows of its
products (``ops/ragged_paged_attention.py``).

**The generation rule** (:class:`GenerationRule`): ``block_length`` 1 is
one token a sequence a step, the next token from the last row's logits.
``block_length`` B > 1 is generation by diffusion over blocks: a
sequence's next B positions are generated together, a row at position
``p`` attends to every column up to the end of ``p``'s block, a DENOISING
pass runs the block's B rows (fixed ids, ``mask_token_id`` elsewhere),
fixes the ``B / denoising_steps`` most confident unfixed positions and
keeps nothing in the cache, and once nothing is unfixed a COMMIT pass
runs the block's final tokens, whose K/V the cache keeps.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

__all__ = ["CacheSpec", "LayerSpec", "DecoderSpec", "GenerationRule",
           "serving_decoder", "FULL", "LATENT", "DENSE", "ROUTED"]

FULL, LATENT = "full", "latent"        # attention kinds
DENSE, ROUTED = "dense", "routed"      # FFN kinds


@dataclass(frozen=True)
class CacheSpec:
    """What one token holds in one layer's paged cache: ``rows`` rows of
    ``lanes`` values (a block of the pool is ``[rows, block_size,
    lanes]``). ``full``: one row a head, K in lanes ``[0, Dh)`` and V in
    ``[Dh, 2 * Dh)``. ``latent``: ONE row for every head, and V is the
    first ``v_lanes`` lanes of the same stored row (``v_aliases_k``)."""
    rows: int
    lanes: int
    v_aliases_k: bool = False
    v_lanes: int = 0


@dataclass(frozen=True)
class LayerSpec:
    attention: str
    cache: CacheSpec
    ffn: str

    def __post_init__(self):
        if self.attention not in (FULL, LATENT):
            raise ValueError(f"attention kind {self.attention!r}: the fused "
                             f"path knows {FULL!r} and {LATENT!r}")
        if self.ffn not in (DENSE, ROUTED):
            raise ValueError(f"FFN kind {self.ffn!r}: the fused path knows "
                             f"{DENSE!r} and {ROUTED!r}")


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
        if self.generation.block_length > 1 and self.attention != FULL:
            raise ValueError(
                "block generation is built for the full attention kind "
                "only (the latent kernel's mask is causal)")
        if len({(ls.attention, ls.cache) for ls in self.layers}) != 1:
            raise ValueError(
                "every layer of one model shares one attention kind and "
                "one cache descriptor: the paged pool is ONE array "
                "[layers, blocks, rows, block_size, lanes] (window and "
                "global layers in one cache manager are not built yet)")

    @property
    def attention(self) -> str:
        return self.layers[0].attention

    @property
    def cache(self) -> CacheSpec:
        return self.layers[0].cache


def serving_decoder(model):
    """The decoder a model serves through the fused paged path."""
    make = getattr(model, "serving_decoder", None)
    if make is None:
        raise TypeError(
            f"{type(model).__name__} exposes no serving_decoder(): the "
            f"fused serving stack consumes a decoder spec "
            f"(models/decoder_spec.py), which models/gpt.py, "
            f"models/axk1.py and models/sdar.py provide")
    return make()
