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

Two attention kinds, two FFN kinds, two callers (``models/gpt.py``,
``models/axk1.py``). Nothing else is described here.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

__all__ = ["CacheSpec", "LayerSpec", "DecoderSpec", "serving_decoder",
           "FULL", "LATENT", "DENSE", "ROUTED"]

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
class DecoderSpec:
    layers: Tuple[LayerSpec, ...]
    vocab_size: int
    max_positions: int

    def __post_init__(self):
        if not self.layers:
            raise ValueError("a decoder spec needs at least one layer")
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
            f"(models/decoder_spec.py), which models/gpt.py and "
            f"models/axk1.py provide")
    return make()
