"""A layer whose mixer is a recurrent state ALONE (no attention kind, no
``CacheSpec``, no query heads): what the decoder spec says of it, what it
refuses, and that a spec with a state in every cache-bearing layer
(Falcon-H1) still builds the step programs it built at the parent commit
(PR 41) — program names, operand shapes and the traced equations, on a
toy spec; the four stateless families' are held by
``tests/test_state_pool.py``."""
import pytest

from paddle_tpu.models import decoder_spec as DS
import _toys
from test_state_pool import _signature

FULL = DS.CacheSpec(rows=2, lanes=16)
TAIL = DS.StateSpec((("conv", (2, 64), "float32"),))


def _conv(ffn=DS.DENSE):
    return DS.LayerSpec(None, None, ffn, state=TAIL)


def _attn(ffn=DS.ROUTED, **kw):
    return DS.LayerSpec(DS.FULL, FULL, ffn, query_heads=8, **kw)


def _spec(*layers, **kw):
    return DS.DecoderSpec(tuple(layers), 256, 128, **kw)


# -- the spec --------------------------------------------------------------------

def test_a_state_alone_layer_builds_and_the_cache_layers_are_a_subset():
    spec = _spec(_conv(), _conv(), _attn(), _conv(DS.ROUTED),
                 _conv(DS.ROUTED), _conv(DS.ROUTED), _attn())
    assert spec.cache_layers == (2, 6)
    assert spec.state_layers == (0, 1, 3, 4, 5)
    assert spec.state is TAIL and TAIL.nbytes == 2 * 64 * 4
    # layer 0 has neither: the spec's attention and cache are the first
    # cache-bearing layer's
    assert spec.layers[0].attention is None and spec.layers[0].cache is None
    assert spec.attention == DS.FULL and spec.cache is FULL
    (group,) = spec.cache_groups
    assert group.layers == (2, 6) and group.q_group == 4
    assert spec.layer_group(2) == (0, 0) and spec.layer_group(6) == (0, 1)


@pytest.mark.parametrize("layer", [0, 1, 3])
def test_layer_group_of_a_cache_less_layer_is_an_error_not_group_0(layer):
    spec = _spec(_conv(), _conv(), _attn(), _conv())
    with pytest.raises(ValueError, match="holds no cache"):
        spec.layer_group(layer)
    with pytest.raises(IndexError, match="out of range"):
        spec.layer_group(4)


def test_a_state_beside_attention_and_a_state_alone_share_one_descriptor():
    spec = _spec(_attn(DS.DENSE, state=TAIL), _conv())
    assert spec.state_layers == (0, 1) and spec.cache_layers == (0,)
    other = DS.StateSpec((("conv", (3, 64), "float32"),))
    with pytest.raises(ValueError, match="differ in its descriptor"):
        _spec(_attn(), DS.LayerSpec(None, None, DS.DENSE, state=TAIL),
              DS.LayerSpec(None, None, DS.DENSE, state=other))


@pytest.mark.parametrize("make,match", [
    (lambda: DS.LayerSpec(None, None, DS.NO_FFN), "computes nothing"),
    (lambda: DS.LayerSpec(None, FULL, DS.DENSE, state=TAIL),
     "come together"),
    (lambda: DS.LayerSpec(DS.FULL, None, DS.DENSE, state=TAIL),
     "come together"),
    (lambda: DS.LayerSpec(None, None, DS.DENSE, state=TAIL, query_heads=8),
     "no query heads"),
    (lambda: DS.LayerSpec(None, None, DS.DENSE, state=TAIL, window=8),
     "has no window"),
    (lambda: DS.LayerSpec("conv", FULL, DS.DENSE),
     "state= alone with attention=None"),
    (lambda: _spec(_conv(), _conv()), "no layer of the spec holds a cache"),
    (lambda: _spec(_attn(), _conv(), generation=DS.GenerationRule(
        block_length=4, denoising_steps=4, mask_token_id=255)),
     "state alone is not built under block generation"),
], ids=["no-mixer-and-no-ffn", "cache-without-attention",
        "attention-without-cache",
        "query-heads", "window", "a-third-kind", "no-cache-at-all",
        "block-generation"])
def test_what_is_not_built_is_refused_by_its_message(make, match):
    with pytest.raises(ValueError, match=match):
        make()


# -- a spec whose every layer has attention builds the parent's step programs ------

# tests/test_state_pool.py holds the four STATELESS families (GPT-2,
# A.X-K1, SDAR, MiMo) to the signatures recorded before there was a state;
# here the fifth, Falcon-H1 — a state in EVERY cache-bearing layer — to
# what `test_state_pool._signature` read at the parent commit (PR 41):
# (program name, operands, results, sha1 of their shapes and dtypes,
# equations of the traced step), a toy engine of two slots and a chunk
# budget of 16 (`rpa.TOWER_ROW_MULTIPLE` 8: the q32 program runs on 24
# tower rows). The equations are PR 51's (the decode update of
# `ops/ssm.py:ssm_scan` is ONE jitted kernel call a layer, 17 equations
# fewer); operands, results and their shapes are PR 41's
PARENT = {
    (8, 1): ("fused_step_q8_t1", 56, 5, "5d7f1da3f51c", 729),
    (32, 4): ("fused_step_q32_t4", 56, 5, "6cf606c8cc0e", 788),
}


@pytest.mark.parametrize("Q,T", sorted(PARENT))
def test_a_state_beside_every_cache_builds_the_parents_program(
        Q, T, monkeypatch):
    import paddle_tpu.ops.ragged_paged_attention as rpa
    from paddle_tpu.serving import GenerationEngine
    monkeypatch.setattr(rpa, "TOWER_ROW_MULTIPLE", 8)
    net = _toys.default("falcon_h1")
    spec = DS.serving_decoder(net).spec
    assert spec.cache_layers == spec.state_layers == (0, 1)
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           prefill_budget=16)
    try:
        assert _signature(eng, net, Q, T) == PARENT[(Q, T)]
    finally:
        eng.close()
