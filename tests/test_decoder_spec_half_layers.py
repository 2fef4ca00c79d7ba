"""Half a layer in the decoder spec: a layer that is its mixer WITHOUT an
FFN (``LayerSpec.ffn`` ``"none"``) and a layer that is its FFN WITHOUT a
mixer (no attention, no cache, no state) — what a spec may state, what is
refused by name, and that the tower computes nothing for the half a layer
lacks: a toy decoder whose layers count every call made of them.
"""
import numpy as np
import pytest

from paddle_tpu.models import decoder_spec as DS

FULL = DS.CacheSpec(rows=2, lanes=32)
LATENT = DS.CacheSpec(rows=1, lanes=128, v_aliases_k=True, v_lanes=32)
STATE = DS.StateSpec((("conv", (3, 8), "float32"),
                      ("ssm", (2, 4, 8), "float32")))

attention_alone = lambda: DS.LayerSpec(DS.FULL, FULL, DS.NO_FFN,
                                       query_heads=4)
state_alone = lambda: DS.LayerSpec(None, None, DS.NO_FFN, state=STATE)
ffn_alone = lambda kind=DS.ROUTED: DS.LayerSpec(None, None, kind)


def _spec(*layers, **kw):
    return DS.DecoderSpec(layers=tuple(layers), vocab_size=64,
                          max_positions=64, **kw)


def test_a_mixer_alone_and_an_ffn_alone_are_layers_of_one_spec():
    spec = _spec(state_alone(), ffn_alone(), state_alone(),
                 attention_alone(), ffn_alone(DS.DENSE))
    assert spec.cache_layers == (3,) and spec.state_layers == (0, 2)
    assert [ls.has_mixer for ls in spec.layers] == [True, False, True, True,
                                                    False]
    assert [ls.routes for ls in spec.layers] == [False, True, False, False,
                                                 False]
    assert spec.layer_group(3) == (0, 0)
    with pytest.raises(ValueError, match="or it has none"):
        spec.layer_group(1)
    (group,) = spec.cache_groups
    assert group.q_group == 2
    # a latent attention and a state beside attention may go without an
    # FFN too
    assert DS.LayerSpec(DS.LATENT, LATENT, DS.NO_FFN).has_mixer
    assert not DS.LayerSpec(DS.FULL, FULL, DS.NO_FFN, state=STATE).routes
    assert DS.NO_FFN == "none" and DS.LATENT_PROJ in DS.SECTIONS
    assert DS.section_of("jit(f)/layer1/moe_experts/latent_proj/dot") \
        == DS.LATENT_PROJ


@pytest.mark.parametrize("make,match", [
    (lambda: DS.LayerSpec(None, None, DS.NO_FFN), "computes nothing"),
    (lambda: DS.LayerSpec(None, None, "gated"), "'dense', 'routed' and "
                                                  "'none'"),
    (lambda: DS.LayerSpec(None, None, DS.ROUTED, query_heads=4),
     "no query heads"),
    (lambda: DS.LayerSpec(None, None, DS.DENSE, window=8), "has no window"),
    (lambda: DS.LayerSpec(DS.LATENT, LATENT, DS.NO_FFN, shortcut=1),
     "around a DENSE FFN of a layer with attention"),
    (lambda: DS.LayerSpec(None, None, DS.DENSE, shortcut=1),
     "around a DENSE FFN of a layer with attention"),
    (lambda: _spec(ffn_alone(), ffn_alone(DS.DENSE)),
     "no layer of the spec holds a cache"),
    (lambda: _spec(attention_alone(), generation=DS.GenerationRule(
        block_length=4, denoising_steps=4, mask_token_id=63)),
     "without an FFN or without a mixer is not built under block"),
    (lambda: _spec(DS.LayerSpec(DS.FULL, FULL, DS.DENSE), ffn_alone(),
                   generation=DS.GenerationRule(
                       block_length=4, denoising_steps=4, mask_token_id=63)),
     "without an FFN or without a mixer is not built under block"),
], ids=["neither-half", "a-fourth-ffn-kind", "query-heads-without-attention",
        "window-without-attention", "shortcut-without-an-ffn",
        "shortcut-without-attention", "no-cache-at-all",
        "no-ffn-under-blocks", "no-mixer-under-blocks"])
def test_what_half_a_layer_cannot_be_is_refused_by_its_message(make, match):
    with pytest.raises(ValueError, match=match):
        make()


def test_the_tower_calls_nothing_of_the_half_a_layer_lacks():
    """Layers ``state | FFN | attention | FFN`` of a toy decoder. Every
    method appends its name: the state layer runs ``mixer`` and
    ``attn_out``, the attention layer ``attn_in`` and ``attn_out``, the
    FFN layers ``ffn_out`` alone; the routed one's counters come back
    summed, a layer without an FFN adds none; the state arrays pass
    through the one layer that has a state."""
    import jax.numpy as jnp
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.models.generation import _fused_tower
    calls = []
    add = lambda x, v: Tensor(x._data + v, stop_gradient=True)

    class Layer:
        def __init__(self, index):
            self.index = index

        def attn_in(self, x, positions):
            calls.append((self.index, "attn_in"))
            Q = x.shape[1]
            z = jnp.zeros((Q, 2, 16), jnp.float32)
            return jnp.zeros((4, Q, 16), jnp.float32), (z, z)

        def mixer(self, x, layout, state, index):
            calls.append((self.index, "mixer"))
            assert index == 0
            tail, ssm = state
            return jnp.full(x.shape[1:], 10.0, jnp.float32), (tail + 1.0, ssm)

        def attn_out(self, x, a, row_valid, s=None):
            calls.append((self.index, "attn_out"))
            assert (a is None) == (s is not None)
            return add(x, 1.0 if s is None else s), None

        def ffn_out(self, x, row_valid):
            calls.append((self.index, "ffn_out"))
            counters = None
            if self.index == 1:
                counters = tuple(jnp.int32(v) for v in (5, 2, 8, 16, 0))
            return add(x, 100.0), counters

    class Dec:
        spec = _spec(state_alone(), ffn_alone(), attention_alone(),
                     ffn_alone(DS.DENSE))
        layers = [Layer(i) for i in range(4)]

        @staticmethod
        def final_norm(x):
            return x

    Q, S, bs = 8, 1, 8
    pool = jnp.zeros((1, 3, 2, bs, 32), jnp.float32)
    state = (jnp.zeros((1, S + 1, 3, 8), jnp.float32),
             jnp.zeros((1, S + 1, 2, 4, 8), jnp.float32))
    i32 = lambda *v: jnp.asarray(v, jnp.int32)
    x, new_pool, _, counters, new_state = _fused_tower(
        Dec, Tensor(jnp.zeros((1, Q, 4), jnp.float32)),
        jnp.arange(Q, dtype=jnp.int32), pool, None,
        jnp.ones(Q, jnp.int32), jnp.arange(Q, dtype=jnp.int32),
        i32(0), i32(0), i32(0), jnp.ones((S, 1), jnp.int32), i32(0), i32(Q),
        False, 0.0, state=state)
    assert calls == [(0, "mixer"), (0, "attn_out"), (1, "ffn_out"),
                     (2, "attn_in"), (2, "attn_out"), (3, "ffn_out")]
    np.testing.assert_array_equal(np.asarray(x._data), 211.0)
    np.testing.assert_array_equal(np.asarray(counters), [5, 2, 8, 16, 0])
    np.testing.assert_array_equal(np.asarray(new_state[0]), 1.0)
    assert new_pool.shape == pool.shape
