"""A routed shortcut in the decoder spec (``LayerSpec.shortcut``): what a
spec may state, what ``DecoderSpec`` refuses by name, and that the tower
alone carries the value — a toy decoder whose opening layer returns a
marker and whose later layers see only ``x``.
"""
import numpy as np
import pytest

from paddle_tpu.models import decoder_spec as DS

LATENT = DS.CacheSpec(rows=1, lanes=128, v_aliases_k=True, v_lanes=32)


def _layer(shortcut=0, ffn=DS.DENSE):
    return DS.LayerSpec(DS.LATENT, LATENT, ffn, shortcut=shortcut)


def _spec(*layers):
    return DS.DecoderSpec(layers=tuple(layers), vocab_size=64,
                          max_positions=64)


def test_eight_sub_blocks_are_one_cache_group_and_four_of_them_route():
    spec = _spec(*[_layer(1), _layer()] * 4)
    (group,) = spec.cache_groups
    assert group.layers == tuple(range(8)) and spec.cache_layers \
        == tuple(range(8))
    assert [ls.routes for ls in spec.layers] == [True, False] * 4
    assert {ls.ffn for ls in spec.layers} == {DS.DENSE}
    assert _layer(ffn=DS.ROUTED).routes and not _layer().routes
    # a longer shortcut, and one opened right after another closed
    assert _spec(_layer(2), _layer(), _layer(), _layer(1), _layer())
    assert DS.ROUTED_COUNTERS == 5
    assert {DS.ZERO_EXPERTS, DS.SHORTCUT} <= set(DS.SECTIONS)
    assert DS.section_of("jit(f)/layer0/moe_experts/zero_experts/mul") \
        == "zero_experts"
    assert DS.section_of("jit(f)/layer1/shortcut/add") == "shortcut"


@pytest.mark.parametrize("make,match", [
    (lambda: _spec(_layer(), _layer(1)), "past the last layer"),
    (lambda: _spec(_layer(1), _layer(2), _layer(), _layer()),
     "before the one that closes at layer 1 has closed"),
    (lambda: _spec(_layer(2), _layer(1), _layer()),
     "before the one that closes at layer 2 has closed"),
    (lambda: _layer(1, DS.ROUTED), "around a DENSE FFN"),
    (lambda: _layer(-1), "must be >= 0"),
])
def test_what_a_shortcut_cannot_be_is_refused_by_its_message(make, match):
    with pytest.raises(ValueError, match=match):
        make()


def test_the_tower_carries_the_value_and_adds_it_at_the_closing_layer():
    """Four latent layers of a toy decoder; layer 0 opens a shortcut that
    closes at layer 2. Each layer adds 1 to ``x``; the opening layer hands back 100 a row: ``x`` is 1, 2
    after layers 0, 1 (nothing added yet), 103 after layer 2 (its own add,
    THEN the carried value), 104 after layer 3."""
    import jax.numpy as jnp
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.models.generation import _fused_tower
    seen = []

    class Layer:
        def __init__(self, opens):
            self.opens = opens

        def attn_in(self, x, positions):
            seen.append(float(x._data[0, 0, 0]))
            q = jnp.zeros((x.shape[1], 2, 128), jnp.float32)
            return q, jnp.zeros((x.shape[1], 128), jnp.float32)

        def attn_out(self, x, a, row_valid):
            y = Tensor(x._data + 1.0, stop_gradient=True)
            if self.opens:
                return y, None, jnp.full(x.shape[1:], 100.0, jnp.float32)
            return y, None

    class Dec:
        spec = _spec(_layer(2), _layer(), _layer(), _layer())
        layers = [Layer(True), Layer(False), Layer(False), Layer(False)]
        attention_scale = 1.0

        @staticmethod
        def final_norm(x):
            return x

    Q, T, bs = 8, 1, 8
    pool = jnp.zeros((4, 3, 1, bs, 128), jnp.float32)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)
    x, _, _, counters, _ = _fused_tower(
        Dec, Tensor(jnp.zeros((1, Q, 4), jnp.float32)),
        jnp.arange(Q, dtype=jnp.int32), pool, None,
        jnp.ones(Q, jnp.int32), jnp.arange(Q, dtype=jnp.int32),
        i32(0), i32(0), i32(0), jnp.ones((1, T), jnp.int32), i32(0), i32(Q),
        False, 0.0)
    assert counters is None
    assert seen == [0.0, 1.0, 2.0, 103.0]
    np.testing.assert_array_equal(np.asarray(x._data), 104.0)
