"""The recurrent state a slot in the ONE pool manager
(``serving/paging.py``): a row of every part of the spec's state
descriptor a slot, claimed with the slot and given back with it, counted
under its own ledger key, never cleared on the host, nothing offered to
the prefix cache — and, for the specs that have no state, the step
programs they had before there was one.
"""
import hashlib

import numpy as np
import pytest

from paddle_tpu.framework.monitor import stat_histogram
from paddle_tpu.profiler import memory as M
from paddle_tpu.serving import PagedKVPool

import _toys

PARTS = (("conv", (3, 24), "float32"), ("ssm", (2, 4, 8), "float32"))
SLOT_BYTES = 3 * (3 * 24 + 2 * 4 * 8) * 4          # three layers with state


def _pool(slots=4, **kw):
    return PagedKVPool(num_layers=5, num_slots=slots, num_heads=2,
                       max_len=64, head_dim=8, block_size=8,
                       state=(3, PARTS), **kw)


def test_one_array_a_part_with_a_row_a_slot_and_one_no_slot_owns():
    pool = _pool()
    assert [(n, s, d.name) for n, s, d in pool.state_parts] == [
        ("conv", (3, 5, 3, 24), "float32"), ("ssm", (3, 5, 2, 4, 8),
                                             "float32")]
    assert [tuple(a.shape) for a in pool.state_data] == [
        (3, 5, 3, 24), (3, 5, 2, 4, 8)]
    assert all(float(np.abs(np.asarray(a)).max()) == 0.0
               for a in pool.state_data)
    assert pool.state_slot_bytes == SLOT_BYTES
    assert pool.state_bytes == 5 * SLOT_BYTES
    # the blocks' own figures do not move: a block's bytes are the blocks'
    plain = PagedKVPool(num_layers=5, num_slots=4, num_heads=2, max_len=64,
                        head_dim=8, block_size=8)
    assert pool.capacity_bytes == plain.capacity_bytes
    assert pool.block_bytes == plain.block_bytes
    assert plain.state_parts == () and plain.state_data == ()
    assert plain.state_bytes == 0 and plain.state_live_bytes == 0


def test_a_slots_state_is_claimed_with_it_and_given_back_with_it():
    pool = _pool()
    assert pool.state_live_bytes == 0
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (0, 1) and pool.state_live_bytes == 2 * SLOT_BYTES
    h = stat_histogram("serving/state_slots_in_use")
    assert h and h["max"] >= 2
    pool.free(a)
    assert pool.state_live_bytes == SLOT_BYTES
    # the row is not cleared on the host: the step starts position 0 from
    # zero itself (the previous owner's last launch may be in flight)
    before = [np.asarray(x).copy() for x in pool.state_data]
    assert pool.alloc() == 0
    for x, y in zip(before, pool.state_data):
        np.testing.assert_array_equal(x, np.asarray(y))


def test_the_state_stands_in_the_ledger_under_its_own_key():
    pool = _pool()
    led = M.ledger()
    assert led[f"{pool.ledger_key}/state"] == 5 * SLOT_BYTES
    assert led[f"{pool.ledger_key}/capacity"] == pool.capacity_bytes
    pool.drop_ledger()
    assert not [k for k in M.ledger() if k.startswith(pool.ledger_key)]
    plain = PagedKVPool(num_layers=1, num_slots=1, num_heads=1, max_len=8,
                        head_dim=8, block_size=8)
    assert f"{plain.ledger_key}/state" not in M.ledger()


def test_reset_data_gives_fresh_state_arrays():
    import jax.numpy as jnp
    pool = _pool()
    pool.state_data = tuple(jnp.ones_like(a) for a in pool.state_data)
    pool.reset_data()
    assert all(float(np.abs(np.asarray(a)).max()) == 0.0
               for a in pool.state_data)


def test_nothing_is_offered_to_or_matched_in_the_prefix_cache():
    pool = _pool()
    tokens = list(range(1, 41))
    slot = pool.alloc()
    pool.admit_fresh(slot, len(tokens))
    pool.set_slot(slot, pos=0, lo=0)
    pool.advance(slot, 40)
    pool.register_prefix(slot, tokens)
    assert pool.cached_blocks == 0
    assert pool.match_prefix(tokens + [99]) == []
    pool.free(slot)
    assert pool.blocks_in_use == 0 and pool.blocks_available == pool.num_blocks
    # the same calls on a pool without state do share
    plain = PagedKVPool(num_layers=5, num_slots=4, num_heads=2, max_len=64,
                        head_dim=8, block_size=8)
    slot = plain.alloc()
    plain.admit_fresh(slot, len(tokens))
    plain.register_prefix(slot, tokens)
    assert plain.cached_blocks == 5 and len(plain.match_prefix(
        tokens + [99])) == 5


@pytest.mark.parametrize("kw,match", [
    (dict(dtype="int8", block_size=32), "over a mesh or beside int8/fp8"),
    (dict(mesh="a mesh"), "over a mesh or beside int8/fp8"),
], ids=["int8-blocks", "mesh"])
def test_a_state_layout_that_is_not_built_is_refused(kw, match):
    args = dict(num_layers=2, num_slots=2, num_heads=2, max_len=64,
                head_dim=8, block_size=8, state=(2, PARTS))
    args.update(kw)
    with pytest.raises(ValueError, match=match):
        PagedKVPool(**args)


# -- a spec without state builds the step programs it built at the parent -----

# (program name, operands, results, sha1 of the operands' and results'
# shapes and dtypes, equations of the traced step) of the four stateless
# toy specs, recorded at the parent commit (PR 39) with this file's
# `_signature`: a layer without a state descriptor goes through the tower
# as it did. The three routed families' were recorded again at PR 44,
# which changed what they trace: a fourth counter in the step's result
# (another digest) and `routed_experts`' layout (10 equations a routed
# layer more; the same in the compact twins below), and at PR 46 for
# the fifth counter and nothing else: another digest (a result five
# longer), one more add a routed layer after the first and one more
# operand of the stack — GPT-2's and Falcon-H1's (no router) stand as
# recorded, so a spec without a shortcut goes through the tower as it did
PARENT = {
    ("axk1", 8, 1): ("fused_step_q8_t1", 64, 3, "b4328c0b1088", 604),
    ("axk1", 32, 4): ("fused_step_q32_t4", 64, 3, "9327d4a3ff50", 604),
    ("gpt2", 8, 1): ("fused_step_q8_t1", 53, 3, "51d7dda33271", 108),
    ("gpt2", 32, 4): ("fused_step_q32_t4", 53, 3, "282358f216af", 108),
    ("mimo", 8, 1): ("fused_step_q8_t1", 68, 4, "71b7c4ad3fd7", 571),
    ("mimo", 32, 4): ("fused_step_q32_t4", 68, 4, "849b80a524cc", 571),
    ("sdar", 8, 1): ("block_step_q8_t1", 46, 3, "4af2c7f91751", 417),
    ("sdar", 32, 4): ("block_step_q32_t4", 46, 3, "7697fed80065", 417),
}

# Since PR 41 a program's tower runs on R(Q) <= Q rows
# (`engine._tower_rows`; the rule's row multiple is 8 here, the toy
# engines holding two slots and a chunk budget of 16): 2 decode rows + up
# to 16 chunk rows are 24 tower rows under the 32 kernel rows of q32, which
# hold the two slots' q blocks and the budget — the SAME program name and
# result, per-row operands of 24, and the row axes' index arithmetic with
# three gathers a full-attention layer (two a latent one) on top of the
# parent's equations. Where R(Q) == Q the parent's program is traced,
# equation for equation: every q8 program, and BOTH of sdar's, whose
# two slots' blocks of 8 rows fill the kernel's rows (the identity
# rule's proof)
COMPACT = {
    ("axk1", 32, 4): (24, ("fused_step_q32_t4", 64, 3, "5511450ffbb2", 663)),
    ("gpt2", 32, 4): (24, ("fused_step_q32_t4", 53, 3, "0ef8be99449b", 167)),
    ("mimo", 32, 4): (24, ("fused_step_q32_t4", 68, 4, "433c37a70b2f", 637)),
}


def _signature(eng, net, Q, T):
    import jax
    from paddle_tpu.models.generation import build_fused_step_fn
    fn = build_fused_step_fn(net, 2, Q, T, 8)
    jaxpr = jax.make_jaxpr(fn)(eng._params, eng._buffers,
                               eng._pool_operand(),
                               *eng._null_step_operands(Q, T)).jaxpr
    shapes = lambda vs: [(tuple(v.aval.shape), str(v.aval.dtype))
                         for v in vs]
    digest = hashlib.sha1(repr((shapes(jaxpr.invars),
                                shapes(jaxpr.outvars))).encode())
    return (fn.__name__, len(jaxpr.invars), len(jaxpr.outvars),
            digest.hexdigest()[:12], len(jaxpr.eqns))


@pytest.mark.parametrize("family", ["gpt2", "axk1", "sdar", "mimo"])
def test_a_spec_without_state_builds_the_parents_step_programs(
        family, monkeypatch):
    import paddle_tpu.ops.ragged_paged_attention as rpa
    from paddle_tpu.models.decoder_spec import serving_decoder
    from paddle_tpu.serving import GenerationEngine
    monkeypatch.setattr(rpa, "TOWER_ROW_MULTIPLE", 8)
    net = _toys.default(family)
    spec = serving_decoder(net).spec
    assert spec.state is None and spec.state_layers == ()
    # the budget is no part of a program: it says which R a Q has
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           prefill_budget=16)
    try:
        assert eng._pool.state_data == ()
        assert "state" not in eng.stats()
        for Q, T in ((8, 1), (32, 4)):
            rows, want = COMPACT.get((family, Q, T),
                                     (Q, PARENT[(family, Q, T)]))
            assert eng._tower_rows(Q) == rows
            assert _signature(eng, net, Q, T) == want
        if family == "sdar":
            assert not [k for k in COMPACT if k[0] == "sdar"]
        # the padded twin of a compact program IS the parent's
        eng._tower_rows = lambda Q: int(Q)
        assert _signature(eng, net, 32, 4) == PARENT[(family, 32, 4)]
    finally:
        eng.close()
