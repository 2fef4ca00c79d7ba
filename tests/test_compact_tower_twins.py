"""The compact step against its padded twin, a family at a time: the
same launches driven by hand through ``engine._run_fused_step`` on an
engine whose tower runs on its own row axis (``R = tower_rows(Q)``) and on
the SAME engine with ``_tower_rows = lambda Q: Q`` — tokens, cache blocks
and state rows agree. The rule ``R(Q)``, the plans and the other users of
the tower are ``tests/test_compact_tower.py``'s; this half is a file of
its own because a file is what the suite's workers are handed (PR 45)."""
import numpy as np
import pytest

import paddle_tpu.ops.ragged_paged_attention as rpa

import _toys


@pytest.fixture
def small_multiple(monkeypatch):
    monkeypatch.setattr(rpa, "TOWER_ROW_MULTIPLE", 8)


# three requests in slots 0, 1 and 3 of four (slot 2 stays absent): two
# launches of chunks (the second beside a decode row), then two of decode
# rows only — no context crosses into a third block, so one table bucket
PROMPTS = {0: 14, 1: 3, 3: 11}
LAUNCHES = [{0: 9, 1: 3, 3: 4}, {0: 5, 1: 1, 3: 7}, {0: 1, 1: 1, 3: 1},
            {0: 1, 1: 1, 3: 1}]


def _drive(net, padded_twin):
    """The launches above through ``engine._run_fused_step`` with the
    scheduler's bookkeeping done by hand (positions advance, the feed
    drains, a slot whose feed is drained takes its token). Returns the
    tokens each launch gave the slots that got one, the ``(Q, R)`` of the
    launches and the pool's arrays at the end."""
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.serving.scheduler import GenerationRequest
    eng = GenerationEngine(net, num_slots=4, max_len=64, block_size=8,
                           prefill_budget=16)
    try:
        if padded_twin:
            eng._tower_rows = lambda Q: int(Q)
        pool = eng._pool
        reqs = {}
        for slot in range(4):
            assert pool.alloc() == slot
        for slot, n in PROMPTS.items():
            reqs[slot] = GenerationRequest(
                (np.arange(n) * 7 + 3 * slot + 1) % 50 + 2, 8)
            eng._run_admit(reqs[slot], slot)
        tokens, shapes = [], []
        for plan in LAUNCHES:
            for slot, n in plan.items():
                pool.ensure_writable_range(slot, pool.slot_pos(slot) + n - 1)
            Q, _, ops, *_ = eng._ragged_operands(reqs, plan)
            shapes.append((Q, int(ops[0].shape[0])))
            toks = np.asarray(eng._run_fused_step(reqs, plan))
            got = {}
            for slot, n in plan.items():
                req = reqs[slot]
                pool.advance(slot, n)
                del req.pending_feed[:n]
                if not req.pending_feed:
                    req.last_token = got[slot] = int(toks[slot])
            tokens.append(got)
        blocks = [np.asarray(a, np.float32)[:, 1:] for a in pool.group_data]
        state = [np.asarray(a)[:, :4] for a in pool.state_data]
        return tokens, shapes, blocks, state
    finally:
        eng.close()


@pytest.mark.parametrize("family", ["gpt2", "axk1", "mimo", "falcon_h1"])
def test_a_mixed_launch_through_the_compact_step_is_the_padded_one(
        family, small_multiple):
    net = _toys.default(family)
    tokens, shapes, blocks, state = _drive(net, padded_twin=False)
    t_tokens, t_shapes, t_blocks, t_state = _drive(net, padded_twin=True)
    # the chunk launches move a bucket up and run 24 tower rows under 64
    # kernel rows, the decode launches 8 under 32; the twin runs Q rows
    assert shapes == [(64, 24), (64, 24), (32, 8), (32, 8)]
    assert t_shapes == [(32, 32), (32, 32), (32, 32), (32, 32)]
    assert [sorted(t) for t in tokens] == [[1], [0, 1, 3], [0, 1, 3],
                                           [0, 1, 3]]
    assert tokens == t_tokens
    for a, b in zip(blocks + state, t_blocks + t_state):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    assert state or family != "falcon_h1"
