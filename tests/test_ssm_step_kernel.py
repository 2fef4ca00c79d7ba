"""The Mamba-2 decode update as ONE Pallas kernel
(``paddle_tpu/ops/ssm.py:state_step``) against the plain ``jax.numpy``
statement of a step (``ssm_step``), interpreted on the CPU at toy sizes in
both served models' proportions: heads a group 16 (the published widths of
both) and 2 (both toys), state 128 lanes wide and 32. A launch mixes what
a launch can hold — a one-row sequence that continues, a FRESH one-row
sequence on a slot that still holds another request's state, an absent
slot, a sequence of several rows (the chunked scan's: it keeps its OLD
state here), pad rows — and the rows of the slots that do not step hold
NaN: whatever the kernel does not step comes back bit for bit. Every head
block ``step_head_block`` could pick is forced in turn, so that grids of
one, two and four blocks a slot — a block inside a group, a block of
whole groups — run the same cases.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import ssm as SSM
from paddle_tpu.ops.ragged_paged_attention import BLOCK_Q, ragged_layout

S = 5
Q_LENS, POS0 = [1, 1, 0, 5, 1], [40, 0, 0, 7, 3]
ONE, FRESH, ABSENT, LONGER, OTHER = range(S)


@pytest.fixture
def head_block(monkeypatch):
    """Force the kernel's head block; the block is read when
    ``_step_call`` is traced, so its jit cache is cleared on both sides."""
    def force(hb):
        monkeypatch.setattr(SSM, "step_head_block", lambda *_: hb)
        SSM._step_call.clear_cache()
    yield force
    monkeypatch.undo()
    SSM._step_call.clear_cache()


def _launch():
    """The launch's layout as the engine builds it and, from it, what
    ``ssm_scan`` hands the kernel: each slot's first row and what the
    slot does."""
    blk_seq, qstart, pos0, _, _ = ragged_layout(Q_LENS, POS0)
    Q = len(blk_seq) * BLOCK_Q
    valid = np.zeros(Q, bool)
    for s, n in enumerate(Q_LENS):
        valid[qstart[s]:qstart[s] + n] = True
    kv_len = np.asarray([p + n for p, n in zip(POS0, Q_LENS)], np.int32)
    lay = SSM.seq_layout(jnp.asarray(blk_seq), jnp.asarray(qstart),
                         jnp.asarray(pos0), jnp.asarray(kv_len),
                         jnp.asarray(valid), BLOCK_Q)
    how = jnp.where(lay.seq_len == 1, 1 + lay.seq_fresh.astype(jnp.int32), 0)
    return lay, Q, valid, how


def _inputs(Q, valid, H, P, N, G, L, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    # pad rows hold NaN: they are no slot's first row
    pad = lambda v: jnp.where(
        valid.reshape((-1,) + (1,) * (v.ndim - 1)), v, jnp.nan)
    x, dt = pad(f(Q, H, P)), pad(jax.nn.softplus(f(Q, H) - 2.0))
    b, c = pad(f(Q, G, N)), pad(f(Q, G, N))
    a = -jnp.exp(jnp.linspace(0.0, 2.0, H, dtype=jnp.float32))
    return x, dt, a, b, c, f(H), f(L, S + 1, H, P, N)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("H,P,N,G,hb", [
    (32, 8, 128, 2, 32), (32, 8, 128, 2, 16), (32, 8, 128, 2, 8),
    (16, 8, 32, 8, 16), (16, 8, 32, 8, 8), (4, 16, 32, 2, 4),
], ids=["group16-n128-hb32", "group16-n128-hb16", "group16-n128-hb8",
        "group2-n32-hb16", "group2-n32-hb8", "group2-n32-hb4"])
def test_the_kernel_is_one_step_of_the_recurrence_for_the_slots_that_step(
        head_block, H, P, N, G, hb, layer):
    head_block(hb)
    lay, Q, valid, how = _launch()
    assert how.tolist() == [1, 2, 0, 0, 1]
    x, dt, a, b, c, d, state = _inputs(Q, valid, H, P, N, G, 3, seed=H + hb)
    r0 = lay.seq_qstart
    y, new = SSM.state_step(state, layer, how, x[r0], dt[r0], a, b[r0],
                            c[r0], d)
    steps = np.asarray(how) > 0
    want_y, want = SSM.ssm_step(
        jnp.where((how == 2)[:, None, None, None], 0.0, state[layer, :S]),
        x[r0], dt[r0], a, b[r0], c[r0], d)
    np.testing.assert_allclose(new[layer, :S][steps], want[steps], atol=1e-6)
    # a sum of N products in another order: float32 rounding of values ~10
    np.testing.assert_allclose(y[steps], want_y[steps], rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(new[layer, ONE] - state[layer, ONE]).max()) > 0.01
    # the fresh slot started from zero, whatever it held
    np.testing.assert_allclose(
        new[layer, FRESH],
        (dt[r0[FRESH]][:, None] * x[r0[FRESH]])[:, :, None]
        * jnp.repeat(b[r0[FRESH]], H // G, axis=0)[:, None, :], atol=1e-6)
    # bit for bit: the absent slot, the longer sequence's (the chunked
    # scan has yet to read it), the row no slot owns, every other layer
    for s in (ABSENT, LONGER, S):
        np.testing.assert_array_equal(new[layer, s], state[layer, s])
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(new[other], state[other])
    assert bool(jnp.isfinite(new).all())
    assert float(jnp.abs(y[~steps]).max()) == 0.0


@pytest.mark.parametrize("hb", [8, 32])
def test_a_launch_through_the_kernel_and_the_loop_is_each_sequence_alone(
        head_block, hb):
    """``ssm_scan`` whole, the kernel's head block forced: the one-row
    sequences from the kernel, the longer one from the loop that reads the
    kernel's OUTPUT — each the sequential recurrence from its slot's state
    (zero where fresh)."""
    head_block(hb)
    H, P, N, G = 32, 8, 32, 2
    lay, Q, valid, _ = _launch()
    x, dt, a, b, c, d, state = _inputs(Q, valid, H, P, N, G, 2, seed=hb)
    clean = lambda v: jnp.where(jnp.isnan(v), 0.0, v)
    x, dt, b, c = clean(x), clean(dt), clean(b), clean(c)
    y, new = SSM.ssm_scan(x, dt, a, b, c, d, state, 1, lay, chunk=4)
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[1, ABSENT], state[1, ABSENT])
    for s, n in enumerate(Q_LENS):
        h = jnp.zeros((H, P, N)) if POS0[s] == 0 else state[1, s]
        for r in range(int(lay.seq_qstart[s]), int(lay.seq_qstart[s]) + n):
            want_y, h = SSM.ssm_step(h[None], x[r][None], dt[r][None], a,
                                     b[r][None], c[r][None], d)
            h = h[0]
            np.testing.assert_allclose(y[r], want_y[0], atol=1e-4)
        if n:
            np.testing.assert_allclose(new[1, s], h, atol=1e-4)
    assert float(jnp.abs(y[~valid]).max()) == 0.0


@pytest.mark.parametrize("H,P,N,G,hb", [
    (32, 128, 256, 2, 8),           # Falcon-H1: 131 KB a head, 1 MB a block
    (128, 64, 128, 8, 32),          # Nemotron-3: 32 KB a head, 1 MB a block
    (4, 16, 32, 2, 4),              # both toys: every head
    (24, 64, 128, 3, 24),           # three whole groups
    (64, 128, 256, 2, 8),           # a quarter of a group's heads
], ids=["falcon-h1", "nemotron3", "toy", "whole-groups", "inside-a-group"])
def test_the_head_block_follows_from_the_shapes(H, P, N, G, hb):
    got = SSM.step_head_block(H, P, N, G)
    assert got == hb and H % got == 0
    assert 4 * got * P * N * 4 <= SSM.STEP_VMEM_BUDGET
    assert got % (H // G) == 0 or (H // G) % got == 0


def test_a_block_too_fat_for_the_budget_is_the_smallest_that_tiles():
    # one head is 4 MB: nothing fits four times; 8 heads is what Mosaic
    # can tile of 16
    assert SSM.step_head_block(16, 512, 2048, 2) == 8


@pytest.mark.parametrize("change,match", [
    (dict(state=jnp.zeros((2, S + 1, 4, 16, 32), jnp.bfloat16)),
     "float32, not bfloat16"),
    (dict(state=jnp.zeros((2, S, 4, 16, 32), jnp.float32)),
     "does not go with"),
    (dict(b=jnp.zeros((S, 3, 32), jnp.float32)), "does not go with"),
    (dict(layer=2), "layer 2 out of range"),
], ids=["bf16-state", "no-spare-row", "groups-split-heads", "layer"])
def test_what_the_kernel_cannot_step_is_refused_by_name(change, match):
    z = lambda *s: jnp.zeros(s, jnp.float32)
    kw = dict(state=z(2, S + 1, 4, 16, 32), layer=1,
              how=jnp.ones(S, jnp.int32), x=z(S, 4, 16), dt=z(S, 4),
              a=z(4), b=z(S, 2, 32), c=z(S, 2, 32), d=z(4))
    kw.update(change)
    with pytest.raises(ValueError, match=match):
        SSM.state_step(**kw)


def test_the_analyzer_bills_the_state_once_where_it_is_donated():
    """The kernel's state output aliases its operand
    (``analysis/liveness.py``: an in-place pair): a DONATED state through
    two layers is one state at the peak — the plan gate would refuse the
    cells' 1.6 | 2.7 GB twice over —, a state the caller keeps is still
    charged twice, and the kernel's body is no program point."""
    from paddle_tpu.analysis import liveness
    H, P, N, G = 8, 16, 128, 2
    lay, Q, valid, how = _launch()
    x, dt, a, b, c, d, state = _inputs(Q, valid, H, P, N, G, 3, seed=1)
    nbytes = state.size * 4                                # 1.1 MiB
    r0 = lay.seq_qstart

    def two_layers(state, x, dt, b, c):
        for layer in (0, 2):
            y, state = SSM.state_step(state, layer, how, x[r0], dt[r0], a,
                                      b[r0], c[r0], d)
            x = x + y.sum() * 0
        return x, state

    kept = liveness.callable_liveness(two_layers, state, x, dt, b, c)
    donated = liveness.callable_liveness(two_layers, state, x, dt, b, c,
                                         donate_argnums=(0,))
    assert kept.static_peak_bytes >= 2 * nbytes
    assert nbytes <= donated.static_peak_bytes < nbytes + nbytes // 4
    assert not [p for p in donated.timeline
                if "(_step_kernel" in (p.source or "")]
