"""SDAR-MoE (grouped-query attention, softmax-routed experts, generation
by diffusion over blocks) against its plain reference
(``benchmark/lib/reference_sdar.py``: float32, ``highest``, no cache, no
kernels, the source's generation loop as written), at ``SDARConfig.tiny()``
sizes with the benchmark's seeded weights, on the CPU in float32. Logits
are compared, and the tokens and the ORDER they were fixed in with the
reference's own loop. The tolerance is ``tests/test_axk1.py``'s, for its
reason: float32 sums in another order differ by ~1e-6 of a unit-RMS
value, so 1e-4 on logits of spread 1 is two orders of room.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import family_sdar as F
from benchmark.lib import reference_sdar as R
from paddle_tpu.models import axk1 as AX
from paddle_tpu.models import sdar as SD
from paddle_tpu.models.decoder_spec import GenerationRule, serving_decoder
from paddle_tpu.serving import GenerationEngine, scheduler

import _toys

SEED = _toys.SEEDS["sdar"]
ORDER_OF_SUM = 1e-4        # see the module doc

# The two engines the tests that only serve requests share (``engines``
# hands each out drained, its pool and trie as new): ONE slot, so that a
# request's launches are the only ones, and TWO. Contexts of up to eight
# cache blocks of 8; chunks of at most 12 tokens, which end inside a block.
ONE = dict(num_slots=1, max_len=64, block_size=8, prefill_budget=12)
TWO = dict(num_slots=2, max_len=64, block_size=8, prefill_budget=12)


def _model(**over):
    """The ``model`` group of a configuration at toy sizes."""
    return _toys.config("sdar", **over)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def net():
    return _toys.seeded("sdar")


@pytest.fixture(scope="module")
def make():
    return _toys.weights("sdar")


def _blocks(p, n):
    """Blocks of 4 a request of ``p`` prompt tokens and ``n`` new ones
    denoises: all but the last are committed."""
    return -(-(p + n) // 4) - p // 4


def _ids(rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        1, 250, size=(rows, length)).astype(np.int32)


# -- 1. the layer ----------------------------------------------------------------

def test_the_programs_forward_pass_is_the_references(net, make, model):
    """Grouped heads (8 query heads on 2 KV heads), q_norm / k_norm,
    half-split rotary positions, softmax top-4 of 16, the block mask: the
    program's plain ``forward`` against the reference's, 41 positions (a
    last block cut short)."""
    ids = _ids(2, 41)
    want = R.logits(make, model, ids)
    got = np.array(net(jnp.asarray(ids))._data)
    got[..., model["mask_token_id"]] = -np.inf      # the reference's column
    assert float(want[np.isfinite(want)].std()) > 0.5   # logits of spread ~1
    np.testing.assert_allclose(got, want, atol=ORDER_OF_SUM)


def test_a_row_sees_its_whole_block_and_nothing_after_it(net):
    """Changing a token changes the logits of every row of its block and
    of the blocks after it, and of no row before its block."""
    ids = _ids(1, 16, seed=4)
    other = ids.copy()
    other[0, 9] = (other[0, 9] + 7) % 250 + 1        # block 2 (rows 8-11)
    a = np.asarray(net(jnp.asarray(ids))._data)[0]
    b = np.asarray(net(jnp.asarray(other))._data)[0]
    moved = np.abs(a - b).max(axis=-1) > 1e-6
    assert not moved[:8].any() and moved[8:].all()


def test_rotary_positions_turn_lane_i_with_lane_i_plus_half():
    x = np.random.default_rng(3).standard_normal((5, 2, 16)).astype(np.float32)
    pos = np.asarray([0, 1, 7, 100, 3000])
    got = np.asarray(SD.rope_half_split(jnp.asarray(x), jnp.asarray(pos),
                                        1e6))
    inv = 1e6 ** (-np.arange(8) / 8.0)
    ang = pos[:, None] * inv[None, :]
    a, b = x[..., :8], x[..., 8:]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)    # position 0


def test_softmax_router_scores_top_k_and_renormalisation():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((5, 64)).astype(np.float32)
    wg = (rng.standard_normal((16, 64)) / 8).astype(np.float32)
    idx, w, scores = AX.route_top_k(jnp.asarray(x), jnp.asarray(wg), 4, 1.0,
                                    scoring="softmax")
    z = x.astype(np.float64) @ wg.T
    by_hand = np.exp(z - z.max(-1, keepdims=True))
    by_hand /= by_hand.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(scores), by_hand, atol=1e-6)
    for r in range(5):
        top = np.argsort(-by_hand[r])[:4]
        assert sorted(np.asarray(idx[r])) == sorted(top)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        AX.route_top_k(jnp.asarray(x), jnp.asarray(wg), 4, 1.0,
                       scoring="tanh")


def test_a_near_tie_is_decided_by_float32_scores():
    """Two experts whose router logits differ by one bfloat16 step: their
    softmax scores round to the same bfloat16 value, so bfloat16 scores
    would tie and top-k keep the LOWER index; float32 scores keep the
    higher score, as the reference does."""
    bf = jnp.bfloat16
    x = jnp.zeros((1, 64), bf).at[0, 0].set(0.25)
    wg = jnp.full((16, 64), -4.0, bf)                # scores 0.15287, 0.15302
    wg = wg.at[3, 0].set(0.50390625).at[9, 0].set(0.5078125)   # one step up
    idx, _, scores = AX.route_top_k(x, wg, 1, 1.0, scoring="softmax")
    assert scores.dtype == jnp.float32
    assert float(scores[0, 9]) > float(scores[0, 3])
    assert scores[0, 9].astype(bf) == scores[0, 3].astype(bf)
    assert int(idx[0, 0]) == 9
    d = R.Dims.of(_model(num_experts_per_tok=1))
    ref_idx, _, _ = R.route(d, wg.astype(jnp.float32), x.astype(jnp.float32))
    assert int(ref_idx[0, 0]) == 9


@pytest.mark.parametrize("parts", [[(0, 16)], [(0, 4), (4, 8), (8, 12),
                                               (12, 16)], [(0, 10), (10, 16)]],
                         ids=["all-held", "four-shares", "two-uneven-shares"])
def test_the_shares_parts_add_up_to_the_whole_layer(parts):
    """Every expert held, or ranges of them on several chips: the shares'
    routed parts, summed, are the uncut reference's expert layer (there is
    no shared expert to count once)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    valid = jnp.ones(24, bool)
    whole = _model()
    lw = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        F.Weights(SEED, whole, "float32").layer(1))
    with jax.default_matmul_precision("highest"):
        want, _ = R.expert_ffn(R.Dims.of(whole), lw, x)
    total, pairs = 0.0, 0
    for lo, hi in parts:
        layer = F.build_lm(_model(experts_held=[lo, hi]), SEED,
                           "float32").layers[1]
        out, counters = layer.ffn.apply(x, valid)
        total = total + out
        pairs += int(counters[0])
        assert int(counters[2]) == 24
    assert pairs == 24 * 4                 # every (row, expert) pair, once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=ORDER_OF_SUM)


# -- 2. generation through the paged cache -----------------------------------------

def _serial(monkeypatch):
    """One launch in flight: every turn lands its own launch."""
    real = scheduler.Scheduler._chunked_cycle
    monkeypatch.setattr(scheduler.Scheduler, "_chunked_cycle",
                        lambda self, cold=False: real(self, True))


@contextlib.contextmanager
def _spy_dispatches(eng):
    """Record every launch the scheduler dispatches inside the block:
    ``[(plan, the slots whose state the step is told to take from the
    launch in flight)]``."""
    seen, real = [], eng._sched._do_chunked

    def spy(active, plan, prev):
        seen.append((dict(plan), set() if prev is None else set(prev[1])))
        return real(active, plan, prev)

    eng._sched._do_chunked = spy
    try:
        yield seen
    finally:
        eng._sched._do_chunked = real


def _launches(eng, since=0):
    """The records of the launches ``eng`` made after its turn ``since``
    (``eng._sched._cycle`` when the test took the engine), once the last
    one has entered the ring."""
    _toys.settle(eng)
    jax.effects_barrier()
    return [c for c in eng.flight_recorder.snapshot()["cycles"]
            if c.get("launch_q") and c["cycle"] > since]


@pytest.fixture(scope="module")
def heard(model):
    """``(net, seen)``: a second build of the toy whose head reports its
    output of every launch into ``seen`` — the report is traced into the
    step programs, so the net is this fixture's alone."""
    net = F.build_lm(model, SEED, "float32")
    seen, real = [], net.logits

    def recording(hidden):
        out = real(hidden)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), out._data)
        return out

    net.logits = recording
    return net, seen


def _served(engines, heard, prompt, n):
    """One request through the one-slot engine with the head's output of
    every launch recorded: ``(tokens, passes, [logits of the block's rows
    a denoising launch], cycle records, dispatches)``."""
    net, seen = heard
    eng = engines(net, **ONE)
    jax.effects_barrier()
    seen.clear()
    since = eng._sched._cycle
    with _spy_dispatches(eng) as dispatches:
        h = eng.submit(prompt, n)
        toks = [int(t) for t in h.stream()]
        cycles = _launches(eng, since)
    assert len(seen) == len(cycles) == len(dispatches)
    denoise = [lg[:, 0] for lg, c in zip(seen, cycles)
               if c.get("denoise_slots")]
    return toks, list(h.trace.token_passes), denoise, cycles, dispatches


@pytest.mark.parametrize("in_flight", [1, 2])
@pytest.mark.parametrize("p,n", [(12, 8), (13, 9), (14, 6), (15, 7), (3, 5)],
                         ids=["residue0", "residue1", "residue2", "residue3",
                              "no-prefill"])
def test_every_pass_through_the_paged_cache_is_the_references(
        engines, heard, make, model, monkeypatch, p, n, in_flight):
    """Prompts of every residue mod 4 (and one shorter than a block: no
    prefill at all), fed in chunks of 12 that end inside a cache block of
    8, outputs that are and are not multiples of 4: the logits of every
    denoising pass, the tokens and the pass each was fixed in equal the
    reference's generation loop — with one and with two launches in
    flight. A block costs its slot one launch a denoising pass: its
    commit rides with the next block's first pass, and with two launches
    in flight the ride reads the finished block's tokens from the
    un-fetched result of the launch before it."""
    if in_flight == 1:
        _serial(monkeypatch)
    prompt = _ids(1, p, seed=p)[0].tolist()
    toks, passes, logits, cycles, dispatches = _served(engines, heard,
                                                       prompt, n)
    want = R.generate(make, model, prompt, n)
    assert toks == want["tokens"] and passes == want["passes"]
    assert model["mask_token_id"] not in toks
    assert len(logits) == len(want["logits"])
    for got, (_, _, ref) in zip(logits, want["logits"]):
        got = got.copy()
        got[:, model["mask_token_id"]] = -np.inf
        np.testing.assert_allclose(got, ref, atol=ORDER_OF_SUM)
    overlapped = [c["overlapped"] for c in cycles]
    assert any(overlapped) == (in_flight == 2)
    # the launches' counters: a pass fixes one position; a block of four
    # takes FOUR launches of its slot (less the passes the prompt's
    # leftover tokens save), a request of n blocks 4 n; every block but
    # the last is committed, by a ride and never alone
    n_passes = len(want["logits"])
    blocks = _blocks(p, n)
    assert n_passes == 4 * blocks - p % 4
    assert sum(c.get("tokens_fixed", 0) for c in cycles) == n_passes
    decode = [c for c in cycles if not c.get("chunk_tokens")]
    assert len(decode) == n_passes
    assert all(c["denoise_slots"] == 1 and c["commit_slots"] == 0
               for c in decode)
    rides = [c for c in decode if c["ride_slots"]]
    assert len(rides) == blocks - 1
    assert all(c["launch_rows"] == (8 if c["ride_slots"] else 4)
               for c in decode)
    assert all(c["moe_rows"] == 2 * c["launch_rows"] for c in cycles)
    # ... and the last block takes no commit: nothing follows its passes
    assert not decode[-1]["ride_slots"] and decode[-1]["emitted"]
    # where the finished block's tokens came from: the un-fetched result
    # of the launch in flight, whenever there is one (the launch that
    # opens a busy stretch lands in its own turn, so from the third on)
    ride_src = [(src, c) for (plan, src), c in zip(dispatches, cycles)
                if plan.get(0) == 8]
    assert [c for _, c in ride_src] == rides
    assert all(src == ({0} if c["overlapped"] else set())
               for src, c in ride_src)
    assert all(c["overlapped"] == (in_flight == 2)
               for c in rides if c["cycle"] > cycles[1]["cycle"])


def test_two_fixed_a_pass_follows_the_references_loop(make, monkeypatch):
    """``denoising_steps`` 2 on blocks of 4: a pass fixes its two most
    confident positions."""
    model = _model(denoising_steps=2)
    net = F.build_lm(model, SEED, "float32")
    prompt = _ids(1, 9, seed=5)[0].tolist()
    eng = GenerationEngine(net, **ONE)
    h = eng.submit(prompt, 10)
    toks = [int(t) for t in h.stream()]
    eng.close()
    want = R.generate(F.Weights(SEED, model, "float32"), model, prompt, 10)
    assert toks == want["tokens"]
    assert list(h.trace.token_passes) == want["passes"]
    assert set(want["passes"]) == {0, 1}


def test_a_batch_of_mixed_requests_agrees_and_the_trie_holds_prompts_only(
        net, make, model):
    """Seven requests on four slots, mixed residues and lengths, chunks
    beside blocks in one launch: every request's text and order are the
    reference's, and the prefix trie was handed whole committed cache
    blocks of PROMPTS only — never a block that holds generated or masked
    positions."""
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(1, 250, p).tolist(), n) for p, n in
            [(5, 9), (8, 8), (3, 6), (17, 7), (9, 4), (10, 12), (26, 5)]]
    eng = GenerationEngine(net, num_slots=4, max_len=64, block_size=8,
                           prefill_budget=8)
    handles = [eng.submit(p, n) for p, n in reqs]
    outs = [[int(t) for t in h.stream()] for h in handles]
    keys = list(eng._pool._trie)
    cycles = _launches(eng)
    eng.close()
    for (p, n), h, got in zip(reqs, handles, outs):
        want = R.generate(make, model, p, n)
        assert got == want["tokens"]
        assert list(h.trace.token_passes) == want["passes"]
    assert keys and all(len(k) % 8 == 0 for k in keys)
    prompts = [tuple(p) for p, _ in reqs]
    assert all(any(k == p[:len(k)] for p in prompts) for k in keys)
    # the records add up: a pass fixes a position, so the slot-passes are
    # the positions the prompts left open; all are emitted but the
    # surplus of the last blocks; a ride is counted once, as a denoising
    # pass, and stands for every block but a request's last
    total = lambda key: sum(c.get(key, 0) for c in cycles)
    opened = sum(-(-(len(p) + n) // 4) * 4 - len(p) for p, n in reqs)
    surplus = sum(-(len(p) + n) % 4 for p, n in reqs)
    assert total("denoise_slots") == total("tokens_fixed") == opened
    assert total("emitted") == sum(n for _, n in reqs) == opened - surplus
    assert total("ride_slots") == sum(_blocks(len(p), n) - 1 for p, n in reqs)
    assert total("commit_slots") == 0 and total("late_rows") == 0
    assert all(c["ride_slots"] <= c["denoise_slots"] for c in cycles
               if "denoise_slots" in c)


def test_a_commit_with_no_next_rows_in_its_launch_rides_alone(
        engines, net, make, model, monkeypatch):
    """The degenerate ride: were a finished block's slot handed its B rows
    only, the launch commits alone (no token, ``commit_slots``), the next
    block opens a launch later and the text is still the reference's —
    the same operands and the same step, five launches a block."""
    real = scheduler.Scheduler._chunk_plan
    monkeypatch.setattr(
        scheduler.Scheduler, "_chunk_plan",
        lambda self: {s: min(n, 4) if not self._slots[s].pending_feed else n
                      for s, n in real(self).items()})
    reqs = [(_ids(1, p, seed=p)[0].tolist(), n) for p, n in [(13, 9), (6, 8)]]
    eng = engines(net, **TWO)
    since = eng._sched._cycle
    handles = [eng.submit(p, n) for p, n in reqs]
    outs = [[int(t) for t in h.stream()] for h in handles]
    cycles = _launches(eng, since)
    for (p, n), h, got in zip(reqs, handles, outs):
        want = R.generate(make, model, p, n)
        assert got == want["tokens"]
        assert list(h.trace.token_passes) == want["passes"]
    total = lambda key: sum(c.get(key, 0) for c in cycles)
    assert total("ride_slots") == 0
    assert total("commit_slots") == 4 == sum(_blocks(len(p), n) - 1
                                             for p, n in reqs)
    assert total("emitted") == 17


def test_a_request_preempted_inside_a_block_resumes_and_still_agrees(
        net, make, model):
    """Two requests whose growth exceeds the pool: the younger is
    preempted between two passes of a block, re-admitted (its emitted
    tokens fed again, the block all masked) and both stay the reference's
    own text, fixed in the reference's order."""
    pa, pb = _ids(1, 6, seed=61)[0].tolist(), _ids(1, 7, seed=62)[0].tolist()
    eng = GenerationEngine(net, num_slots=2, max_len=32, block_size=8,
                           num_blocks=4, prefill_budget=16)
    ha, hb = eng.submit(pa, 22), eng.submit(pb, 22)
    oa = [int(t) for t in ha.stream()]
    ob = [int(t) for t in hb.stream()]
    preempts = eng.stats()["preempts"]
    eng.close()
    assert preempts >= 1
    for p, o, h in ((pa, oa, ha), (pb, ob, hb)):
        want = R.generate(make, model, p, 22)
        assert o == want["tokens"]
        assert list(h.trace.token_passes) == want["passes"]
    assert eng._pool.blocks_in_use == 0


def test_a_request_preempted_at_a_ride_resumes_and_still_agrees(
        engines, net, make, model, monkeypatch):
    """The younger of two requests is preempted in the turn that planned
    its ride (its block finished and emitted, not committed): the pipeline
    is drained, the request fed again from its emitted tokens, and both
    texts and orders stay the reference's."""
    real = scheduler.Scheduler._prepare_chunked
    state = {}

    def prepare(self, plan):
        young = max(self._slots, key=lambda s: self._slots[s].id)
        req = self._slots[young]
        if not state and len(self._slots) == 2 and plan.get(young) == 8 \
                and not req.pending_feed:
            self._drain()
            state.update(emitted=req.emitted, passes=req.block_pass)
            self._preempt_youngest()
            plan = {s: n for s, n in plan.items() if s in self._slots}
        return real(self, plan)

    monkeypatch.setattr(scheduler.Scheduler, "_prepare_chunked", prepare)
    pa, pb = _ids(1, 6, seed=71)[0].tolist(), _ids(1, 9, seed=72)[0].tolist()
    eng = engines(net, **TWO)
    since, preempts = eng._sched._cycle, eng.stats()["preempts"]
    ha, hb = eng.submit(pa, 14), eng.submit(pb, 13)
    oa = [int(t) for t in ha.stream()]
    ob = [int(t) for t in hb.stream()]
    assert eng.stats()["preempts"] - preempts == 1
    cycles = _launches(eng, since)
    # preempted with its first block (positions 9-11) whole and emitted
    assert state == {"emitted": 3, "passes": 3}
    for p, o, h in ((pa, oa, ha), (pb, ob, hb)):
        want = R.generate(make, model, p, len(o))
        assert o == want["tokens"]
        assert list(h.trace.token_passes) == want["passes"]
    assert (len(oa), len(ob)) == (14, 13)
    assert eng._pool.blocks_in_use == 0
    assert sum(c.get("commit_slots", 0) for c in cycles) == 0


@pytest.mark.parametrize("in_flight", [1, 2])
def test_a_request_cancelled_at_a_ride_retires_and_its_rows_are_late(
        engines, net, make, model, monkeypatch, in_flight):
    """``cancel()`` as the ride is dispatched. With two launches in flight
    the launch before it lands afterwards, finds the cancel and retires
    the request (the finished block is not emitted); the ride's 2 B rows
    land for a request that has ended: late rows, dropped and counted,
    and the slot serves the next request. With one launch in flight the
    ride itself lands the cancel, and nothing is late."""
    if in_flight == 1:
        _serial(monkeypatch)
    eng = engines(net, **ONE)
    since, late = eng._sched._cycle, eng._sched.late_rows
    real = eng._sched._do_chunked
    prompt = _ids(1, 8, seed=81)[0].tolist()

    def cancelling(active, plan, prev):
        if plan.get(0) == 8 and not active[0].pending_feed:
            active[0].cancel()
        return real(active, plan, prev)

    eng._sched._do_chunked = cancelling
    try:
        h = eng.submit(prompt, 12)
        with pytest.raises(scheduler.RequestCancelled):
            got = []
            for t in h.stream():
                got.append(int(t))
    finally:
        eng._sched._do_chunked = real
    again = [int(t) for t in eng.submit(prompt, 12).stream()]
    cycles = _launches(eng, since)
    want = R.generate(make, model, prompt, 12)["tokens"]
    # one launch in flight: the block was emitted a turn before the ride
    assert got == ([] if in_flight == 2 else want[:4])
    assert again == want
    assert sum(c["late_rows"] for c in cycles) == (8 if in_flight == 2 else 0)
    assert eng._sched.late_rows - late == (8 if in_flight == 2 else 0)
    assert eng._pool.blocks_in_use == 0


@pytest.mark.parametrize("in_flight", [1, 2])
def test_a_request_that_ends_inside_a_block_takes_no_ride_after_it(
        engines, net, make, model, monkeypatch, in_flight):
    """``max_new_tokens`` ends inside the second block: its surplus is
    denoised and dropped, no commit and no ride follow it, nothing is
    late. An EOS in the first block is learned a launch late: with two
    launches in flight the ride behind that block has been dispatched and
    its 2 B rows are late rows."""
    if in_flight == 1:
        _serial(monkeypatch)
    prompt = _ids(1, 12, seed=91)[0].tolist()
    want = R.generate(make, model, prompt, 10)["tokens"]
    eng = engines(net, **ONE)
    since, late = eng._sched._cycle, eng._sched.late_rows
    toks = [int(t) for t in eng.submit(prompt, 6).stream()]
    ended = eng._sched.late_rows - late
    at = max(i for i in range(4) if want[i] not in want[:i])
    eos = [int(t) for t in eng.submit(prompt, 10,
                                      eos_token_id=want[at]).stream()]
    cycles = _launches(eng, since)
    assert toks == want[:6] and ended == 0
    assert eos == want[:at + 1]
    first = [c for c in cycles if not c.get("chunk_tokens")][:8]
    assert [c["ride_slots"] for c in first] == [0, 0, 0, 0, 1, 0, 0, 0]
    assert [c["emitted"] for c in first] == [0, 0, 0, 4, 0, 0, 0, 2]
    assert sum(c["tokens_fixed"] for c in first) == 8
    assert eng._sched.late_rows - late == (8 if in_flight == 2 else 0)
    assert eng._pool.blocks_in_use == 0


def test_a_shared_prefix_is_served_from_the_trie(engines, net, make, model):
    pre = _ids(1, 24, seed=9)[0].tolist()
    eng = engines(net, **TWO)
    before = eng.stats()
    first = [int(t) for t in eng.submit(pre + [5, 6], 6).stream()]
    again = [int(t) for t in eng.submit(pre + [7, 8, 9], 6).stream()]
    st = eng.stats()
    assert st["prefix_hits"] - before["prefix_hits"] >= 1
    assert st["prefill_tokens_saved"] - before["prefill_tokens_saved"] >= 16
    assert first == R.generate(make, model, pre + [5, 6], 6)["tokens"]
    assert again == R.generate(make, model, pre + [7, 8, 9], 6)["tokens"]


def test_the_teacher_forced_check_reads_zero_on_the_programs_own_text(
        engines, net, make, model):
    """What the cell's ``correct`` computes: on float32 against float32
    every served token is the reference's first choice at the pass that
    fixed it, and every pass fixed the reference's most confident
    position; a token altered after the fact is seen."""
    prompt = _ids(1, 14, seed=8)[0].tolist()
    h = engines(net, **ONE).submit(prompt, 17)
    toks = [int(t) for t in h.stream()]
    passes = list(h.trace.token_passes)
    kw = dict(width=32, states=32, q_block=16, states_per_call=8,
              head_rows=64)
    out = R.served_margins(make, model, [(prompt, toks, passes)], **kw)
    # the last block (positions 28-31) ends in a surplus position the
    # record does not have: positions 14..27 are compared
    assert out["gap"].size == 14 and out["order_gap"].size == 14
    assert float((out["gap"] / out["std"]).max()) < ORDER_OF_SUM
    assert float(out["order_gap"].max()) < ORDER_OF_SUM
    wrong = list(toks)
    wrong[5] = (wrong[5] + 1) % 250 + 1
    bad = R.served_margins(make, model, [(prompt, wrong, passes)], **kw)
    assert float((bad["gap"] / bad["std"]).max()) > 0.1


def test_the_request_lane_of_a_profile_shows_blocks(engines, net, tmp_path):
    """A finished request exports a span a block, from the block before it
    to the stamp its tokens share, with the passes that fixed them."""
    import json
    from paddle_tpu import profiler
    eng = engines(net, **ONE)
    with profiler.profile() as sess:
        toks = list(eng.submit(_ids(1, 9, seed=3)[0].tolist(), 7).stream())
        _toys.settle(eng)
    with open(sess.export_chrome_trace(str(tmp_path / "blocks.json"))) as f:
        evs = json.load(f)["traceEvents"]
    blocks = [e for e in evs if e.get("ph") == "X" and e["name"] == "block"
              and e["cat"] == "serving/request"]
    # positions 9-11 (one prompt leftover: 3 passes), then 12-15
    assert len(toks) == 7 and [e["args"]["tokens"] for e in blocks] == [3, 4]
    assert sorted(blocks[0]["args"]["fixed_in_pass"]) == [0, 1, 2]
    assert sorted(blocks[1]["args"]["fixed_in_pass"]) == [0, 1, 2, 3]


# -- 3. the decoder spec, the tile law and the refusals ---------------------------

def test_the_decoder_spec_says_what_each_model_is(net):
    sd = serving_decoder(net).spec
    assert sd.attention == "full" and {ls.ffn for ls in sd.layers} == {"routed"}
    assert (sd.cache.rows, sd.cache.lanes) == (2, 32)     # KV heads, 2 x Dh
    assert sd.generation == GenerationRule(4, 4, 255)
    assert sd.generation.passes(4) == 4 and sd.generation.passes(1) == 1
    big = SD.SDARConfig()
    assert (big.num_key_value_heads, 2 * big.head_dim) == (4, 256)
    gpt = serving_decoder(_toys.default("gpt2")).spec
    ax = serving_decoder(_toys.default("axk1")).spec
    assert gpt.generation.block_length == ax.generation.block_length == 1
    with pytest.raises(ValueError, match="mask_token_id"):
        GenerationRule(block_length=4, denoising_steps=4)
    with pytest.raises(ValueError, match="multiple of"):
        GenerationRule(block_length=4, denoising_steps=3, mask_token_id=1)


def test_the_tile_law_covers_a_head_of_128(monkeypatch):
    from paddle_tpu.ops import kv_append as KA
    from paddle_tpu.ops import ragged_paged_attention as RPA
    monkeypatch.setattr(RPA, "_interpret", lambda: False)    # as on a TPU
    RPA.check_kv_tile("bfloat16", 16, 128)                   # 256 lanes
    RPA.check_kv_tile("bfloat16", 16, lanes=256)
    with pytest.raises(ValueError, match="128-lane"):
        RPA.check_kv_tile("bfloat16", 16, 48)
    # 4 KV heads of 128: a block is 32 KB, the walk fetches 8 at a time
    assert RPA.kv_group_blocks(4, 16, 128, "bfloat16") == 8
    assert KA.append_ring_blocks(4, 16, 128, "bfloat16") == KA.APPEND_RING_MAX
    with pytest.raises(ValueError, match="never straddles"):
        RPA.ragged_paged_attention(
            jnp.zeros((2, 8, 16)), jnp.zeros((1, 3, 2, 8, 32)), 0,
            np.zeros(1, np.int32), np.zeros(1, np.int32),
            np.zeros(1, np.int32), np.zeros((1, 1), np.int32),
            np.zeros(1, np.int32), np.zeros(1, np.int32), mask_block=3)
    with pytest.raises(ValueError, match="multiple of the pool's KV heads"):
        RPA.ragged_paged_attention(
            jnp.zeros((3, 8, 16)), jnp.zeros((1, 3, 2, 8, 32)), 0,
            np.zeros(1, np.int32), np.zeros(1, np.int32),
            np.zeros(1, np.int32), np.zeros((1, 1), np.int32),
            np.zeros(1, np.int32), np.zeros(1, np.int32))


@pytest.mark.parametrize("kwargs,match", [
    (dict(spec_draft="auto"), "spec_draft"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(kv_dtype="int8"), "int8/fp8 KV blocks"),
    (dict(mesh="a mesh"), "tensor-parallel"),
    (dict(block_size=8, max_len=30), None),                  # accepted
])
def test_what_block_generation_cannot_do_yet_is_refused_by_name(net, kwargs,
                                                                match):
    kw = dict(num_slots=2, max_len=32, block_size=8)
    kw.update(kwargs)
    if match is None:
        GenerationEngine(net, **kw).close()
        return
    with pytest.raises(ValueError, match=match):
        GenerationEngine(net, **kw)


def test_a_threshold_schedule_and_sampling_are_refused(engines, net, model):
    with pytest.raises(ValueError, match="low_confidence_dynamic"):
        SD.SDARConfig.tiny(remasking="low_confidence_dynamic").generation
    wide = F.build_lm(_model(block_length=16, denoising_steps=16), SEED,
                      "float32")
    with pytest.raises(ValueError, match="never straddles a cache block"):
        GenerationEngine(wide, num_slots=1, max_len=32, block_size=8)
    with pytest.raises(ValueError, match="do_sample"):
        engines(net, **ONE).submit([1, 2, 3], 4, do_sample=True)
