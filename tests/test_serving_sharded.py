"""Tensor-parallel paged serving: ``GenerationEngine(mesh=)`` (ISSUE-15).

The head-sharded engine must be a DROP-IN for the single-device one:

* **parity** — 16 mixed concurrent greedy requests (a shared system
  prompt riding the prefix cache + copy-on-write, per-request EOS
  early stop, mixed lengths) through the mp=2 sharded FUSED engine are
  token-identical to the single-device fused engine, with ZERO
  retraces once the buckets are warm and a clean ``analyze()`` bill on
  the shard_map'd fused step;
* **memory** — stats() and the HBM ledger bill per-device KV block
  bytes at exactly 1/mp of the single-device pool (the scale-out
  claim: mp devices pool mp x the KV budget);
* **policy** — block-pressure preemption (requeue + feed again) rides the
  sharded pool unchanged, still token-exact vs ``generate``.

Runs on the CPU mesh the tier-1 conftest forces
(``--xla_force_host_platform_device_count=8``).
"""
import threading

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from paddle_tpu.framework import trace_probe
from paddle_tpu.models import generate
from paddle_tpu.profiler import memory as _memory
from paddle_tpu.serving import GenerationEngine

import _toys

VOCAB = 96
MP = 2

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < MP,
    reason="needs >= 2 devices (the tier-1 conftest forces 8)")


def _mesh():
    return Mesh(np.array(jax.devices()[:MP]).reshape(MP), ("mp",))


@pytest.fixture(scope="module")
def make_model():
    """Factory for identically-trained tiny char GPTs. Sharding
    device_puts the params IN PLACE (``shard_params_megatron``), so a
    sharded engine gets a model of its OWN — seeded init + seeded data
    make every copy bit-identical to the one the single-device engines and
    ``generate`` share."""
    return _toys.new_char_gpt


def _prompt(rng, n):
    return rng.randint(1, VOCAB, n).astype(np.int32)


def _specs():
    """16 mixed requests through 4 slots: 6 share an 8-token system prompt
    (one whole block — prefix-cache hits, then copy-on-write when the
    tails diverge), 10 are random mixed lengths. EOS entries are patched
    in by the test (the token needs a trained model to pick). Until PR 45
    there were 32 (12 + 20) through 8 slots, with contexts of up to 28
    tokens: the shape of ``test_serving_engine.py``'s storm, which still
    runs it on one device. What is THIS file's is the same kinds of
    traffic through a mesh, and here a storm's cost is its step programs
    (3 to 9 s each to build, tens of ms to run): contexts stay within two
    blocks of 8, so that the table buckets are 1 and 2, and four slots
    keep every slot full for the whole storm at half the rows a launch."""
    rng = np.random.RandomState(2)
    sys_prompt = _prompt(rng, 8)
    specs = []
    for _ in range(6):
        tail = _prompt(rng, int(rng.randint(1, 5)))
        specs.append([np.concatenate([sys_prompt, tail]),
                      int(rng.randint(2, 5)), None])
    for _ in range(10):
        specs.append([_prompt(rng, int(rng.randint(2, 13))),
                      int(rng.randint(1, 5)), None])
    return specs


def _storm(eng, specs):
    outs = [None] * len(specs)

    def client(i):
        p, n, eos = specs[i]
        outs[i] = eng.submit(p, max_new_tokens=n, eos_token_id=eos)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(specs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [h.result(timeout=600) for h in outs]


def _warm(eng, specs):
    for p, n, eos in specs:
        eng.submit(p, max_new_tokens=n, eos_token_id=eos) \
           .result(timeout=600)


# ---------------------------------------------------------------------------
# parity + compile discipline + analyze + the 1/mp ledger (fused path)
# ---------------------------------------------------------------------------

class TestShardedFusedParity:
    def test_32_mixed_requests_sharded_equals_single(self, make_model):
        """The acceptance criterion: the same mixed concurrent
        greedy requests (prefix hits, COW, EOS early stop) through the
        single-device fused engine and the mp=2 sharded fused engine
        produce token-identical output; the storm causes ZERO retraces
        on the warm sharded engine; the shard_map'd fused step analyzes
        clean; and both stats() and the HBM ledger bill the sharded
        pool's per-device block bytes at exactly 1/mp."""
        specs = _specs()
        single_model = _toys.char_gpt()
        # per-request EOS on four mixed requests: the token the trained
        # model actually emits third, so both engines stop early at the
        # same position
        short = [i for i, (p, _, _) in enumerate(specs) if len(p) <= 8]
        assert len(short) >= 4
        for i in short[:4]:
            p = specs[i][0]
            ref = generate(single_model, p[None, :], max_new_tokens=8)
            specs[i] = [p, 8, int(ref.numpy()[0, len(p) + 2])]

        def mk_engine(model, mesh):
            return GenerationEngine(model, num_slots=4, max_len=48,
                                    min_bucket=8, block_size=8, mesh=mesh)

        single = mk_engine(single_model, None)
        single_outs = _storm(single, specs)
        single_stats = single.stats()
        single.close()

        eng = mk_engine(make_model(), _mesh())
        _warm(eng, specs)
        sharded_outs = _storm(eng, specs)
        report = eng.analyze()
        stats = eng.stats()
        led = _memory.ledger()
        capacity_on_ledger = led.get(f"{eng._pool.ledger_key}/capacity")
        eng.close()

        for sout, shout in zip(single_outs, sharded_outs):
            np.testing.assert_array_equal(shout, sout)
        # every sharded (q, table) bucket traced exactly ONCE with no
        # recorded retrace cause. (A bucket FIRST-compiling during the
        # storm is legal: the concurrent admission interleaving is
        # thread-timing-dependent, so the storm can reach a q bucket
        # the sequential warm wave never formed — same contract as the
        # spec-decode suite.)
        sites = {k: v for k, v in trace_probe.snapshot().items()
                 if k.startswith("serving/") and f"#{eng._eid}" in k}
        assert sites, "sharded serving probe sites missing"
        retraced = {k: v["traces"] for k, v in sites.items()
                    if v["traces"] != 1 or v["causes"]}
        assert not retraced, f"warm sharded buckets retraced: {retraced}"
        # the clean bill: donation-safe, host-sync-free sharded step
        assert report.ok(), report.table()
        assert "donation-safety" in report.passes_run
        assert "host-sync" in report.passes_run
        # the scale-out claim, on both surfaces: stats() and the ledger
        # bill PER-DEVICE bytes at exactly 1/mp of the single pool
        assert stats["mp"] == MP and stats["mp_axis"] == "mp"
        assert stats["kv_bytes_per_device"] == stats["kv_bytes"]["blocks"]
        assert stats["kv_bytes"]["blocks"] * MP \
            == single_stats["kv_bytes"]["blocks"]
        assert stats["kv_pool_capacity_bytes"] * MP \
            == single_stats["kv_pool_capacity_bytes"]
        assert capacity_on_ledger == stats["kv_pool_capacity_bytes"]
        # the shared system prompt really rode the prefix cache
        assert stats["prefix_hits"] > 0
        # every request retired, no block leaked
        assert stats["active_requests"] == 0
        assert stats["kv_blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# scheduler policy under block pressure: preemption rides the shards
# ---------------------------------------------------------------------------

class TestShardedPreemption:
    def test_block_pressure_preempts_and_finishes_exact(self, make_model):
        """Two long requests whose combined growth exceeds the block
        budget on the SHARDED pool: the youngest is preempted (replica
        page tables are host-side and replicated, so the requeue/replay
        machinery is untouched by the head sharding) and both still
        produce the exact ``generate`` sequence."""
        model = make_model()
        eng = GenerationEngine(model, num_slots=2, max_len=32,
                               block_size=8,
                               num_blocks=4, mesh=_mesh())
        pa = _prompt(np.random.RandomState(6), 4)
        pb = _prompt(np.random.RandomState(7), 4)
        ha = eng.submit(pa, max_new_tokens=24)
        hb = eng.submit(pb, max_new_tokens=24)
        oa = ha.result(timeout=600)
        ob = hb.result(timeout=600)
        stats = eng.stats()
        eng.close()
        assert stats["preempts"] >= 1
        ref_model = _toys.char_gpt()
        ra = generate(ref_model, pa[None, :], max_new_tokens=24)
        rb = generate(ref_model, pb[None, :], max_new_tokens=24)
        np.testing.assert_array_equal(oa, ra.numpy()[0])
        np.testing.assert_array_equal(ob, rb.numpy()[0])
        assert eng._pool.blocks_in_use == 0


# ---------------------------------------------------------------------------
# construction validation: fail fast, named errors
# ---------------------------------------------------------------------------

class TestShardedValidation:
    def test_mesh_rejects_quantized_blocks(self, make_model):
        with pytest.raises(ValueError, match="int8|quantiz"):
            GenerationEngine(make_model(), num_slots=2, max_len=32,
                             block_size=8,
                             kv_dtype="int8", mesh=_mesh())

    def test_mesh_axis_must_divide_heads(self, make_model):
        # tiny model has 4 heads; a 3-way mesh cannot split them
        if len(jax.devices()) < 3:
            pytest.skip("needs >= 3 devices")
        mesh3 = Mesh(np.array(jax.devices()[:3]).reshape(3), ("mp",))
        with pytest.raises(ValueError, match="head"):
            GenerationEngine(make_model(), num_slots=2, max_len=32,
                             block_size=8,
                             mesh=mesh3)
