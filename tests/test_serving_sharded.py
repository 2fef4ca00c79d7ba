"""Tensor-parallel paged serving: ``GenerationEngine(mesh=)`` (ISSUE-15).

The head-sharded engine must be a DROP-IN for the single-device one:

* **parity** — 32 mixed concurrent greedy requests (a shared system
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

import paddle_tpu as paddle
from paddle_tpu.framework import trace_probe
from paddle_tpu.models import GPTConfig, GPTForPretraining, generate
from paddle_tpu.profiler import memory as _memory
from paddle_tpu.serving import GenerationEngine

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
    device_puts the params IN PLACE (``shard_params_megatron``), so the
    single-device and sharded engines must each get their OWN model —
    seeded init + seeded data make every copy bit-identical, and the
    few training steps give the logits clear argmax margins so greedy
    parity cannot flake on the psum's reduction order."""
    def make():
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=VOCAB, hidden_size=64,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=128, max_position_embeddings=64,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        model = GPTForPretraining(cfg)
        opt = paddle.optimizer.Adam(learning_rate=3e-3,
                                    parameters=model.parameters())
        corpus = ("the quick brown fox jumps over the lazy dog. "
                  "pack my box with five dozen liquor jugs. ") * 6
        data = np.frombuffer(corpus.encode(), np.uint8) \
                 .astype(np.int32) % VOCAB
        rng = np.random.RandomState(0)
        seq, batch = 24, 8
        for _ in range(30):
            starts = rng.randint(0, len(data) - seq - 1, batch)
            chunk = np.stack([data[s:s + seq + 1] for s in starts])
            loss, _ = model(
                paddle.to_tensor(chunk[:, :-1]),
                paddle.to_tensor(chunk[:, 1:].astype(np.int64)))
            loss.backward()
            opt.step()
            opt.clear_grad()
        model.eval()
        return model
    return make


def _prompt(rng, n):
    return rng.randint(1, VOCAB, n).astype(np.int32)


def _specs():
    """32 mixed requests: 12 share an 8-token system prompt (one whole
    block — prefix-cache hits, then copy-on-write when the tails
    diverge), 20 are random mixed lengths. EOS entries are patched in
    by the test (the token needs a trained model to pick)."""
    rng = np.random.RandomState(2)
    sys_prompt = _prompt(rng, 8)
    specs = []
    for _ in range(12):
        tail = _prompt(rng, int(rng.randint(1, 9)))
        specs.append([np.concatenate([sys_prompt, tail]),
                      int(rng.randint(2, 9)), None])
    for _ in range(20):
        specs.append([_prompt(rng, int(rng.randint(2, 21))),
                      int(rng.randint(1, 9)), None])
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
        """The acceptance criterion: the same 32 mixed concurrent
        greedy requests (prefix hits, COW, EOS early stop) through the
        single-device fused engine and the mp=2 sharded fused engine
        produce token-identical output; the storm causes ZERO retraces
        on the warm sharded engine; the shard_map'd fused step analyzes
        clean; and both stats() and the HBM ledger bill the sharded
        pool's per-device block bytes at exactly 1/mp."""
        specs = _specs()
        single_model = make_model()
        # per-request EOS on four mixed requests: the token the trained
        # model actually emits third, so both engines stop early at the
        # same position
        for i in (3, 9, 17, 25):
            p = specs[i][0]
            ref = generate(single_model, p[None, :], max_new_tokens=8)
            specs[i] = [p, 8, int(ref.numpy()[0, len(p) + 2])]

        def mk_engine(model, mesh):
            return GenerationEngine(model, num_slots=8, max_len=48,
                                    min_bucket=8, block_size=8, mesh=mesh)

        single = mk_engine(single_model, None)
        _warm(single, specs)
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
        ref_model = make_model()
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
