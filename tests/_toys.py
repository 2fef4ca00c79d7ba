"""The toy models the serving tests run, and the engines they are served
through — beside ``_mock_serving.py``, which is the scheduler's model-free
device. One place, so that a new family is one entry here and a test asks
for a toy by its family's name.

* ``served_model``: the char GPT trained for a few steps (a fixture; every
  test file that needs clear argmax margins used to train its own).
* ``default(family)``: the family's own class at its ``Config.tiny()``,
  weights from its own initialiser — what the step programs' recorded
  signatures and sections are taken on (``new_default``: a copy of one's
  own, for a ``mesh=`` engine, which places its model's parameters).
* ``config(family, **over)`` / ``seeded(family)`` / ``weights(family)``:
  the ``model`` group of a benchmark configuration at toy sizes, the
  program holding the benchmark's seeded weights in float32, and the
  ``Weights`` the plain references (``benchmark/lib/reference_*.py``) read.
* ``engines``: a module-scoped fixture; ``engines(model, **arguments)`` is
  the ``GenerationEngine`` of those arguments, built once a module and
  closed with it, handed out drained and with a fresh pool. On the CPU an
  engine's cost is its step programs (every ``(Q, T)`` a launch needs is
  traced, lowered and compiled as interpreted kernels, seconds each, and
  they belong to the engine), so a test that only submits requests and
  compares what comes back takes a shared engine at one of its file's few
  shapes and reads counters as differences; a test whose subject is a new
  engine's counters or recorder, or the engine's own close, still builds
  its own — at one of those shapes.

Everything is made once a process (``functools.cache``): a worker of the
suite runs many files, and a toy does not change.
"""
import functools
import importlib
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle

VOCAB = 96                 # the char GPT's

_DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "data")

# the seed of each family's benchmark weights in its tests
SEEDS = {"axk1": 2 ** 31 + 77, "sdar": 2 ** 31 + 33, "mimo": 2 ** 31 + 35,
         "falcon_h1": 2 ** 31 + 40, "lfm2": 2 ** 31 + 42,
         "longcat": 2 ** 31 + 46, "nemotron_h": 2 ** 31 + 50}


def new_char_gpt():
    """A tiny char GPT trained for a few steps: trained logits have clear
    argmax margins, so greedy parity between two programs (the engine's
    step and ``generate``'s loop, speculative and plain, tiered and not)
    cannot flake on numeric noise. Seeded init and seeded data: every
    copy is bit-identical (a sharded engine places its model's parameters
    IN PLACE, so it takes a copy of its own)."""
    from paddle_tpu.models import GPTConfig, GPTForPretraining
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=128,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.Adam(learning_rate=3e-3,
                                parameters=model.parameters())
    corpus = ("the quick brown fox jumps over the lazy dog. "
              "pack my box with five dozen liquor jugs. ") * 6
    data = np.frombuffer(corpus.encode(), np.uint8).astype(np.int32) % VOCAB
    rng = np.random.RandomState(0)
    seq, batch = 24, 8
    for _ in range(30):
        starts = rng.randint(0, len(data) - seq - 1, batch)
        chunk = np.stack([data[s:s + seq + 1] for s in starts])
        loss, _ = model(paddle.to_tensor(chunk[:, :-1]),
                        paddle.to_tensor(chunk[:, 1:].astype(np.int64)))
        loss.backward()
        opt.step()
        opt.clear_grad()
    model.eval()
    return model


char_gpt = functools.cache(new_char_gpt)      # the one most tests share


@pytest.fixture(scope="session")
def served_model():
    return char_gpt()


def new_default(family):
    """The family's class at its own ``tiny()`` configuration."""
    if family == "gpt2":
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        return GPTForPretraining(GPTConfig.tiny())
    if family == "axk1":
        from paddle_tpu.models.axk1 import AXK1Config, AXK1ForCausalLM
        return AXK1ForCausalLM(AXK1Config.tiny())
    if family == "sdar":
        from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
        return SDARForCausalLM(SDARConfig.tiny())
    if family == "mimo":
        from paddle_tpu.models.mimo import MiMoV2Config, MiMoV2ForCausalLM
        return MiMoV2ForCausalLM(MiMoV2Config.tiny())
    if family == "falcon_h1":
        from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                                 FalconH1ForCausalLM)
        return FalconH1ForCausalLM(FalconH1Config.tiny())
    if family == "longcat":
        from paddle_tpu.models.longcat import (LongCatConfig,
                                               LongCatForCausalLM)
        return LongCatForCausalLM(LongCatConfig.tiny())
    if family == "nemotron_h":
        from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                                  NemotronHForCausalLM)
        return NemotronHForCausalLM(NemotronHConfig.tiny())
    assert family == "lfm2", family
    from paddle_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
    return Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny())


default = functools.cache(new_default)        # the one most tests share


def _lib(family, kind="family"):
    """``benchmark/lib``'s ``family_<family>`` (or ``reference_<family>``)."""
    return importlib.import_module(f"benchmark.lib.{kind}_{family}")


_AXK1_SCALES = {"gain": 1.0, "norm_std": 0.1, "embed_std": 1.0}
_SDAR_SCALES = {"gain": 1.0, "norm_std": 0.1, "qk_gain": 1.5,
                "router_gain": 2.0, "expert_gain": 0.5, "embed_std": 1.0}


def config(family, **over):
    """The ``model`` group of a configuration at toy sizes: the file under
    ``benchmark/tests/data`` where the family's rehearsal has one of the
    widths the tests were written on, else the fields of ``tiny()``."""
    if family == "axk1":
        from paddle_tpu.models.axk1 import AXK1Config
        cfg = AXK1Config.tiny()
        m = {k: getattr(cfg, k) for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
            "first_k_dense_replace", "routed_scaling_factor",
            "norm_topk_prob", "rms_norm_eps", "rope_theta", "rope_scaling",
            "max_position_embeddings")}
        m.update(experts_held=[4, 12], weight_scales=_AXK1_SCALES)
    elif family == "sdar":
        from paddle_tpu.models.sdar import SDARConfig
        cfg = SDARConfig.tiny()
        m = {k: getattr(cfg, k) for k in (
            "vocab_size", "hidden_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "rope_theta",
            "max_position_embeddings", "block_length", "denoising_steps",
            "mask_token_id")}
        m.update(n_routed_experts=cfg.num_experts, experts_held=[0, 16],
                 first_k_dense_replace=0, weight_scales=_SDAR_SCALES)
    else:
        # mimo, falcon_h1, lfm2, longcat, nemotron_h (mimo's: layers
        # global (dense), window, window, global; window 8; 16 experts,
        # top 4; longcat's: 2 published layers = 4 sub-blocks, a router of
        # 16 + 8 identity outputs, top 4, experts 4..11 held;
        # nemotron_h's: blocks MEM*EM, 16 experts in a latent of 32, top
        # 4, experts 4..11 held)
        name = family.replace("_", "-")
        with open(os.path.join(_DATA, f"tiny-{name}-config.json")) as f:
            m = json.load(f)["model"]
    m.update(over)
    return m


@functools.cache
def seeded(family):
    """The program at ``config(family)`` with the benchmark's seeded
    weights, float32."""
    return _lib(family).build_lm(config(family), SEEDS[family], "float32")


@functools.cache
def weights(family):
    """What the family's plain reference reads its weights from."""
    return _lib(family).Weights(SEEDS[family], config(family), "float32")


def settle(eng):
    """Wait until ``eng`` has nothing queued, held or in flight and its
    last launch's record is in the flight recorder's ring (it enters at
    the END of the turn that woke the client)."""
    sched = eng._sched
    while (sched._queue or sched._slots or sched._inflight is not None
           or (sched._cycle and eng.flight_recorder.snapshot()["cycles"][-1][
               "cycle"] != sched._cycle)):
        time.sleep(0.001)


@pytest.fixture(scope="module")
def engines():
    """``engines(model, **arguments)``: the module's one ``GenerationEngine``
    of those arguments, drained, with a pool and a prefix trie as new
    (``PagedKVPool.reset_data``, the scheduler's own failure path). Its
    step programs, counters and flight recorder go on from test to test:
    read those as differences."""
    from paddle_tpu.serving import GenerationEngine
    built = {}

    def get(model, **arguments):
        key = (id(model), tuple(sorted(arguments.items())))
        if key in built:
            settle(built[key])
            built[key]._pool.reset_data()
        else:
            built[key] = GenerationEngine(model, **arguments)
        return built[key]

    yield get
    for eng in built.values():
        eng.close()
