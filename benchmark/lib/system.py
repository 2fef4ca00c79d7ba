"""The system under test, as the benchmark sees it: the ONLY file under
``benchmark/`` that imports ``paddle_tpu``.

Everything here goes through entry points a user calls (``GPTForPretraining``,
``amp.decorate``, ``paddle.Model``/``train_batch``, ``GenerationEngine``,
``FrontDoor``) plus the program's own counters (program registry, flight
recorder, ``engine.stats()``). The weights come from ``lib/weights.py``.
"""
from __future__ import annotations

import threading

import jax

from . import weights as W


class CompileCounter:
    """Counts every backend compile of this process from jax's own
    monitoring events (cache hits are retrievals, counted apart), so
    "nothing compiled inside the window" does not rest on the program's
    counters alone."""

    def __init__(self):
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        from paddle_tpu.framework import program_registry
        recs = program_registry.snapshot()
        return {"backend": self.backend_compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "registry": sum(r["compiles"] for r in recs.values()),
                "sites": {s: r["compiles"] for s, r in recs.items()}}


def compiles_between(a: dict, b: dict) -> dict:
    sites = {s: n - a["sites"].get(s, 0) for s, n in b["sites"].items()
             if n - a["sites"].get(s, 0)}
    return {"backend": b["backend"] - a["backend"],
            "registry": b["registry"] - a["registry"], "sites": sites}


def compile_cache_status() -> dict:
    import paddle_tpu  # noqa: F401  (arms the in-checkout cache at import)
    from paddle_tpu.framework import compile_cache
    st = dict(compile_cache.status())
    st["entries"] = compile_cache.entries()
    return st


def _gpt_config(model: dict):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(
        vocab_size=int(model["vocab_size"]),
        hidden_size=int(model["hidden_size"]),
        num_hidden_layers=int(model["num_hidden_layers"]),
        num_attention_heads=int(model["num_attention_heads"]),
        intermediate_size=int(model["intermediate_size"]),
        max_position_embeddings=int(model["max_position_embeddings"]),
        hidden_dropout_prob=float(model["hidden_dropout_prob"]),
        attention_dropout_prob=float(model["attention_dropout_prob"]),
        initializer_range=float(model["initializer_range"]))


def build_lm(model: dict, seed: int, dtype: str, lm_loss_chunks: int = 1):
    """``GPTForPretraining`` at the configuration's sizes, decorated to
    ``dtype`` the way a user does it (``amp.decorate(O2)``), holding the
    benchmark's seeded weights."""
    from paddle_tpu import amp
    from paddle_tpu.models.gpt import GPTForPretraining
    net = GPTForPretraining(_gpt_config(model), lm_loss_chunks=lm_loss_chunks)
    if dtype != "float32":
        amp.decorate(net, level="O2", dtype=dtype)
    state = W.program_state(W.make_weights(seed, model, dtype))
    missing, unexpected = net.set_state_dict(state)
    if missing or unexpected:
        raise RuntimeError(f"weight names do not line up with the program: "
                           f"missing {missing[:3]}, unexpected {unexpected[:3]}")
    return net


def build_trainer(model: dict, recipe: dict, seed: int):
    """The ``bench_gpt2`` recipe as a ``paddle.Model``: chunked tied-head
    loss, bf16 AMP O2, AdamW with fp32 masters. The network's output IS
    its loss, so ids and labels are both inputs. Returns (model,
    optimizer, parameter-name prefix)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.static import InputSpec

    class NextTokenLoss(nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids, labels):
            return self.lm(ids, labels=labels)[0].reshape([1])

    lm = build_lm(model, seed, recipe["dtype"],
                  lm_loss_chunks=int(recipe["lm_loss_chunks"]))
    opt = paddle.optimizer.AdamW(
        learning_rate=float(recipe["lr"]),
        weight_decay=float(recipe["weight_decay"]),
        beta1=float(recipe["beta1"]), beta2=float(recipe["beta2"]),
        epsilon=float(recipe["epsilon"]),
        parameters=lm.parameters(),
        multi_precision=bool(recipe["fp32_master_weights"]))
    trainer = paddle.Model(
        NextTokenLoss(lm),
        inputs=[InputSpec([None, None], "int32", "ids"),
                InputSpec([None, None], "int32", "labels")])
    trainer.prepare(opt, loss=lambda loss: loss.mean())
    return trainer, opt, "lm.gpt."


def optimizer_slots(trainer, opt, slot: str) -> dict:
    """{parameter name: device array} of one optimizer slot
    (``moment1``, ``master_weight``) after the steps dispatched so far.
    The arrays are donated by the next step: reduce them before it."""
    trainer.parameters()                  # mirrors the functional state
    suffix = "_" + slot
    return {k[:-len(suffix)]: v._data for k, v in opt.state_dict().items()
            if isinstance(k, str) and k.endswith(suffix)}


def train_step_compiled_text() -> str:
    from paddle_tpu.framework import program_registry
    return "\n".join(program_registry.compiled_text(s) or ""
                     for s in program_registry.snapshot()
                     if s.startswith("hapi/train_step["))


def train_step_memory() -> dict:
    """What XLA says the compiled train step needs (bytes)."""
    from paddle_tpu.framework import program_registry
    out = {}
    for site, rec in program_registry.snapshot().items():
        if site.startswith("hapi/train_step["):
            out = {k: rec.get(k) for k in ("temp_bytes", "argument_bytes",
                                           "output_bytes", "static_peak_bytes")}
    return out


def pool_blocks_for_share(model: dict, serving: dict) -> int:
    """Blocks that take ``pool_hbm_share`` of the device memory still
    free once the weights are resident — the pool rule of the
    configuration; the engine's own planner then admits it or raises."""
    from paddle_tpu.serving import PagedKVPool
    if "pool_blocks" in serving:        # the CPU rehearsals: no memory_stats
        return int(serving["pool_blocks"])
    ms = jax.devices()[0].memory_stats() or {}
    free = ms["bytes_limit"] - ms["bytes_in_use"]
    heads = int(model["num_attention_heads"])
    return PagedKVPool.blocks_within_budget(
        int(free * float(serving["pool_hbm_share"])),
        num_layers=int(model["num_hidden_layers"]), num_heads=heads,
        block_size=int(serving["block_size"]),
        head_dim=int(model["hidden_size"]) // heads,
        dtype=serving["dtype"])


class Served:
    """A ``FrontDoor`` over a fused paged ``GenerationEngine``."""

    def __init__(self, net, model: dict, serving: dict, slots: int):
        from paddle_tpu.serving import FrontDoor, GenerationEngine
        self.num_blocks = pool_blocks_for_share(model, serving)
        self.engine = GenerationEngine(
            net, kv_layout="paged", attention="fused",
            block_size=int(serving["block_size"]),
            max_len=int(serving["max_len"]), num_slots=int(slots),
            num_blocks=self.num_blocks,
            prefill_budget=int(serving["prefill_budget"]),
            max_queue=int(serving["max_queue"]))
        self.door = FrontDoor(self.engine)
        self.url = self.door.start().url
        self._cycles = {}
        self._poll_stop = threading.Event()
        self._poller = None

    def stats(self) -> dict:
        return self.engine.stats()

    def door_stats(self) -> dict:
        return self.door.stats()

    def generate(self, prompts, max_tokens) -> list:
        """Submit ``prompts`` together through ``engine.submit`` and wait
        for all (warm-up waves)."""
        handles = [self.engine.submit(p, int(m))
                   for p, m in zip(prompts, max_tokens)]
        return [[int(t) for t in h.stream()] for h in handles]

    # the flight recorder's cycle ring holds 256 records: a traced run
    # polls it (the untraced run that is timed does not)
    def start_cycle_poll(self, every_s: float = 0.5) -> None:
        def poll():
            while not self._poll_stop.wait(every_s):
                self._drain_cycles()
        self._poller = threading.Thread(target=poll, daemon=True)
        self._poller.start()

    def _drain_cycles(self) -> None:
        for rec in self.engine.flight_recorder.snapshot()["cycles"]:
            self._cycles[rec["cycle"]] = rec

    def stop_cycle_poll(self) -> list:
        """Cycle records seen, oldest first. ``t`` is
        ``time.perf_counter()`` at the cycle's start."""
        self._poll_stop.set()
        if self._poller is not None:
            self._poller.join(timeout=5)
        self._drain_cycles()
        return [self._cycles[k] for k in sorted(self._cycles)]

    def close(self) -> None:
        self.door.close()
        self.engine.close(cancel_pending=True)
