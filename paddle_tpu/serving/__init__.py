"""``paddle_tpu.serving`` — continuous-batching LLM serving (L9+).

The autoregressive counterpart of ``inference.BatchingEngine``: where
that engine gathers fixed-shape ``predictor.run`` calls, this one serves
many concurrent ``generate``-style requests through ONE jitted, donated
fused step over a paged KV-cache pool. Requests join and leave the
in-flight batch EVERY step (continuous batching) instead of waiting for
a whole generation to drain — a long request never stalls a short one,
and a retired request's blocks are reused mid-flight.

Reference analog: the reference serves decoder LMs through
fused_multi_transformer's fixed-capacity CacheKV
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu:1) behind
AnalysisPredictor + paddle-serving request batching; the TPU-native
collapse is slot-addressed decode over a shared pool (the Ragged Paged
Attention shape, PAPERS.md) with XLA-donated in-place updates.

::

    from paddle_tpu.serving import GenerationEngine

    engine = GenerationEngine(model, num_slots=8, max_len=256)
    handle = engine.submit(prompt_ids, max_new_tokens=64,
                           eos_token_id=eos)
    for token in handle.stream():   # tokens as they are produced
        ...
    engine.close()                  # drains in-flight work

One serving path: block-granular KV management (:mod:`.paging`) with
per-request page tables, ref-counted block sharing and a prefix cache —
admission gates on FREE BLOCKS, not on worst-case sequence length, and a
repeated system prompt feeds only its uncovered tail — under ONE fused
ragged launch a cycle (``models.generation.build_fused_step_fn``) that
mixes budgeted prompt chunks with every decode row.
``kv_dtype="int8"`` stores the blocks QUANTIZED with
per-block max-abs scales (~4x blocks per byte budget, ~2x+ concurrent
requests), and ``spec_draft=`` + ``spec_k=`` adds
draft-model SPECULATIVE DECODING — k candidate tokens verified per
slot per cycle in one fused ragged launch, exact greedy parity,
``stats()['spec_tokens_per_cycle']`` > 1 on agreeing workloads.

SLO observability (ISSUE 6): every handle carries ``handle.trace`` — a
:class:`~.tracing.RequestTrace` of timestamped lifecycle events with
derived per-request TTFT/TPOT — the scheduler keeps an always-on
bounded :class:`~.flight_recorder.FlightRecorder`
(``engine.dump_flight_recorder()``, auto-dumped on step failure), and
``engine.stats()`` reports per-ENGINE TTFT/TPOT percentiles from its
own retired traces. What it does on the chip is measured by
``benchmark/run.py``.

Modules: :mod:`.paging` (THE pool: request slots, free-list block
allocator, page tables, refcounts/copy-on-write, prefix-cache trie +
LRU eviction), :mod:`.scheduler` (admission queue, backpressure,
the per-cycle chunk budget, block-pressure preemption, the cycle),
:mod:`.tracing` (per-request lifecycle traces + chrome-trace lanes),
:mod:`.flight_recorder` (bounded postmortem rings + per-engine latency
reservoirs + tail-sampled traces), :mod:`.engine` (the thread-safe
user surface + monitor/profiler/analysis wiring), :mod:`.slo` (SLO
objectives, multi-window burn rates, per-replica goodput),
:mod:`.opsserver` (the zero-dependency HTTP ops surface: /metrics,
/statusz, /varz, /healthz, /readyz, /tracez, /timeline — a pluggable
route table), :mod:`.frontdoor` (the OpenAI-style ``/v1/completions``
inference front door: SSE streaming, per-tenant token-bucket admission,
weighted-fair interactive/batch lanes riding the scheduler's
(lane, tenant) deficit-round-robin), :mod:`.host_tier` (the
hierarchical KV cache: ``GenerationEngine(host_tier_bytes=...)`` spills
LRU-evicted prefix blocks to a bounded host-DRAM
:class:`~.host_tier.HostBlockPool` on a background spiller thread and
promotes them back through double-buffered async H2D copies the
scheduler overlaps with decode — the prefix cache outgrows HBM).
"""
from __future__ import annotations

from .engine import GenerationEngine, PlanError  # noqa: F401
from .fleet import EngineFleet  # noqa: F401
from .flight_recorder import FlightRecorder  # noqa: F401
from .frontdoor import FrontDoor, TokenBucket  # noqa: F401
from .host_tier import (HostBlockPool, HostTierError,  # noqa: F401
                        HostTierFullError, PromotionTicket)
from .opsserver import OpsServer  # noqa: F401
from .paging import (BlockError, PagedKVPool,  # noqa: F401
                     PoolCapacityError, PoolExhaustedError)
from .scheduler import (DeadlineExceeded, GenerationRequest,  # noqa: F401
                        QueueFullError, RequestCancelled, Scheduler)
from .slo import SLOObjective, SLOTracker  # noqa: F401
from .slo import attainment_from_buckets  # noqa: F401
from .tracing import RequestTrace  # noqa: F401

__all__ = ["GenerationEngine", "PlanError", "EngineFleet",
           "PagedKVPool", "GenerationRequest", "Scheduler",
           "QueueFullError", "DeadlineExceeded", "RequestCancelled",
           "PoolCapacityError", "PoolExhaustedError", "BlockError",
           "RequestTrace", "FlightRecorder", "OpsServer",
           "FrontDoor", "TokenBucket",
           "HostBlockPool", "HostTierError", "HostTierFullError",
           "PromotionTicket",
           "SLOTracker", "SLOObjective", "attainment_from_buckets"]
