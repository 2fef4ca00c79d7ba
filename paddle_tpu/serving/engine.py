"""``GenerationEngine`` — the user surface of the continuous-batching
LLM server.

Many concurrent ``submit(prompt_ids, ...)`` calls are served by ONE
jitted, pool-donated fused step (``models.generation.build_fused_step_fn``)
over the paged KV-cache pool (:mod:`.paging`), driven by the scheduler's
cycle (:mod:`.scheduler`). The serving-side twin of the PR-2 donated
training loop: buffers are donated and rebound, the hot loop never syncs
except the one windowed token fetch, and every step program must pass
the PR-3 analyzer clean (``engine.analyze()``).

One path: a request owns only the blocks covering its tokens so far,
addressed through its page table; admission gates on FREE BLOCKS, memory
pressure preempts the youngest request (requeued, fed again) instead of
deadlocking, and full prompt blocks are shared across requests through
the prefix cache — a repeated system prompt feeds only its uncovered
tail. Each turn of the scheduler dispatches ONE fused ragged launch
mixing ``prefill_budget`` tokens of prompt chunks with every decode row;
the first generated token comes out of the launch that fed the final
chunk. Two launches are in flight: a launch goes out before the one
before it is fetched, and a decode row whose input token the host has
not seen yet reads it from that launch's un-fetched result, on the
device (``_run_fused_step``'s ``prev``). Greedy output is
token-identical to ``models.generate`` run per request
(tests/test_serving_engine.py).

Compile discipline: one trace per (pow2 q-row bucket, pow2 page-table
bucket), watched by ``framework.trace_probe`` sites
(``serving/fused[qQ,tT]#N``; ``serving/spec[...]``,
``serving/spec_draft[kK]``, ``serving/spec_prefill[B]`` with a draft),
so a retrace shows up in the ``dispatch/retrace_cause`` counters exactly
like training-loop churn.

Observability (PR-1 wiring + the ISSUE-6 SLO spine): counters
``serving/requests``, ``serving/completed``, ``serving/tokens``,
``serving/launch_overlapped``, ``serving/launch_rows`` and
``serving/tower_rows`` (a launch's real rows and the rows its tower runs
on), ``serving/kv_walks_handed`` (the attention kernel's walks that began
on a group the walk before them started), ``serving/preempt``,
``serving/queue_full``, ``serving/cancelled``,
``serving/deadline_exceeded``, ``serving/prefix_hit``/``prefix_miss``/
``prefill_tokens_saved``/``prefix_evict``; histograms
``serving/queue_depth``, ``serving/active_slots``,
``serving/batch_occupancy``, ``serving/cycle_ms``, ``serving/ttft_ms``,
``serving/tpot_ms``, ``serving/tokens_per_sec``,
``serving/kv_blocks_in_use``; spans ``serving/cycle`` with
nested sweep/admit/prefill/plan/decode_dispatch/host_fetch/emit children,
plus a chrome-trace LANE per finished request (``serving/tracing.py``).
Every request handle carries ``handle.trace`` (a
:class:`~.tracing.RequestTrace` with derived TTFT/TPOT), the scheduler
keeps an always-on bounded flight recorder
(:meth:`GenerationEngine.dump_flight_recorder`, auto-dumped when a
step failure poisons requests), and the :meth:`GenerationEngine.stats`
snapshot packages the operator view — per-ENGINE TTFT/TPOT percentiles
included — so nobody has to scrape process-global monitor counters by
prefix.
"""
from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Iterator, Optional

import numpy as np

from ..framework import metrics as _metrics
from ..framework import program_registry as _registry
from ..framework import trace_probe as _probe
from ..framework.monitor import stat_add
from ..profiler import memory as _memory
from ..profiler import span as _prof
from .paging import PagedKVPool, PoolCapacityError
from .scheduler import GenerationRequest, Scheduler

__all__ = ["GenerationEngine", "PlanError"]

_engine_seq = 0
_engine_seq_lock = threading.Lock()


class PlanError(RuntimeError):
    """The static HBM plan says this replica will not fit (ISSUE 18).

    Raised at ``GenerationEngine(hbm_budget_bytes=...)`` construction —
    BEFORE any compile — when the donation-aware liveness estimate of
    the LARGEST decode-path bucket plus the pool+scales ledger bytes
    exceeds the budget. Carries the full plan dict as ``.plan``
    (``static_peak_bytes``, ``pool_bytes``, ``budget_bytes``,
    ``peak_point``)."""

    def __init__(self, message: str, plan: dict):
        super().__init__(message)
        self.plan = plan


def _device_memory_limit() -> Optional[int]:
    """Per-device HBM limit when the backend reports one, else None
    (CPU reports nothing — no fake numbers, no default gate there).
    Construction-time admission query, not scheduler-cycle polling —
    the memory-stats-hot-path rule's argued exception."""
    import jax
    try:
        stats = jax.devices()[0].memory_stats()  # lint: ok
    except Exception:                            # noqa: BLE001
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    return int(limit) if limit else None


def _next_engine_id() -> int:
    global _engine_seq
    with _engine_seq_lock:
        _engine_seq += 1
        return _engine_seq


# live engines for the statusz console (weak: a GC'd or closed engine
# drops out of the section on its own); the section is registered with
# the metrics registry once, at the first engine construction, so a
# process that never serves never shows an empty serving section twice
_LIVE_ENGINES: "weakref.WeakSet" = weakref.WeakSet()
_statusz_registered = False
_statusz_lock = threading.Lock()


@contextlib.contextmanager
def _phase(phases_ms: dict, name: str):
    """One part of the engine's build: the span ``startup/<name>`` and
    its wall into ``phases_ms[name]``."""
    t0 = time.perf_counter()
    with _prof.record(f"startup/{name}", "startup"):
        yield
    phases_ms[name] = (time.perf_counter() - t0) * 1e3


def _startup_line(st: dict) -> str:
    """``stats()["startup"]`` on one line: the engine's build, then what
    building its programs took, part by part."""
    built = [p for p in st["programs"] if not p.get("fallback")]
    secs = lambda key: sum(p[key] or 0.0 for p in built) / 1e3
    hits = sum(p["cache_hits"] for p in built)
    lookups = hits + sum(p["cache_misses"] for p in built)
    return (f"startup: build {st['build_ms'] / 1e3:.1f} s | "
            f"{len(st['programs'])} programs: trace {secs('trace_ms'):.1f}, "
            f"lower {secs('lower_ms'):.1f}, "
            f"{'load' if lookups and hits == lookups else 'compile'} "
            f"{secs('compile_ms'):.1f}, first call "
            f"{secs('first_call_ms'):.1f} s "
            f"({hits}/{lookups} cache hits)")


def _engine_section() -> str:
    """statusz section: one line of load + cache + health per live
    engine, plus recent flight-recorder trouble (failed cycles, the
    last auto-dump path) — the serving half of the ops console."""
    engines = [e for e in list(_LIVE_ENGINES) if not e._closed]
    if not engines:
        return "(no live engines)"
    lines = []
    for e in sorted(engines, key=lambda e: e._eid):
        try:
            s = e.stats()
            head = (f"engine #{e._eid} queue={s['queue_depth']} "
                    f"active={s['active_requests']} "
                    f"slots={s['slots_in_use']}/{s['num_slots']}"
                    f" blocks={s['kv_blocks_in_use']}/{s['num_blocks']}"
                    f" prefix_hit={s['prefix_hit_ratio']:.2f}")
            if "host_tier" in s:
                ht = s["host_tier"]
                head += (f" host_tier={ht['blocks']}/"
                         f"{ht['capacity_blocks']}")
            if "spec_accept_rate" in s:
                head += f" spec_accept={s['spec_accept_rate']:.2f}"
            if s.get("serving_mfu") is not None:
                head += f" mfu={s['serving_mfu']:.3f}"
            if s.get("decode_tokens_per_sec") is not None:
                head += f" tok/s={s['decode_tokens_per_sec']:.1f}"
            lines.append(head)
            lines.append("  " + _startup_line(s["startup"]))
            ttft = s.get("ttft_ms")
            if ttft:
                lines.append(f"  ttft p50 {ttft['p50']:.1f} ms  "
                             f"p95 {ttft['p95']:.1f} ms  "
                             f"(n={ttft['count']})")
            if s.get("nonfinite_cycles"):
                lines.append(f"  !! nonfinite decode cycles: "
                             f"{s['nonfinite_cycles']}")
            rec = e.flight_recorder
            failed = [c for c in rec.snapshot()["cycles"]
                      if c.get("failed")]
            if failed:
                lines.append(f"  !! {len(failed)} failed cycles in the "
                             f"ring; last: {failed[-1].get('failed')}")
            if rec.last_dump_path:
                lines.append(f"  last auto-dump: {rec.last_dump_path}")
        except Exception as err:                         # noqa: BLE001
            lines.append(f"engine #{e._eid}: (stats error: {err!r})")
    return "\n".join(lines)


def _register_engine_telemetry(engine: "GenerationEngine") -> None:
    global _statusz_registered
    with _statusz_lock:
        if not _statusz_registered:
            _metrics.register_statusz_section("serving engines",
                                              _engine_section)
            _statusz_registered = True
    _LIVE_ENGINES.add(engine)
    # per-engine scrape-time collector: the stats() island re-published
    # as labeled registry metrics ({engine=<id>}), pulled only when a
    # snapshot/export/sampler asks — zero cost on the serving hot path
    ref = weakref.ref(engine)

    def _collect():
        e = ref()
        if e is None or e._closed:
            return ()
        s = e.stats()
        labels = {"engine": str(e._eid)}
        out = [("gauge", "serving_queue_depth", labels,
                s["queue_depth"]),
               ("gauge", "serving_slots_in_use", labels,
                s["slots_in_use"]),
               ("gauge", "serving_kv_bytes_in_use", labels,
                s["kv_bytes_in_use"]),
               ("counter", "serving_requests_retired", labels,
                s["requests_retired"]),
               ("counter", "serving_preempts", labels, s["preempts"]),
               ("counter", "serving_nonfinite_cycles", labels,
                s["nonfinite_cycles"]),
               ("gauge", "serving_kv_blocks_in_use", labels,
                s["kv_blocks_in_use"]),
               ("gauge", "serving_prefix_hit_ratio", labels,
                s["prefix_hit_ratio"])]
        # tiered hit split: one {engine, tier} counter series per
        # tier so dashboards can stack hbm/host/miss admissions
        for tier, n in s["tier_hits"].items():
            out.append(("counter", "serving_tier_hit",
                        dict(labels, tier=str(tier)), n))
        ht = s.get("host_tier")
        if ht is not None:
            out.append(("gauge", "serving_host_tier_bytes_in_use",
                        labels, ht["bytes_in_use"]))
            out.append(("counter", "serving_host_tier_demoted", labels,
                        ht["demoted_blocks"]))
            out.append(("counter", "serving_host_tier_promoted", labels,
                        ht["promoted_blocks"]))
        if s.get("decode_tokens_per_sec") is not None:
            out.append(("gauge", "serving_decode_tokens_per_sec",
                        labels, s["decode_tokens_per_sec"]))
        # per-tenant goodput labels (front-door multi-tenancy): one
        # {engine, tenant} series per tenant seen by this engine
        for tenant, ts in (s.get("tenants") or {}).items():
            tl = dict(labels, tenant=str(tenant))
            out.append(("counter", "serving_tenant_retired", tl,
                        ts["retired"]))
            out.append(("gauge", "serving_tenant_goodput_rps", tl,
                        ts["goodput_rps"]))
        return out
    _metrics.register_collector(f"serving_engine/{engine._eid}", _collect)


def _refuse_with_latent(kv_dtype, mesh, spec_draft,
                        host_tier_bytes) -> None:
    """A model whose cache is latent (one row a token for all heads,
    ``models/decoder_spec.py``): each mechanism that has no latent form
    yet is refused here, by name."""
    import jax.numpy as jnp
    if mesh is not None:
        raise ValueError(
            "mesh= (tensor-parallel serving) does not compose with a "
            "latent pool yet: the mp shards partition the pool's head "
            "axis, and a latent row has none (the heads share it; TP of "
            "latent attention shards the up-projections instead)")
    if spec_draft is not None:
        raise ValueError(
            "spec_draft does not compose with a latent pool yet: the "
            "draft tower and make_draft_model() build GPT blocks, and the "
            "latent kernel takes a q block's real rows from kv_len, which "
            "verify rows break")
    if kv_dtype is not None and jnp.dtype(kv_dtype).name in (
            "int8", "float8_e4m3fn"):
        raise ValueError(
            "int8/fp8 KV blocks do not compose with a latent pool yet: "
            "the per-block max-abs scales are laid out [layers, 2, "
            "blocks, heads] for K and V planes, and a latent row is both")
    if host_tier_bytes is not None:
        raise ValueError(
            "host_tier_bytes does not compose with a latent pool yet: "
            "demotion and promotion copy [layers, 2, heads, ...] K/V "
            "planes of a block (serving/host_tier.py)")


def _refuse_with_blocks(kv_dtype, mesh, spec_draft,
                        host_tier_bytes) -> None:
    """A model generated by diffusion over blocks (``block_length`` > 1
    in its decoder spec's generation rule): each mechanism that has no
    block form yet is refused here, by name."""
    import jax.numpy as jnp
    if spec_draft is not None:
        raise ValueError(
            "spec_draft does not compose with block generation yet: a "
            "draft proposes the NEXT tokens of a causal model, and a "
            "block's positions are fixed in order of confidence")
    if host_tier_bytes is not None:
        raise ValueError(
            "host_tier_bytes does not compose with block generation yet: "
            "demotion and promotion copy [layers, 2, heads, ...] K/V "
            "planes a QUERY head (serving/host_tier.py), and this pool "
            "holds KV heads")
    if kv_dtype is not None and jnp.dtype(kv_dtype).name in (
            "int8", "float8_e4m3fn"):
        raise ValueError(
            "int8/fp8 KV blocks do not compose with block generation "
            "yet: a block's rows are rewritten every pass, and each "
            "rewrite would requantize the cache block around them")
    if mesh is not None:
        raise ValueError(
            "mesh= (tensor-parallel serving) does not compose with block "
            "generation yet: the sharded step has no unmask stage, and "
            "grouped heads under mesh= are not built")


def _refuse_with_groups(kv_dtype, mesh, spec_draft,
                        host_tier_bytes) -> None:
    """A model whose layers form more than one cache group (window and
    global layers, ``models/decoder_spec.py``): each mechanism that is not
    built over several block arrays and page tables a request is refused
    here, by name."""
    import jax.numpy as jnp
    if spec_draft is not None:
        raise ValueError(
            "spec_draft does not compose with more than one cache group "
            "yet: a rejected draft rolls the pool's position back, and a "
            "window group has by then freed the blocks behind it")
    if host_tier_bytes is not None:
        raise ValueError(
            "host_tier_bytes does not compose with more than one cache "
            "group yet: the tier demotes and promotes the blocks of the "
            "prefix cache, and nothing is offered to it here (a window "
            "group keeps no prefix)")
    if kv_dtype is not None and jnp.dtype(kv_dtype).name in (
            "int8", "float8_e4m3fn"):
        raise ValueError(
            "int8/fp8 KV blocks do not compose with more than one cache "
            "group yet: the per-block scales are ONE array [layers, 2, "
            "blocks, heads], and the groups differ in blocks and heads")
    if mesh is not None:
        raise ValueError(
            "mesh= (tensor-parallel serving) does not compose with more "
            "than one cache group yet: the sharded step hands every layer "
            "one head-partitioned pool and one table")


def _refuse_with_state(kv_dtype, mesh, spec_draft,
                       host_tier_bytes) -> None:
    """A model whose layers hold a recurrent state — beside their
    attention cache (a state-space mixer in every layer) or IN PLACE of
    one (a layer whose mixer is the state alone),
    ``models/decoder_spec.py:StateSpec``: each mechanism that needs a
    SNAPSHOT of a sequence's state at some earlier position, or a state
    layout that is not built, is refused here, by name. Prefix reuse is
    the same need and takes no option: the pool offers no block to the
    prefix cache and matches none (``serving/paging.py``), and a
    preempted request is re-fed from position 0."""
    import jax.numpy as jnp
    if spec_draft is not None:
        raise ValueError(
            "spec_draft does not compose with a recurrent state yet: a "
            "rejected draft rolls the pool's position back, and a "
            "state cannot take a row back (it would need the state "
            "before every candidate row)")
    if host_tier_bytes is not None:
        raise ValueError(
            "host_tier_bytes does not compose with a recurrent state yet: "
            "the tier demotes and promotes the blocks of the prefix "
            "cache, and nothing is offered to it here (a prefix's blocks "
            "are no use without the state at its end)")
    if kv_dtype is not None and jnp.dtype(kv_dtype).name in (
            "int8", "float8_e4m3fn"):
        raise ValueError(
            "int8/fp8 KV blocks do not compose with a recurrent state "
            "yet: the quantized step threads its scale array where this "
            "one threads the state arrays, and the two are not built "
            "together")
    if mesh is not None:
        raise ValueError(
            "mesh= (tensor-parallel serving) does not compose with a "
            "recurrent state yet: the sharded step has no mixer (beside "
            "attention or in place of it), and the state arrays have no "
            "partitioned layout")


def _group_block_counts(groups, num_slots, max_len, block_size, num_blocks,
                        prefill_budget):
    """Blocks a cache group's array holds. One group: ``num_blocks`` as
    given. More: ``num_blocks`` (default: every slot at ``max_len``) is
    what a cache held UNIFORMLY would hold of every group, and its bytes
    are shared out by the rule of the spec — a WINDOW group gets what
    its slots can hold at all, ``num_slots x (ceil(W / block_size) + 2)``
    (a window's blocks, one it straddles into, one a chunk's first rows
    straddle into) plus the blocks of ``prefill_budget`` rows; the bytes
    that saves go to the window-0 groups, up to every slot at
    ``max_len``."""
    if len(groups) == 1:
        return [num_blocks]
    cdiv = lambda a, b: -(-int(a) // int(b))
    worst = num_slots * cdiv(max_len, block_size)
    uniform = worst if num_blocks is None else int(num_blocks)
    values = [len(g.layers) * g.cache.rows * g.cache.lanes for g in groups]
    counts, saved = [], 0
    for g, v in zip(groups, values):
        n = uniform
        if g.window:
            n = min(uniform, num_slots * (cdiv(g.window, block_size) + 2)
                    + cdiv(prefill_budget or max_len, block_size))
            saved += (uniform - n) * v
        counts.append(n)
    whole = [i for i, g in enumerate(groups) if not g.window]
    if whole:
        more = saved // sum(values[i] for i in whole)
        for i in whole:
            counts[i] = min(worst, uniform + more)
    return counts


class GenerationEngine:
    """Continuous-batching serving over a decoder the fused stack has a
    spec of (``models/decoder_spec.py``: GPT-2, A.X-K1, SDAR,
    MiMo-V2-Flash, Falcon-H1, LFM2-MoE, LongCat-Flash, Nemotron-H) — one
    token
    a sequence a step, or a block of them by diffusion, as the spec's
    generation rule says.

    ``model`` is a ``models.GPTForPretraining`` / ``GPTModel`` /
    ``AXK1ForCausalLM`` / ``SDARForCausalLM`` / ``MiMoV2ForCausalLM`` /
    ``FalconH1ForCausalLM`` / ``Lfm2MoeForCausalLM`` /
    ``LongCatForCausalLM`` / ``NemotronHForCausalLM`` (anything
    ``serving_decoder`` has a spec of);
    its parameters are snapshotted at construction (sharded parameters
    serve sharded — jit follows the placement).

    * ``num_slots`` — concurrent in-flight requests (the launch's batch);
    * ``max_len`` — a request's virtual capacity:
      ``prompt + max_new_tokens <= max_len``;
    * ``top_k``/``top_p`` — the sampled path's truncation, STATIC per
      engine (part of the step's trace); per-request
      ``do_sample``/``temperature`` are traced values;
    * ``max_queue``/``prefill_budget`` — backpressure, and the prompt
      tokens fed per cycle next to the decode rows (see
      :mod:`.scheduler`): a prompt burst cannot monopolize a cycle;
    * ``block_size``/``num_blocks`` — the :class:`~.paging.PagedKVPool`.
      ``block_size`` defaults to ``max(16, the kernel's floor for the
      pool's dtype)`` (16 for bf16/float32, 32 for int8/fp8); an
      explicit value under the floor raises. ``num_blocks`` defaults to
      the worst case (every slot at ``max_len``); shrink it to what the
      device holds — admission then gates on blocks, pressure preempts,
      and full prompt blocks are shared through the prefix cache. A model
      whose spec has more than one CACHE GROUP (window and global
      layers) gets a block array and a page table a request for each:
      ``num_blocks`` is then what a cache held uniformly in every layer
      would hold, and its bytes are shared out by the spec's rule
      (``_group_block_counts``: the window group what its slots can hold
      at all, the rest to the layers that keep the whole context). A
      model whose layers hold a recurrent STATE — beside their cache (a
      state-space mixer) or in place of one (a layer whose mixer is the
      state alone: the block arrays then hold the OTHER layers only) —
      gets one row of it a slot, sized from the spec and ``num_slots``
      and held by the same pool; nothing selects it;
    * ``kv_dtype`` — ``"int8"``/``"float8_e4m3fn"`` stores the blocks
      quantized with per-block max-abs scales;
    * ``spec_draft``/``spec_k`` — speculative decoding: a small draft
      proposes ``spec_k`` tokens a decode slot a cycle, verified in the
      same fused launch (``min_bucket`` floors the draft's pow2 prefill
      buckets and does nothing else);
    * ``mesh``/``mp_axis`` — tensor-parallel serving over a 1-D mesh;
    * ``host_tier_bytes`` — a host-DRAM tier behind the prefix cache;
    * ``kv_layout``/``attention`` — removed in PR 31: there is one
      serving path. They accept ``"paged"`` / ``"fused"`` (what
      ``benchmark/`` still passes) and select nothing.

    Greedy engine output is token-identical to ``models.generate`` run
    per request (the parity contract, tests/test_serving_engine.py).
    """

    @_prof.record("startup/engine_build", "startup")
    def __init__(self, model, num_slots: int = 8,
                 max_len: Optional[int] = None, *, top_k: int = 0,
                 top_p: float = 1.0, pad_token_id: int = 0,
                 max_queue: int = 128, prefill_budget: Optional[int] = None,
                 min_bucket: int = 8, seed: int = 0, dtype=None,
                 kv_layout: str = "paged",
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 attention: str = "fused", kv_dtype=None,
                 spec_draft=None, spec_k: int = 4,
                 mesh=None, mp_axis: str = "mp",
                 hbm_budget_bytes: Optional[int] = None,
                 lane_weights: Optional[dict] = None,
                 host_tier_bytes: Optional[int] = None):
        # the engine's own build, for stats()["startup"]: where it began
        # on the flight recorder's clock, and what each part took
        t_build = time.perf_counter()
        phases_ms = dict.fromkeys(
            ("params", "pallas_smoke", "pool", "plan_gate", "scheduler"),
            0.0)
        import jax

        from ..nn.layer.layers import get_buffers_tree, get_params_tree
        from ..ops import pallas_smoke
        from ..ops.ragged_paged_attention import (check_kv_tile,
                                                  min_kv_block_for)

        for name, got, only in (("kv_layout", kv_layout, "paged"),
                                ("attention", attention, "fused")):
            if got != only:
                raise ValueError(
                    f"{name}={got!r}: the option was removed in PR 31 — "
                    f"the paged pool and the fused step are the one "
                    f"serving path ({name}={only!r} is still accepted "
                    f"and selects nothing)")
        if min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, got {min_bucket}")
        if mesh is not None:
            # tensor-parallel serving (ISSUE 15): the paged pool is a
            # head-partitioned GSPMD array and every step is a
            # shard_map over mp_axis — scale-UP, vs EngineFleet's
            # scale-OUT replicas
            if host_tier_bytes is not None:
                raise ValueError(
                    "host_tier_bytes does not compose with mesh= yet: "
                    "demotion/promotion copies would need per-shard "
                    "gathers against the head-partitioned pool — run "
                    "tiered engines single-device (or per EngineFleet "
                    "replica)")
            if kv_dtype is not None:
                raise ValueError(
                    "mesh= does not compose with kv_dtype= yet: the "
                    "quantized block scales would need their own "
                    "head-sharded layout — serve quantized pools "
                    "single-device (or per EngineFleet replica)")
            if spec_draft is not None:
                raise ValueError(
                    "mesh= does not compose with spec_draft= yet: the "
                    "draft tower and verify program have no sharded "
                    "builders — run speculative engines single-device")
        # everything below sizes itself from the model's decoder spec
        # (models/decoder_spec.py): the layers that hold a cache (the
        # spec's ``cache``/``attention`` are the FIRST such layer's),
        # cache rows and lanes a token, vocabulary, positions
        from ..models import decoder_spec as _ds
        spec = _ds.serving_decoder(model).spec
        cache = spec.cache
        if spec.attention == _ds.LATENT:
            _refuse_with_latent(kv_dtype, mesh, spec_draft,
                                host_tier_bytes)
        if spec.generation.block_length > 1:
            _refuse_with_blocks(kv_dtype, mesh, spec_draft,
                                host_tier_bytes)
        groups = spec.cache_groups
        if len(groups) > 1:
            _refuse_with_groups(kv_dtype, mesh, spec_draft,
                                host_tier_bytes)
        if spec.state is not None:
            _refuse_with_state(kv_dtype, mesh, spec_draft,
                               host_tier_bytes)
        max_len = int(max_len or spec.max_positions)
        # every jit is deferred, so without this check an oversized
        # max_len would only surface as SILENTLY WRONG tokens (XLA clamps
        # the out-of-range position gather past max_positions)
        if max_len > spec.max_positions:
            raise ValueError(
                f"max_len {max_len} exceeds max_position_embeddings="
                f"{spec.max_positions}")
        model.eval()                      # serving is inference-only
        self._model = model
        self._decoder_spec = spec
        self._pad = int(pad_token_id)
        self._top_k, self._top_p = int(top_k), float(top_p)
        self._mesh = mesh
        self._mp_axis = str(mp_axis)
        self._mp = 1
        with _phase(phases_ms, "params"):
            if mesh is not None:
                from ..models.generation import (_mp_mesh_check,
                                                 shard_params_megatron)
                self._mp = _mp_mesh_check(model, mesh, self._mp_axis)
                # lay the weights out Megatron-style BEFORE the snapshot:
                # the params tree then holds the sharded arrays and the
                # shard_map'd steps consume their local shards directly
                shard_params_megatron(model, mesh, mp_axis=self._mp_axis)
            self._params = get_params_tree(model)
            self._buffers = get_buffers_tree(model)
        if dtype is None:
            dtype = self._params[next(iter(self._params))].dtype
        # a per-head K|V row is two head_dims wide; a latent row has no
        # head_dim and states its lanes
        head_dim = 0 if cache.v_aliases_k else cache.lanes // 2
        # the engine either runs the kernel or raises, here: its block
        # floor is the engine's (derived from the pool's dtype), and no
        # other attention path is selected behind its back
        if block_size is None:
            block_size = max(16, min_kv_block_for(kv_dtype or dtype))
        for grp in groups:
            check_kv_tile(kv_dtype or dtype, block_size,
                          lanes=grp.cache.lanes)
        if int(block_size) % spec.generation.block_length:
            raise ValueError(
                f"block_size {block_size} is no multiple of the model's "
                f"block_length {spec.generation.block_length}: a "
                f"diffusion block never straddles a cache block (its rows "
                f"are one rewrite of ops/kv_append.py and one DMA of the "
                f"attention kernel)")
        with _phase(phases_ms, "pallas_smoke"):
            pallas_smoke.ensure()
        self._key = jax.random.PRNGKey(int(seed))
        self._eid = _next_engine_id()
        self._min_bucket = int(min_bucket)    # the draft's prefill ladder
        # one block array and one page table a request for each cache
        # group of the spec; how many blocks each holds is the spec's to
        # say, not a knob (_group_block_counts)
        counts = _group_block_counts(groups, num_slots, max_len,
                                     block_size, num_blocks, prefill_budget)
        with _phase(phases_ms, "pool"):
            self._pool = PagedKVPool(
                len(groups[0].layers), num_slots, cache.rows,
                max_len, head_dim, block_size=block_size,
                num_blocks=counts[0], dtype=kv_dtype or dtype,
                mesh=mesh, mp_axis=mp_axis, lanes=cache.lanes,
                window=groups[0].window, more_groups=[
                    dict(num_layers=len(g.layers), num_heads=g.cache.rows,
                         lanes=g.cache.lanes, window=g.window, num_blocks=n)
                    for g, n in zip(groups[1:], counts[1:])],
                # a row a slot of every part of the spec's recurrent state
                state=(len(spec.state_layers), spec.state.parts)
                if spec.state is not None else None)
            # hierarchical KV cache (ISSUE 20): a bounded host-DRAM block
            # store behind the device prefix cache — LRU-evicted
            # refcount-0 blocks demote instead of dying, and a hit on a
            # demoted prefix promotes it back via async H2D copies the
            # scheduler overlaps with decode. Host DRAM, so hbm_budget
            # planning never bills it.
            self._host_tier = None
            if host_tier_bytes is not None:
                from .host_tier import HostBlockPool
                self._host_tier = HostBlockPool(
                    int(host_tier_bytes), self._pool.host_block_nbytes,
                    scale_nbytes=self._pool.host_scale_nbytes,
                    name=f"serving/host_tier#{self._eid}")
                self._pool.attach_host_tier(self._host_tier)
        # the first window group's W (0: none): what the launch counters
        # of the windowed walk are counted from
        self._window = next((g.window for g in groups if g.window), 0)
        # the prompt rows a launch holds at most (the scheduler's chunk
        # budget): with the slots and the generation rule, what the rows
        # of a program's tower are counted from (_tower_rows)
        self._chunk_budget = int(prefill_budget or max_len)
        self._sites = []              # every jit site of this engine
        self._fused_jits = {}         # (q bucket, table bucket) -> step
        # the fused step's "previous result" operand while no launch is
        # in flight: the shape of its own result ([slots | sentinel |
        # a routed model's counters]), never read (token_src -1)
        # (a block-generation step's result holds the block state too)
        from ..models.decoder_spec import ROUTED_COUNTERS
        from ..models.generation import block_result_layout
        routed = any(ls.routes for ls in spec.layers)
        self._no_prev = np.zeros(
            num_slots + 1 + ROUTED_COUNTERS * routed
            if spec.generation.block_length == 1 else block_result_layout(
                num_slots, spec.generation.block_length, routed)[-1],
            np.int32)
        if mesh is not None:
            # replicated over the mesh, as the result it stands in for
            # is: one operand type, so one trace a (q, table) bucket
            from jax.sharding import NamedSharding, PartitionSpec
            self._no_prev = jax.device_put(
                self._no_prev, NamedSharding(mesh, PartitionSpec()))
        self._spec_jits = {}          # (q, table) -> spec verify step
        self._copy_jit = None         # lazy COW device block copy
        self._closed = False
        self._close_lock = threading.Lock()
        # speculative decoding: a small draft
        # model proposes spec_k tokens per decode slot per cycle; the
        # target verifies all of them in ONE fused ragged launch
        self._spec = spec_draft is not None
        self._spec_k = int(spec_k)
        if self._spec:
            if self._spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self._init_draft(spec_draft, max_len)
        # fit-BEFORE-compile admission (ISSUE 18): statically plan the
        # LARGEST decode-path bucket + pool/scales ledger bytes against
        # the HBM budget (explicit, else the device limit when the
        # backend reports one — CPU reports none) and raise PlanError
        # naming the fattest program point before any compile. The plan
        # is a make_jaxpr trace of the RAW step builder — no AotSite,
        # no probe, no registry record, zero compiles.
        self._plan = None
        with _phase(phases_ms, "plan_gate"):
            self._hbm_budget_bytes = int(hbm_budget_bytes) \
                if hbm_budget_bytes is not None else _device_memory_limit()
            if self._hbm_budget_bytes is not None:
                self._plan = self.plan_replica(self._hbm_budget_bytes)
        # per-engine compute accounting (scheduler-thread writes, host
        # ints): FLOPs of the step programs actually DISPATCHED — the
        # (q, table)-bucket programs differ widely in cost, so stats()
        # must average what ran, not bill the largest bucket to every
        # cycle
        self._decode_flops_dispatched = 0.0
        self._decode_dispatches = 0
        with _phase(phases_ms, "scheduler"):
            self._sched = Scheduler(
                self._pool, self._run_admit, self._run_fused_step,
                max_queue=max_queue, prefill_budget=prefill_budget,
                do_copy=self._run_copy,
                do_spec_step=self._run_spec_step if self._spec else None,
                spec_k=self._spec_k, lane_weights=lane_weights,
                generation=spec.generation)
        self._startup = {
            "t_build": t_build,
            "build_ms": (time.perf_counter() - t_build) * 1e3,
            "phases_ms": phases_ms}
        # telemetry spine wiring (ISSUE 13): the engine joins the
        # statusz console and publishes its stats() island through the
        # labeled metrics registry ({engine=<id>} gauges/counters)
        _register_engine_telemetry(self)

    # -- client side -------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 32, *,
               do_sample: bool = False, temperature: float = 1.0,
               top_k: Optional[int] = None, top_p: Optional[float] = None,
               eos_token_id: Optional[int] = None,
               timeout: Optional[float] = None,
               tenant: str = "default",
               lane: str = "interactive",
               sink=None) -> GenerationRequest:
        """Enqueue one generation; returns its handle immediately.

        The handle streams tokens as they are produced
        (``handle.stream()``), blocks for the padded full sequence
        (``handle.result()``), and cancels mid-flight
        (``handle.cancel()``). ``timeout`` (seconds) is a hard deadline:
        a request that has not FINISHED by then fails with
        ``DeadlineExceeded``. A full admission queue raises
        ``QueueFullError`` here, synchronously.

        ``do_sample``/``temperature`` are per-request (traced values of
        the shared step program). ``top_k``/``top_p`` are NOT: they
        are static truncation structure baked into the engine's compile
        key at construction, so a differing per-request value here is
        rejected with :class:`ValueError` instead of silently retracing
        the decode step per sampling mix (the retrace-storm bug class
        the ``dispatch/retrace_cause`` counters exist to expose).

        ``tenant``/``lane`` tag the request's weighted-fair admission
        class (the HTTP front door sets them from the wire identity):
        the scheduler deficit-round-robins admission over the queued
        (lane, tenant) classes with per-lane weights
        (``GenerationEngine(lane_weights=...)``, default interactive 4
        : batch 1), so a batch flood cannot starve interactive TTFT.
        Untagged traffic all shares one class — plain FCFS.

        ``sink`` (anything with ``put(batch)``) takes the request's
        tokens and its terminal item in place of the handle's own queue
        — a launch's emissions for all the requests of one sink arrive
        in ONE ``put``, a list of ``(handle, item)`` in emit order (an
        int a token; at the end ``None`` or the error). ``stream()`` of
        such a handle refuses; ``result()`` is unchanged. The front
        door's one stream writer is such a sink."""
        if self._closed:
            raise RuntimeError("GenerationEngine is closed")
        if top_k is not None and int(top_k) != self._top_k:
            raise ValueError(
                f"per-request top_k={top_k} differs from the engine's "
                f"static top_k={self._top_k}: top_k is part of the decode "
                f"step's compile key — build a GenerationEngine("
                f"top_k={top_k}) instead of risking one retrace per "
                f"sampling mix")
        if top_p is not None and float(top_p) != self._top_p:
            raise ValueError(
                f"per-request top_p={top_p} differs from the engine's "
                f"static top_p={self._top_p}: top_p is part of the decode "
                f"step's compile key — build a GenerationEngine("
                f"top_p={top_p}) instead of risking one retrace per "
                f"sampling mix")
        if do_sample and self._decoder_spec.generation.block_length > 1:
            raise ValueError(
                "do_sample=True with block generation is not built: the "
                "step fixes a position's argmax, its confidence the "
                "argmax's probability (greedy, temperature 0)")
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if ids.size < 1:
            raise ValueError("prompt_ids must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        # sequences are aligned at virtual 0: only the true footprint
        # counts, and any feed up to max_len chunks through the step
        if ids.size + int(max_new_tokens) > self._pool.max_len:
            raise PoolCapacityError(
                f"prompt {ids.size} + max_new_tokens {max_new_tokens} "
                f"exceeds the pool's virtual capacity "
                f"{self._pool.max_len}; shorten the request or build "
                f"the engine with a larger max_len")
        req = GenerationRequest(
            ids, max_new_tokens, do_sample=do_sample,
            temperature=temperature, eos_token_id=eos_token_id,
            pad_token_id=self._pad, timeout=timeout,
            tenant=tenant, lane=lane, sink=sink)
        handle = self._sched.submit(req)   # QueueFullError propagates
        stat_add("serving/requests")       # counts ACCEPTED requests
        return handle

    def stream(self, prompt_ids, **kwargs) -> Iterator[int]:
        """``submit(...).stream()`` in one call: an iterator of token
        ids, yielded as each is produced."""
        return self.submit(prompt_ids, **kwargs).stream()

    def close(self, cancel_pending: bool = False) -> None:
        """Graceful shutdown: stop accepting work, DRAIN everything
        queued and in flight, then stop the scheduler thread. With
        ``cancel_pending`` the queue is cancelled instead of served
        (in-flight slots still finish)."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self._sched.close(cancel_pending=cancel_pending)
        # host tier after the scheduler: no more tier_tick/promotions
        # can be dispatched, so close() only has queued work to drain
        # (the spiller finishes in-flight demotions, then both worker
        # threads join)
        if self._host_tier is not None:
            self._host_tier.close()
        # a closed engine's pool is no longer an accounted HBM owner
        self._pool.drop_ledger()
        # ...nor a scraped metrics source or statusz row
        _metrics.unregister_collector(f"serving_engine/{self._eid}")
        _LIVE_ENGINES.discard(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- introspection -----------------------------------------------------
    @property
    def flight_recorder(self):
        """This engine's always-on :class:`~.flight_recorder.
        FlightRecorder` — the per-engine latency reservoirs and cycle
        ring the fleet aggregator pools."""
        return self._sched.recorder

    @property
    def num_slots(self) -> int:
        return self._pool.num_slots

    @property
    def queue_depth(self) -> int:
        return self._sched.queue_depth

    @property
    def active_requests(self) -> int:
        return self._sched.active

    def stats(self) -> dict:
        """One coherent operator snapshot — queue depth, in-flight
        requests, slot/block utilization and the prefix-cache hit ratio
        — so nobody has to scrape process-global monitor counters by
        ``serving/`` prefix (those aggregate across every engine ever
        constructed; this reads THIS engine's pool and scheduler).
        Host bookkeeping only: never blocks on the device."""
        pool = self._pool
        s = {
            "queue_depth": self._sched.queue_depth,
            "active_requests": self._sched.active,
            "num_slots": pool.num_slots,
            "slots_in_use": pool.n_active,
            "slot_utilization": pool.n_active / pool.num_slots,
            "preempts": self._sched.preempts,
            "requests_retired": self._sched.recorder.retired,
            # serving numerics sentinel (scheduler._note_nonfinite):
            # cycles whose logits carried a NaN/Inf — the flag
            # rides the existing per-cycle token fetch, zero extra syncs
            "nonfinite_cycles": self._sched.nonfinite_cycles,
        }
        # per-ENGINE latency percentiles, derived from this engine's own
        # retired request traces — the process-global serving/ttft_ms
        # histogram aggregates every engine ever constructed in the
        # process, so two engines (or back-to-back tests) would
        # contaminate each other's figures there
        s.update(self._sched.recorder.latency_summary())
        # SLO plane: once a tracker (or caller) armed a tail SLO on the
        # recorder, the per-replica goodput rate is part of the
        # operator snapshot — the fleet sums it, the autoscaler reads it
        rec = self._sched.recorder
        if rec.tail_slo_ms is not None:
            g = rec.goodput()
            s["goodput_rps"] = g["goodput_rps"]
            s["slo_violations"] = rec.slo_violations
        # per-tenant goodput split (front-door multi-tenancy): which
        # tenant's traffic is meeting the SLO, labeled per tenant in
        # the scraped serving_tenant_* series via the collector below
        tenants = rec.tenant_summary()
        if tenants:
            s["tenants"] = tenants
        s.update(self._compute_stats())
        s["startup"] = self._startup_stats()
        # KV memory, from the HBM ledger (profiler/memory.py — the pool
        # publishes capacity + in-use bytes there on every alloc/free)
        led = _memory.ledger()
        s["kv_pool_capacity_bytes"] = led.get(
            f"{pool.ledger_key}/capacity", pool.capacity_bytes)
        s["kv_bytes_in_use"] = led.get(
            f"{pool.ledger_key}/in_use", pool.bytes_in_use)
        hits, misses = pool.prefix_hits, pool.prefix_misses
        # tiered hit split (MIGRATION.md "prefix-hit split"): the
        # aggregate prefix_hit_ratio stays for dashboards; the
        # split keys say WHICH tier served each admission — hbm
        # (device trie), host (served through a promotion), miss.
        # Present tier or no tier (host is just 0 untiered).
        th = pool.tier_hits
        denom = max(1, th["hbm"] + th["host"] + th["miss"])
        s.update({
            "block_size": pool.block_size,
            "num_blocks": pool.num_blocks,
            "kv_blocks_in_use": pool.blocks_in_use,
            "block_utilization": pool.blocks_in_use / pool.num_blocks,
            "cached_blocks": pool.cached_blocks,
            "prefix_hits": hits,
            "prefix_misses": misses,
            "prefix_hit_ratio": hits / max(1, hits + misses),
            "tier_hits": dict(th),
            "prefix_hit_hbm": th["hbm"] / denom,
            "prefix_hit_host": th["host"] / denom,
            "prefix_miss": th["miss"] / denom,
            "prefill_tokens_saved": pool.tokens_saved,
            "prefix_evictions": pool.evictions,
            # tiered KV bytes: block storage vs the scale side-array
            # (zero for float pools) — int8 blocks are the whole
            # point of the ~2x-requests-per-budget win, so the
            # operator view must show where the bytes went
            "kv_dtype": pool.dtype.name,
            # block_storage_bytes is PER DEVICE (a sharded pool
            # divides its head axis over mp shards); on a
            # single-device pool shards == 1 and this is the total
            "kv_bytes": {
                "blocks": pool.block_storage_bytes,
                "scales": pool.scales_bytes,
            },
        })
        if len(pool.groups) > 1:
            s["cache_groups"] = [
                {"layers": len(g.layers), "window": g.window,
                 "rows": g.cache.rows, "lanes": g.cache.lanes,
                 "num_blocks": pool.groups[i].num_blocks,
                 "blocks_in_use": pool.group_blocks_in_use(i),
                 "block_bytes": pool.group_block_bytes(i)}
                for i, g in enumerate(self._decoder_spec.cache_groups)]
            s["window_blocks_freed"] = pool.window_blocks_freed
        if pool.state_parts:
            # the recurrent state a slot, beside the blocks: a slot IS
            # its row of every part
            s["kv_bytes"]["state"] = pool.state_bytes
            s["state"] = {
                "layers": len(self._decoder_spec.state_layers),
                "parts": {name: list(shape[2:])
                          for name, shape, _ in pool.state_parts},
                "dtype": pool.state_parts[0][2].name,
                "slot_bytes": pool.state_slot_bytes,
                "slots_in_use": pool.n_active,
                "live_bytes": pool.state_live_bytes}
        if self._host_tier is not None:
            # hierarchical tier snapshot: host capacity/occupancy,
            # demotion/promotion volumes, and the end-to-end
            # promotion latency (ticket creation -> adoption) —
            # the "did the second tier pay for itself" numbers
            s["host_tier"] = self._host_tier.stats()
        if self._mp > 1:
            s["mp"] = self._mp
            s["mp_axis"] = self._mp_axis
            s["kv_bytes_per_device"] = pool.block_storage_bytes
        # chunked-prefill observability: lifetime chunk counters
        # plus ring-window chunk token throughput, so the "long
        # prompts no longer monopolize a cycle" win is measurable
        # (ONE ring pass serves the spec figures below too)
        s["prefill_chunks"] = self._sched.prefill_chunks
        s["chunked_prefill_tokens"] = self._sched.chunk_tokens
        thr = self._sched.recorder.cycle_throughput()
        if thr["cycle_secs"] > 0 and thr["chunk_tokens"] > 0:
            s["chunked_prefill_tokens_per_sec"] = \
                thr["chunk_tokens"] / thr["cycle_secs"]
        if self._spec:
            # the two numbers that prove (or disprove) the multiplier:
            # how often the draft agrees, and how many tokens a decode
            # slot actually nets per cycle (1.0 = plain decode)
            s["spec_k"] = self._spec_k
            s["spec_cycles"] = self._sched.spec_cycles
            s["spec_proposed"] = self._sched.spec_proposed
            s["spec_accepted"] = self._sched.spec_accepted
            s["spec_accept_rate"] = self._sched.spec_accepted \
                / max(1, self._sched.spec_proposed)
            if thr["spec_slots"] > 0:
                s["spec_tokens_per_cycle"] = \
                    thr["spec_emitted"] / thr["spec_slots"]
            s["draft_layers"] = \
                self._draft_gpt.cfg.num_hidden_layers
            s["kv_bytes"]["draft"] = \
                int(np.prod(self._draft_shape)) \
                * np.dtype(self._draft_dtype).itemsize
        return s

    def _aot_site(self, name: str, fn, donate_argnums):
        """One of this engine's jit sites: its builds are stamped with
        the launch that asked for them (``scheduler.note_build``) and
        listed in ``stats()["startup"]``."""
        site = _registry.aot_site(name, fn, donate_argnums=donate_argnums,
                                  on_build=self._sched.note_build)
        self._sites.append(site)
        return site

    def _startup_stats(self) -> dict:
        """What standing this engine up took, from the inside:
        ``t_build`` (``time.perf_counter()`` at the constructor's entry
        — the flight recorder's clock), ``build_ms`` and its
        ``phases_ms`` (the ``startup/*`` spans), and ``programs``: every
        build event of this engine's sites, oldest first
        (``program_registry.ProgramRecord.builds`` — the ``program/*``
        spans' times, the persistent cache's hits and misses, the first
        call) under its ``site``, with the ``launch_rows`` and
        ``slots_active`` of the launch that asked for the program
        (``scheduler.note_build``; ``None`` until its first call
        returned). A program built after the warm-up is listed like any
        other: its ``at`` says when."""
        programs = [
            {"site": site.site, "launch_rows": None, "slots_active": None,
             **build}
            for site in list(self._sites)
            for build in list(site.record.builds)]
        programs.sort(key=lambda p: p["at"])
        return {**self._startup, "programs": programs}

    def _compute_stats(self) -> dict:
        """Model-FLOPs-per-token and serving MFU, from the fused
        step's program-registry cost analysis (``serving/fused[...]`` AOT
        sites). One decode launch advances EVERY slot one token, so
        flops-per-token = step FLOPs / num_slots (the full-batch cost —
        a partially occupied batch still pays it, which is exactly what
        an operator sizing capacity wants to see). Throughput comes
        from THIS engine's flight-recorder cycle ring; MFU needs a
        known device peak (``program_registry.peak_flops``, env-
        overridable) — absent one (CPU), raw FLOP/s are reported."""
        if not self._decode_dispatches:
            return {}
        mean_step_flops = \
            self._decode_flops_dispatched / self._decode_dispatches
        if not mean_step_flops:
            return {}
        out = {}
        S = self._pool.num_slots
        out["model_flops_per_token"] = mean_step_flops / S
        rec = None
        if self._fused_jits:
            rec = self._fused_jits[max(self._fused_jits)].record
        if rec is not None and rec.bytes_accessed and rec.flops:
            # scale the largest bucket's bytes by the mean-cost ratio so
            # bytes-per-token tracks what actually ran, like the FLOPs
            out["decode_bytes_per_token"] = \
                rec.bytes_accessed * (mean_step_flops / rec.flops) / S
        thr = self._sched.recorder.cycle_throughput()
        if thr["cycle_secs"] > 0 and thr["decode_cycles"] > 0:
            out["decode_tokens_per_sec"] = \
                thr["emitted"] / thr["cycle_secs"]
            # FLOPs summed per cycle IN the ring (same window as the
            # wall-time denominator; sweep-only/drain cycles contribute
            # wall but zero FLOPs); the lifetime mean is only the
            # fallback for rings recorded before the engine attached
            flops_in_ring = thr["decode_flops"] or \
                mean_step_flops * thr["decode_cycles"]
            achieved = flops_in_ring / thr["cycle_secs"]
            out["serving_flops_per_sec"] = achieved
            peak = _registry.peak_flops()
            if peak:
                out["serving_mfu"] = achieved / peak
        return out

    def dump_flight_recorder(self, path: Optional[str] = None) -> dict:
        """Postmortem snapshot of the scheduler's always-on flight
        recorder — the last N cycle records (sweep/admit/prefill/
        decode-dispatch/host-fetch breakdown, occupancy, queue depth)
        and the tail of every request's lifecycle events — plus this
        engine's :meth:`stats` snapshot. Written to ``path`` as JSON
        when given; also dumped AUTOMATICALLY (to a temp file, path in
        ``engine._sched.recorder.last_dump_path``) when a step failure
        poisons the in-flight requests, so a production stall is
        debuggable without the profiler ever having been armed."""
        return self._sched.recorder.dump(path, extra={"engine":
                                                      self.stats()})

    def analyze(self, passes=None):
        """PR-3 pre-flight of THE step: trace the jitted program
        (donation contract auto-read from the pjit eqn) and run the
        analysis pipeline. The clean-bill contract is zero
        error-severity findings — donation-safe, no host sync in the
        hot loop; asserted by ``bench.py --dry-run`` and the tier-1
        tests. Tracing hits jit's signature cache, so this never
        retraces (the probe counters stay honest). Analyzes the LARGEST
        built (q, table) bucket (the step that actually served), falling
        back to the smallest on a fresh engine."""
        from .. import analysis

        S = self._pool.num_slots
        if self._spec and self._spec_jits:
            # the speculative verify program (largest built bucket):
            # zeroed metadata is a legal no-op launch, and n_spec = 0
            # everywhere keeps the rejection sampler on its base path
            from ..ops.ragged_paged_attention import BLOCK_Q
            Q, T = max(self._spec_jits)
            K = self._spec_k
            R = self._tower_rows(Q)       # the per-row operands' axis
            V = self._decoder_spec.vocab_size
            scales = (self._pool.scales,) if self._pool.quantized else ()
            return analysis.analyze(
                self._spec_step_fn(Q, T), self._params, self._buffers,
                self._pool.data, *scales, np.zeros(R, np.int32),
                np.zeros(R, np.int32), np.zeros(R, np.int32),
                np.zeros(R, np.int32), np.zeros(Q // BLOCK_Q, np.int32),
                np.zeros(S, np.int32), np.zeros(S, np.int32),
                np.zeros((S, T), np.int32), np.zeros(S, np.int32),
                np.zeros(S, np.int32), np.zeros(S, np.int32),
                np.zeros(S, np.int32), np.zeros((S, K), np.int32),
                np.zeros((S, K, V), np.float32), np.zeros(S, bool),
                np.ones(S, np.float32), self._key, passes=passes,
                name=f"serving.spec_verify[{S} slots, k{K}, q{Q}, t{T}]")
        # zeroed metadata is a legal no-op launch: blk_seq 0 maps every
        # q block to slot 0 with kv_len 0, so the KV walk runs zero
        # iterations
        from ..ops.ragged_paged_attention import BLOCK_Q
        Q, T = max(self._fused_jits) if self._fused_jits \
            else (BLOCK_Q, 1)
        scales = (self._pool.scales,) if self._pool.quantized else ()
        return analysis.analyze(
            self._fused_step_fn(Q, T), self._params, self._buffers,
            self._pool_operand(), *scales,
            *self._null_step_operands(Q, T),
            passes=passes,
            name=f"serving.fused_step[{S} slots, q{Q}, t{T}]")

    def _pool_operand(self):
        """The step's pool operand: the block array, or every cache
        group's as a tuple where the spec has more than one; paired with
        the slots' state arrays where the spec's layers hold a recurrent
        state."""
        pool = self._pool
        blocks = pool.data if len(pool.groups) == 1 else pool.group_data
        return (blocks, pool.state_data) if pool.state_parts else blocks

    def _rebind_pool(self, operand) -> None:
        """What a donated step gave back for :meth:`_pool_operand`."""
        pool = self._pool
        if pool.state_parts:
            operand, pool.state_data = operand
        if len(pool.groups) > 1:
            pool.group_data = operand
        else:
            pool.data = operand

    def _null_step_operands(self, Q: int, T: int) -> tuple:
        """The fused step's operands after the pool, zeroed: a legal
        no-op launch of the (Q, T) program (``analyze``, the plan gate)."""
        from ..ops.ragged_paged_attention import BLOCK_Q
        S = self._pool.num_slots
        R = self._tower_rows(Q)       # the per-row operands' axis
        i32 = lambda *shape: np.zeros(shape, np.int32)
        G = len(self._pool.groups)
        # write targets, tables and floors are one a cache group
        each = i32 if G == 1 else \
            (lambda *shape: tuple(i32(*shape) for _ in range(G)))
        head = (i32(R), i32(R), each(R), i32(R), i32(Q // BLOCK_Q), i32(S),
                i32(S), each(S, T), each(S), i32(S))
        B = self._decoder_spec.generation.block_length
        if B > 1:
            from ..models.generation import BLOCK_UNFIXED
            return head + (
                self._no_prev, np.full(S, -1, np.int32), i32(S, B),
                np.full((S, B), BLOCK_UNFIXED, np.int32),
                np.full(R, -1, np.int32), np.full(S, -1, np.int32), i32(S),
                self._key)
        return head + (i32(S), self._no_prev, np.full(R, -1, np.int32),
                       np.zeros(S, bool), np.ones(S, np.float32), self._key)

    def plan_replica(self, hbm_budget_bytes: Optional[int] = None,
                     top_k: int = 4) -> dict:
        """Static fit-before-compile HBM plan of this replica's worst
        case (ISSUE 18): donation-aware liveness
        (``analysis/liveness.py``) over the LARGEST bucket this engine
        can dispatch — the spec-verify / fused step at the full-slot q
        bucket and max table bucket — with the pool+scales ledger bytes attributed PER DEVICE (a head-sharded
        pool's global-shape operand is swapped for its per-device
        ``capacity_bytes``). Trace-only: the RAW step builder goes
        through ``jax.make_jaxpr`` with no AotSite, no probe and no
        registry record, so ``compile/count`` does not move — proven by
        the bench.py dry-run canary. Raises :class:`PlanError` naming
        the fattest program point when ``hbm_budget_bytes`` (or the
        construction-time budget) is exceeded; the same call is the
        elastic scale-out path's dry admission check."""
        from ..analysis import liveness

        budget = int(hbm_budget_bytes) if hbm_budget_bytes is not None \
            else self._hbm_budget_bytes
        S = self._pool.num_slots
        params, buffers = self._params, self._buffers
        pool = self._pool
        scales = (pool.scales,) if pool.quantized else ()

        from ..ops.ragged_paged_attention import BLOCK_Q
        T = pool.max_table_len
        if self._spec:
            from ..models.generation import build_spec_verify_fn
            K = self._spec_k
            # each speculating slot contributes k+1 ragged rows,
            # padded to whole q blocks
            blocks_per_slot = -(-(K + 1) // BLOCK_Q)
            Q = self._q_bucket(S * blocks_per_slot * BLOCK_Q)
            V = self._decoder_spec.vocab_size
            fn = build_spec_verify_fn(
                self._model, S, Q, K, T, pool.block_size,
                top_k=self._top_k, top_p=self._top_p,
                quantized=pool.quantized, qmax=pool.qmax or 127.0)
            R = self._tower_rows(Q)
            args = (params, buffers, pool.data, *scales,
                    np.zeros(R, np.int32), np.zeros(R, np.int32),
                    np.zeros(R, np.int32), np.zeros(R, np.int32),
                    np.zeros(Q // BLOCK_Q, np.int32),
                    np.zeros(S, np.int32), np.zeros(S, np.int32),
                    np.zeros((S, T), np.int32), np.zeros(S, np.int32),
                    np.zeros(S, np.int32), np.zeros(S, np.int32),
                    np.zeros(S, np.int32), np.zeros((S, K), np.int32),
                    np.zeros((S, K, V), np.float32),
                    np.zeros(S, bool), np.ones(S, np.float32),
                    self._key)
            flavor, site = "spec", f"spec_verify[q{Q},t{T}]"
        else:
            # a slot's widest plan: one row, or a block's commit riding
            # with the next block (2 B rows), in whole q blocks
            B = self._decoder_spec.generation.block_length
            rows = 2 * B if B > 1 else 1
            Q = self._q_bucket(S * -(-rows // BLOCK_Q) * BLOCK_Q)
            if self._mesh is not None:
                from ..models.generation import \
                    build_sharded_fused_step_fn
                fn = build_sharded_fused_step_fn(
                    self._model, S, Q, T, pool.block_size,
                    self._mesh, mp_axis=self._mp_axis,
                    top_k=self._top_k, top_p=self._top_p)
            else:
                from ..models.generation import build_fused_step_fn
                fn = build_fused_step_fn(
                    self._model, S, Q, T, pool.block_size,
                    top_k=self._top_k, top_p=self._top_p,
                    quantized=pool.quantized, qmax=pool.qmax or 127.0)
            args = (params, buffers, self._pool_operand(), *scales,
                    *self._null_step_operands(Q, T))
            flavor, site = "fused", f"fused_step[q{Q},t{T}]"
        donate = (2, 3) if pool.quantized else (2,)
        rep = liveness.callable_liveness(fn, *args, donate_argnums=donate,
                                         top_k=top_k)

        # per-device pool attribution: the step's operand carries the
        # pool at its GLOBAL shape; a head-sharded engine holds only
        # capacity_bytes of it per device (paging.py's ledger figure)
        def _nbytes(a):
            return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize

        operand_pool = sum(_nbytes(a) for a in pool.group_data) \
            + sum(_nbytes(s) for s in scales) \
            + sum(_nbytes(a) for a in pool.state_data)
        # the slots' recurrent state is taken first: what is left is the
        # block arrays'
        per_device_pool = pool.capacity_bytes + pool.state_bytes
        total = rep.static_peak_bytes - operand_pool + per_device_pool

        pk = rep.peak
        plan = {
            "site": f"serving.{site}[{S} slots]#{self._eid}",
            "flavor": flavor, "q_bucket": Q, "table_bucket": T,
            "step_peak_bytes": int(rep.static_peak_bytes),
            "pool_bytes": int(per_device_pool),
            "state_bytes": int(pool.state_bytes),
            "group_blocks": [g.num_blocks for g in pool.groups],
            "static_peak_bytes": int(total),
            "budget_bytes": budget,
            "fits": None if budget is None else bool(total <= budget),
            "headroom_bytes": None if budget is None
            else int(budget - total),
            "peak_point": pk.as_dict() if pk else None,
            "timeline": [p.as_dict() for p in rep.timeline],
        }
        if self._host_tier is not None:
            # informational only: host DRAM, deliberately NOT added to
            # static_peak_bytes — the HBM fit check must never bill
            # the spill tier against the device budget
            plan["host_tier_bytes"] = self._host_tier.capacity_bytes
        if plan["fits"] is False:
            raise PlanError(
                f"replica does not fit: static peak {total:,} B "
                f"(largest {flavor} bucket q{Q} t{T} + "
                f"pool ledger {per_device_pool:,} B) exceeds "
                f"hbm_budget_bytes={budget:,} — fattest program point: "
                f"{pk.primitive if pk else 'n/a'} with "
                f"{pk.live_bytes:,} B live at "
                f"{(pk.source if pk else None) or 'unknown source'}",
                plan)
        return plan

    # -- device side (called from the scheduler thread only) ---------------
    def _run_admit(self, req: GenerationRequest, slot: int) -> None:
        """Admit one request (the scheduler's ``do_prefill``): pure host
        bookkeeping, no prefill program. Blocks covering the whole feed
        are reserved, a prefix-cache match adopts its blocks (ANY tail
        length — chunks drain a long tail in budgeted launches), and the
        remaining tokens arm ``req.pending_feed`` for the per-cycle
        chunk plan. A re-admitted (preempted) request's feed is its
        prompt plus everything it already generated."""
        pool = self._pool
        if self._spec:
            # this slot's previous occupant's draft cache is stale: the
            # next speculative cycle re-syncs via a draft prefill
            self._draft_synced[slot] = False
        feed = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        # block generation prefills WHOLE blocks: the feed is cut at the
        # last block boundary and what is left over opens the first
        # block, already fixed (a block in the middle of its passes at a
        # preemption starts again all masked: only emitted tokens are
        # fed). With block_length 1 nothing is left over
        B = self._decoder_spec.generation.block_length
        cut = feed.size // B * B
        req.block_given = [int(t) for t in feed[cut:]]
        req.block_pass, req.block_state = 0, None
        cached = pool.match_prefix(feed)
        if cached:
            pool.admit_cached(slot, cached)
            pool.note_tier_hit(
                "host" if req._tier_promoted else "hbm")
            # whole cache blocks, so whole diffusion blocks (block_size
            # is a multiple of B), and under the block mask their K/V
            # depend on nothing past them
            m = len(cached) * pool.block_size
            pool.set_slot(slot, pos=m, lo=0)
            req.pending_feed = [int(t) for t in feed[m:cut]]
            req.trace.mark("prefix_hit", tokens_saved=m,
                           pending=len(req.pending_feed))
        else:
            pool.note_tier_hit("miss")
            pool.admit_fresh(slot, feed.size)
            # position 0 is where the first pending token's K/V land
            pool.set_slot(slot, pos=0, lo=0)
            req.pending_feed = [int(t) for t in feed[:cut]]

    def _ragged_operands(self, slot_requests, plan, spec=None,
                         from_prev=()):
        """Host-side flattened ragged-row operands shared by the fused
        step and the speculative verify launch: per-slot contiguous
        rows, page-table-resolved write targets, and the
        scalar-prefetch metadata. TWO row axes (``models/generation.py
        _row_axes``): the kernel's metadata (``blk_seq``, ``seq_qstart``)
        describes the ``Q`` rows of the program, each slot's padded to
        whole q blocks; the per-row operands (``token_ids``, ``qpos``,
        ``write_block``, ``write_off``, ``token_src``, ``row_blk``) and
        ``last_row``'s values are on the program's ``R = _tower_rows(Q)``
        TOWER rows, the same slots in the same order back to back (one
        axis where ``R == Q``). ``Q`` is the smallest bucket that holds
        the launch's padded rows AND whose ``R`` holds its real ones.
        Speculating slots (``spec``)
        contribute their candidate rows with only ``last_token``
        host-known — the draft tokens overlay on the device inside the
        verify program. The decode rows of the slots in ``from_prev``
        have no host token yet (it is in the un-fetched result of the
        launch in flight): ``token_src`` names the slot at such a row
        and -1 everywhere else.

        Under block generation (the spec's ``block_length`` B > 1) a
        decode slot's rows are the B rows of its current block at the
        block's positions, every pass of it; what the rows hold is the
        block's state, which the step reads from the launch in flight
        (``from_prev``) or from the ``block`` operands returned last:
        ``(state_src [S], blk_tok [S, B], blk_pass [S, B], row_blk [Q],
        pass_idx [S], ride [S])`` (``models/generation.py
        _build_block_step_fn``). A slot whose block is finished and whose
        plan is 2 B rows RIDES: the finished block's rows, which show its
        final tokens (the commit), then the next block's at the B
        positions after them, all the mask id, in its pass 0 — one q
        block of the kernel where 2 B is ``BLOCK_Q``, ``kv_len`` to the
        new block's end. With B rows it commits alone.

        A spec with more than one cache group gets ``write_block``,
        ``tables`` and ``lo`` as tuples, one entry a group (a row's offset
        in its block is the same in all): a window group's table names
        the scratch block where it has freed, and its ``lo`` is the first
        position the slot still holds there. The ``(Q, T)`` program is
        named by the first group's table bucket — every group's table
        covers the same virtual blocks."""
        from ..ops.ragged_paged_attention import (BLOCK_Q, kv_group_blocks,
                                                  q_step_blocks,
                                                  ragged_layout,
                                                  ragged_walk_counts)

        pool = self._pool
        S = pool.num_slots
        bs = pool.block_size
        gen = self._decoder_spec.generation
        B = gen.block_length
        q_lens = [0] * S
        pos0s = [0] * S
        row_tokens = {}
        kv_len = np.zeros(S, np.int32)
        sample_mask = np.zeros(S, bool)
        temps = np.ones(S, np.float32)
        n_spec = np.zeros(S, np.int32)
        for slot, req in slot_requests.items():
            n = int(plan.get(slot, 0))
            if n < 1:
                continue
            p = pool.slot_pos(slot)
            q_lens[slot] = n
            pos0s[slot] = p
            kv_len[slot] = p + n
            sample_mask[slot] = req.do_sample
            temps[slot] = req.temperature
            if spec and slot in spec:
                n_spec[slot] = n
                row_tokens[slot] = [req.last_token]
            elif B > 1 and not req.pending_feed:
                # the block's rows show its state (filled in on the
                # device); rows past them open the next block, all masked
                row_tokens[slot] = [0] * B + [gen.mask_token_id] * (n - B)
            elif slot in from_prev:
                row_tokens[slot] = []
            else:
                row_tokens[slot] = (req.pending_feed[:n]
                                    if req.pending_feed
                                    else [req.last_token])
        padded = sum(-(-n // BLOCK_Q) * BLOCK_Q for n in q_lens if n)
        Q = self._launch_bucket(padded, sum(q_lens))
        R = self._tower_rows(Q)
        blk_seq, qstart, pos0, last_row, _ = ragged_layout(
            q_lens, pos0s, q_bucket=Q)
        # a slot's first TOWER row: its first kernel row, or — the tower
        # on an axis of its own — the real rows of the slots before it
        row0 = qstart
        if R != Q:
            lens = np.asarray(q_lens, np.int32)
            row0 = (np.cumsum(lens) - lens).astype(np.int32)
            last_row = np.where(lens > 0, row0 + lens - 1, 0).astype(
                np.int32)
        token_ids = np.zeros(R, np.int32)
        qpos = np.zeros(R, np.int32)
        G = len(pool.groups)
        # pad rows -> scratch block, in every group
        write_blocks = [np.zeros(R, np.int32) for _ in range(G)]
        write_block = write_blocks[0]
        write_off = np.zeros(R, np.int32)
        token_src = np.full(R, -1, np.int32)
        block = None
        if B > 1:
            from ..models.generation import BLOCK_UNFIXED
            state_src = np.full(S, -1, np.int32)
            blk_tok = np.zeros((S, B), np.int32)
            blk_pass = np.full((S, B), BLOCK_UNFIXED, np.int32)
            row_blk = np.full(R, -1, np.int32)
            pass_idx = np.full(S, -1, np.int32)
            ride = np.zeros(S, np.int32)
            for slot, req in slot_requests.items():
                if not q_lens[slot] or req.pending_feed:
                    continue
                r0 = int(row0[slot])
                row_blk[r0:r0 + B] = slot * B + np.arange(B)
                if not req.block_commits_next(gen):
                    pass_idx[slot] = req.block_pass
                elif q_lens[slot] > B:
                    ride[slot], pass_idx[slot] = 1, 0
                if slot in from_prev:
                    state_src[slot] = slot
                else:
                    blk_tok[slot], blk_pass[slot] = req.block_input(B)
            block = (state_src, blk_tok, blk_pass, row_blk, pass_idx, ride)
        else:
            for slot in from_prev:
                token_src[int(row0[slot])] = slot
        for slot, toks in row_tokens.items():
            r0, p0 = int(row0[slot]), int(pos0[slot])
            table = pool.slot_table(slot)
            for i in range(q_lens[slot]):
                if i < len(toks):
                    token_ids[r0 + i] = toks[i]
                qpos[r0 + i] = p0 + i
                write_block[r0 + i] = table[(p0 + i) // bs]
                write_off[r0 + i] = (p0 + i) % bs
            for g in range(1, G):
                table = np.asarray(pool.slot_table(slot, g), np.int32)
                at = p0 + np.arange(q_lens[slot])
                write_blocks[g][r0:r0 + q_lens[slot]] = table[at // bs]
        T = max(pool.table_bucket(s) for s in row_tokens)
        tables = pool.table_array(T, row_tokens)
        lo = np.zeros(S, np.int32)            # paged virtual floor
        more = {}
        los = [lo] + [np.zeros(S, np.int32) for _ in range(1, G)]
        for g, grp in enumerate(pool.groups):
            if grp.window:           # what the group has not freed yet
                for slot in row_tokens:
                    los[g][slot] = pool.slot_lo(slot, g)
        if G > 1:
            tables = (tables,) + tuple(
                pool.table_array(T, row_tokens, g) for g in range(1, G))
            lo, write_block = tuple(los), tuple(write_blocks)
        if self._window:
            # what a window layer must read, and the pairs under its mask:
            # a slot's rows see W - 1 tokens behind the first of them,
            # a row at p its last min(p + 1, W)
            W = self._window
            more = dict(
                kv_tokens_window=sum(
                    min(int(kv_len[s]), W - 1 + n)
                    for s, n in enumerate(q_lens) if n),
                kv_row_tokens_window=sum(
                    int(np.minimum(pos0s[s] + np.arange(n) + 1, W).sum())
                    for s, n in enumerate(q_lens) if n))
        # what the first group's kernel does on this layout, a layer: one
        # KV block a step, one wait for a group of G blocks a fetch (G and
        # the grid step's M q blocks as the kernel reads them from its
        # pool shard's shape)
        dspec = self._decoder_spec
        first = dspec.cache_groups[0]
        if first.cache.v_aliases_k:
            # the latent kernel: every q block of a slot walks that
            # slot's whole context
            from ..ops.mla_paged_attention import latent_group_blocks
            group = latent_group_blocks(bs, pool.lanes, pool.dtype)
            walks = [(-(-n // BLOCK_Q), -(-int(kv_len[s]) // bs))
                     for s, n in enumerate(q_lens) if n]
            walked = dict(
                kv_steps=sum(qb * kb for qb, kb in walks),
                kv_fetches=sum(qb * -(-kb // group) for qb, kb in walks),
                q_blocks=sum(qb for qb, _ in walks), q_blocks_wide=0)
        else:
            heads = pool.num_heads // self._mp
            walked = ragged_walk_counts(
                blk_seq, qstart, pos0, los[0], kv_len, T,
                step_blocks=q_step_blocks(
                    heads, first.q_group, bs, pool.lanes, pool.dtype,
                    v_lanes=first.cache.v_lanes if first.cache.k_lanes
                    else 0, q_blocks=Q // BLOCK_Q),
                block_size=bs, group=kv_group_blocks(
                    heads, bs, 0, pool.dtype, lanes=pool.lanes),
                mask_block=B, window=first.window)
        step = self._spec_step_fn(Q, T) if spec is not None \
            else self._fused_step_fn(Q, T)
        self._sched.note_launch(
            rows=sum(q_lens), q=Q, t=T, program=step.jitted.__name__,
            tower_rows=R, kv_tokens=int(kv_len.sum()), **walked,
            # under the block mask a row sees to the end of its block
            kv_row_tokens=sum(
                n * pos0s[s] + n * (n + 1) // 2 if B == 1 else int(
                    np.minimum(((pos0s[s] + np.arange(n)) // B + 1) * B,
                               pos0s[s] + n).sum())
                for s, n in enumerate(q_lens) if n),
            # a slot's rows are consecutive positions: the blocks from
            # its first row's to its last row's, each rewritten once a
            # layer by ops.kv_append
            kv_write_blocks=sum(
                (pos0s[s] + n - 1) // bs - pos0s[s] // bs + 1
                for s, n in enumerate(q_lens) if n), **more,
            # the slots whose recurrent state the launch reads and
            # writes, the real rows through the mixer and those of them
            # in a sequence of more than one (the chunked scan's)
            **(dict(state_slots=sum(1 for n in q_lens if n),
                    ssm_rows=sum(q_lens),
                    ssm_chunk_rows=sum(n for n in q_lens if n > 1))
               if pool.state_parts else {}),
            # the layers that read and write the pool — what a reader
            # multiplies ``kv_tokens`` by — stamped only where some do not
            cache_layers=len(dspec.cache_layers)
            if len(dspec.cache_layers) < len(dspec.layers) else None,
            # and the layers that read and write the slots' state, where
            # some (and not all) do
            state_layers=len(dspec.state_layers)
            if 0 < len(dspec.state_layers) < len(dspec.layers) else None)
        return (Q, T, (token_ids, qpos, write_block, write_off, blk_seq,
                       qstart, pos0, tables, lo, kv_len, last_row),
                n_spec, sample_mask, temps, token_src, block)

    def _run_fused_step(self, slot_requests, plan, prev=None):
        """Dispatch ONE fused ragged launch (the scheduler's
        ``do_chunked_step``): budgeted prompt chunks + decode rows,
        flattened into the padded row layout of
        ``ops.ragged_paged_attention`` and served by the
        ``build_fused_step_fn`` program for this (q bucket, table
        bucket). ``prev`` is ``(result, slots)`` when the launch in
        flight holds the input token of these slots' decode rows: its
        un-fetched result goes back in as an operand and the token never
        visits the host. Returns the next-token DEVICE array
        un-fetched."""
        pool = self._pool
        prev_toks, from_prev = prev if prev is not None \
            else (self._no_prev, ())
        Q, T, ops, _, sample_mask, temps, token_src, block = \
            self._ragged_operands(slot_requests, plan,
                                  from_prev=from_prev)
        step = self._fused_step_fn(Q, T)
        if block is not None:
            # the block step takes the block state where the one-token
            # step takes last_row and the sampling operands
            args = ops[:-1] + (prev_toks,) + block + (self._key,)
        else:
            args = ops + (prev_toks, token_src, sample_mask, temps,
                          self._key)
        if pool.quantized:
            pool.data, pool.scales, nxt, self._key = step(
                self._params, self._buffers, pool.data, pool.scales,
                *args)
        else:
            # every cache group's array (and the state arrays) is donated
            # and comes back
            operand, nxt, self._key = step(
                self._params, self._buffers, self._pool_operand(), *args)
            self._rebind_pool(operand)
        self._note_decode_dispatch(step)
        return nxt

    def _q_bucket(self, rows: int) -> int:
        """pow2 bucket over the launch's padded q rows — one fused
        trace per (q bucket, table bucket)."""
        from ..ops.ragged_paged_attention import BLOCK_Q
        b = BLOCK_Q
        while b < rows:
            b *= 2
        return b

    def _decode_rows(self) -> int:
        """The most real rows a decode slot holds in a launch: 1, or 2 B
        under block generation (a block's commit riding with the next
        block's first pass), or ``spec_k`` candidates under speculation."""
        B = self._decoder_spec.generation.block_length
        return 2 * B if B > 1 else self._spec_k if self._spec else 1

    def _tower_rows(self, Q: int) -> int:
        """``R(Q)``: the rows the ``Q`` bucket's programs run their tower
        on (``ops.ragged_paged_attention.tower_rows``) — from the slots,
        the chunk budget and :meth:`_decode_rows`. No bucket of its own
        and no knob: a function of ``Q`` and of what the engine was built
        with. The tensor-parallel step is a tower of its own on one axis
        (``_mp_fused_tower``): ``R == Q`` there."""
        if self._mesh is not None:
            return int(Q)
        from ..ops.ragged_paged_attention import tower_rows
        return tower_rows(Q, self._pool.num_slots, self._chunk_budget,
                          self._decode_rows())

    def _launch_bucket(self, padded: int, real: int) -> int:
        """The ``Q`` bucket of a launch of ``padded`` kernel rows of
        which ``real`` are real: the smallest whose kernel rows hold the
        first and whose tower rows hold the second. A launch of decode
        rows beside a chunk fits the bucket of its padded rows; a chunk
        with few decode rows beside it (the ramp's) goes one up and pays
        pad q blocks, which the kernel skips."""
        most = self._pool.num_slots * self._decode_rows() \
            + self._chunk_budget
        if real > most and self._mesh is None:
            raise ValueError(
                f"a launch of {real} real rows fits no program: "
                f"{self._pool.num_slots} slots' decode rows and a chunk "
                f"budget of {self._chunk_budget} are {most}")
        Q = self._q_bucket(padded)
        while self._tower_rows(Q) < real:
            Q *= 2
        return Q

    def _fused_step_fn(self, q_rows: int, table_len: int):
        key = (q_rows, table_len)
        fn = self._fused_jits.get(key)
        if fn is None:
            from ..models.generation import (build_fused_step_fn,
                                             build_sharded_fused_step_fn)
            probe = _probe.site(
                f"serving/fused[q{q_rows},t{table_len}]#{self._eid}")
            if self._mesh is not None:
                built = build_sharded_fused_step_fn(
                    self._model, self._pool.num_slots, q_rows,
                    table_len, self._pool.block_size, self._mesh,
                    mp_axis=self._mp_axis, top_k=self._top_k,
                    top_p=self._top_p, probe=probe)
            else:
                built = build_fused_step_fn(
                    self._model, self._pool.num_slots, q_rows,
                    table_len, self._pool.block_size,
                    top_k=self._top_k, top_p=self._top_p, probe=probe,
                    quantized=self._pool.quantized,
                    qmax=self._pool.qmax or 127.0)
            fn = self._aot_site(
                f"serving/fused[q{q_rows},t{table_len}]#{self._eid}",
                built,
                donate_argnums=(2, 3) if self._pool.quantized else (2,))
            self._fused_jits[key] = fn
        return fn

    # -- speculative decoding (draft propose + fused verify) ---------------
    def _init_draft(self, spec_draft, max_len) -> None:
        """Set up the draft side of speculative decoding: resolve the
        draft model (``"auto"`` builds a 2-layer GPT sharing the
        target's embeddings via ``models.generation.make_draft_model``),
        snapshot its params, and allocate its DENSE per-slot KV pool —
        the draft is small, so worst-case stripes cost little, and the
        dense layout needs no page-table bookkeeping. Draft positions
        mirror the target pool's ``slot_pos`` exactly (both write a
        row's K/V when the row is fed), so the only per-slot draft
        state is a 'synced' flag."""
        import jax.numpy as jnp

        from ..models.generation import make_draft_model
        from ..nn.layer.layers import get_buffers_tree, get_params_tree

        if spec_draft == "auto":
            spec_draft = make_draft_model(self._model)
        dgpt = spec_draft.gpt if hasattr(spec_draft, "gpt") \
            else spec_draft
        if dgpt.cfg.vocab_size != self._decoder_spec.vocab_size:
            raise ValueError(
                f"draft vocab {dgpt.cfg.vocab_size} != target vocab "
                f"{self._decoder_spec.vocab_size}: rejection sampling "
                f"compares distributions over the SAME vocabulary")
        if max_len > dgpt.cfg.max_position_embeddings:
            raise ValueError(
                f"max_len {max_len} exceeds the draft's "
                f"max_position_embeddings="
                f"{dgpt.cfg.max_position_embeddings}")
        spec_draft.eval()
        self._draft_model = spec_draft
        self._draft_gpt = dgpt
        self._draft_params = get_params_tree(spec_draft)
        self._draft_buffers = get_buffers_tree(spec_draft)
        dh = dgpt.cfg.hidden_size // dgpt.cfg.num_attention_heads
        pdt = self._draft_params[next(iter(self._draft_params))].dtype
        self._draft_max_len = int(max_len)
        self._draft_shape = (dgpt.cfg.num_hidden_layers, 2,
                             self._pool.num_slots,
                             dgpt.cfg.num_attention_heads,
                             self._draft_max_len, dh)
        self._draft_dtype = pdt
        self._draft_pool = jnp.zeros(self._draft_shape, pdt)
        self._draft_synced = np.zeros(self._pool.num_slots, bool)
        self._draft_prefill_jits = {}
        self._draft_scan_jits = {}        # kmax -> scanned propose chain

    def _reset_draft(self) -> None:
        """Failure-path twin of ``pool.reset_data()``: the draft pool
        is donated through its steps, so a failed cycle may have left
        it deleted — reallocate and drop every sync flag."""
        import jax.numpy as jnp
        self._draft_pool = jnp.zeros(self._draft_shape, self._draft_dtype)
        self._draft_synced[:] = False

    def _draft_bucket(self, n: int) -> int:
        """pow2 context bucket (from ``min_bucket``) for the draft
        prefill, capped at the
        draft pool's max_len (the cap is reachable because a slot's
        context is always < max_len)."""
        b = self._min_bucket
        while b < n:
            b *= 2
        return min(b, self._draft_max_len)

    def _draft_prefill_fn(self, bucket: int):
        fn = self._draft_prefill_jits.get(bucket)
        if fn is None:
            from ..models.generation import build_draft_prefill_fn
            probe = _probe.site(
                f"serving/spec_prefill[{bucket}]#{self._eid}")
            fn = self._aot_site(
                f"serving/spec_prefill[{bucket}]#{self._eid}",
                build_draft_prefill_fn(self._draft_model, bucket,
                                       self._draft_max_len, probe=probe),
                donate_argnums=(2,))
            self._draft_prefill_jits[bucket] = fn
        return fn

    def _draft_scan_fn(self, kmax: int):
        """ONE program for the whole draft proposal chain: ``lax.scan``
        over the per-token draft step
        (``build_draft_propose_scan_fn``), so a speculative cycle costs
        a single draft dispatch instead of ``kmax`` sequential small
        launches. One trace per distinct ``kmax`` (at most spec_k of
        them; in practice two — the full chain and the budget tail)."""
        fn = self._draft_scan_jits.get(kmax)
        if fn is None:
            from ..models.generation import build_draft_propose_scan_fn
            probe = _probe.site(
                f"serving/spec_draft[k{kmax}]#{self._eid}")
            fn = self._aot_site(
                f"serving/spec_draft[k{kmax}]#{self._eid}",
                build_draft_propose_scan_fn(
                    self._draft_model, self._pool.num_slots,
                    self._draft_max_len, kmax, top_k=self._top_k,
                    top_p=self._top_p, probe=probe),
                donate_argnums=(2,))
            self._draft_scan_jits[kmax] = fn
        return fn

    def _spec_step_fn(self, q_rows: int, table_len: int):
        key = (q_rows, table_len)
        fn = self._spec_jits.get(key)
        if fn is None:
            from ..models.generation import build_spec_verify_fn
            probe = _probe.site(
                f"serving/spec[q{q_rows},t{table_len}]#{self._eid}")
            fn = self._aot_site(
                f"serving/spec[q{q_rows},t{table_len}]#{self._eid}",
                build_spec_verify_fn(self._model, self._pool.num_slots,
                                     q_rows, self._spec_k, table_len,
                                     self._pool.block_size,
                                     top_k=self._top_k,
                                     top_p=self._top_p, probe=probe,
                                     quantized=self._pool.quantized,
                                     qmax=self._pool.qmax or 127.0),
                donate_argnums=(2, 3) if self._pool.quantized else (2,))
            self._spec_jits[key] = fn
        return fn

    def _sync_draft(self, slot: int, req: GenerationRequest) -> None:
        """Bring the draft's KV cache for ``slot`` up to the target's
        context ``[0, pos)``: one right-padded draft prefill of
        ``prompt + generated`` minus the last (not-yet-written) token.
        Runs when a slot starts (or resumes, after preemption/reuse)
        speculative decoding."""
        feed = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        ctx = feed[:-1]
        if ctx.size:
            b = self._draft_bucket(ctx.size)
            ids = np.zeros((1, b), np.int32)
            ids[0, :ctx.size] = ctx
            key_valid = np.zeros((1, b), bool)
            key_valid[0, :ctx.size] = True
            self._draft_pool = self._draft_prefill_fn(b)(
                self._draft_params, self._draft_buffers,
                self._draft_pool, ids, key_valid, np.int32(slot))
        self._draft_synced[slot] = True

    def _run_spec_step(self, slot_requests, plan, spec):
        """Dispatch ONE speculative serving cycle without any host
        sync: (1) newly-decoding slots' draft caches sync via a
        right-padded draft prefill; (2) ``spec_k`` draft launches
        propose candidates autoregressively (each step feeds the
        previous step's device-side proposal — the host never fetches
        a draft token); (3) ONE fused ragged verify launch scores
        every candidate row next to the cycle's prefill-chunk rows,
        runs device-side rejection sampling, and returns ``[accepted |
        corrected | draft echo | sentinel]`` for the scheduler's
        single fetch. Returns that DEVICE array un-fetched."""
        try:
            return self._run_spec_inner(slot_requests, plan, spec)
        except Exception:
            # the draft pool is donated through its steps: a failure
            # may leave it deleted — rebuild so the engine serves on
            # after the scheduler resets the target pool
            self._reset_draft()
            raise

    def _run_spec_inner(self, slot_requests, plan, spec):
        import jax.numpy as jnp

        pool = self._pool
        S = pool.num_slots
        K = self._spec_k
        for slot in spec:
            if not self._draft_synced[slot]:
                self._sync_draft(slot, slot_requests[slot])
        # --- draft proposal loop: K launches, device-chained ---------
        sample_mask = np.zeros(S, bool)
        temps = np.ones(S, np.float32)
        feed0 = np.zeros(S, np.int32)
        pos_d = np.zeros(S, np.int32)
        for slot, req in slot_requests.items():
            sample_mask[slot] = req.do_sample
            temps[slot] = req.temperature
        for slot in spec:
            feed0[slot] = slot_requests[slot].last_token
            pos_d[slot] = pool.slot_pos(slot)
        lo_d = np.zeros(S, np.int32)
        # only as many scanned draft steps as the cycle's LARGEST
        # candidate count needs (every slot's n_spec = min(spec_k,
        # remaining) — a batch tail one token from its budget would
        # otherwise pay spec_k full draft passes for one verified
        # candidate); the verify signature stays [S, K], zero-padded
        # past kmax. The whole chain is ONE lax.scan program: what
        # used to be kmax sequential small launches is a single
        # dispatch per cycle (the flight recorder's
        # spec_draft_dispatches proves it)
        kmax = max(spec.values())
        self._draft_pool, d_dev, q_dev, self._key = \
            self._draft_scan_fn(kmax)(
                self._draft_params, self._draft_buffers,
                self._draft_pool, feed0, pos_d, lo_d, sample_mask,
                temps, self._key)
        self._sched.note_spec_dispatches(1)
        if kmax < K:
            d_dev = jnp.pad(d_dev, ((0, 0), (0, K - kmax)))
            q_dev = jnp.pad(q_dev, ((0, 0), (0, K - kmax), (0, 0)))
        # --- the fused verify launch ---------------------------------
        Q, T, ops, n_spec, sample_mask, temps, _, _ = self._ragged_operands(
            slot_requests, plan, spec=spec)
        step = self._spec_step_fn(Q, T)
        args = ops + (n_spec, d_dev, q_dev, sample_mask, temps,
                      self._key)
        if pool.quantized:
            pool.data, pool.scales, out, self._key = step(
                self._params, self._buffers, pool.data, pool.scales,
                *args)
        else:
            pool.data, out, self._key = step(
                self._params, self._buffers, pool.data, *args)
        self._note_decode_dispatch(step)
        return out

    def _note_decode_dispatch(self, step) -> None:
        """Account the FLOPs of the decode program that actually ran
        this cycle (host arithmetic only): lifetime counters for the
        mean, plus the live cycle record so cycle_throughput() keeps
        achieved-FLOP/s on the same ring window as its wall-time
        denominator (a lifetime mean alone would lag a shifting
        bucket mix)."""
        self._decode_dispatches += 1
        flops = getattr(step, "last_dispatch_flops", None)
        if flops is None:
            rec = getattr(step, "record", None)
            flops = rec.flops if rec is not None else None
        if flops:
            self._decode_flops_dispatched += flops
            self._sched.note_decode_flops(flops)

    def _run_copy(self, dst: int, src: int) -> None:
        """Copy-on-write append support: device-copy block ``src`` over
        block ``dst`` across every layer/kv plane before the step
        writes into ``dst`` — a quantized pool copies the block's
        per-(layer, kv, head) scales in the same program, so the clone
        dequantizes identically. Block ids are traced scalars — ONE
        trace serves every copy — and the pool (and scale array) is
        donated like every other step. Device-to-device only: no host
        sync."""
        if self._copy_jit is None:
            if self._pool.quantized:
                def _copy(pool, scales, dst, src):
                    return (pool.at[:, dst].set(pool[:, src]),
                            scales.at[:, :, dst].set(scales[:, :, src]))

                self._copy_jit = self._aot_site(
                    f"serving/copy#{self._eid}", _copy,
                    donate_argnums=(0, 1))
            else:
                def _copy(pool, dst, src):
                    return pool.at[:, dst].set(pool[:, src])

                self._copy_jit = self._aot_site(
                    f"serving/copy#{self._eid}", _copy,
                    donate_argnums=(0,))
        if self._pool.quantized:
            self._pool.data, self._pool.scales = self._copy_jit(
                self._pool.data, self._pool.scales, np.int32(dst),
                np.int32(src))
        else:
            self._pool.data = self._copy_jit(
                self._pool.data, np.int32(dst), np.int32(src))
