"""Compiled-program registry: what did each jit site cost to compile,
and what does one execution of it cost in FLOPs and bytes.

Reference analog: ``paddle.flops`` / the profiler's per-program tables —
the reference hand-counts per-layer FLOPs (hapi/dynamic_flops.py); here
the compiler already knows, so every jit site the framework OWNS (the
eager-op dispatch wrappers, the hapi donated train step, the serving
prefill/decode steps per bucket) registers its compiled executable here
at compile time:

* **build cost** — a program's build split where the work happens:
  tracing, lowering, and the compile (a backend compile cold, a
  persistent-cache retrieval warm: the cache's hits and misses ride
  along), then the FIRST call of the fresh executable. Each part is a
  ``program/*`` span (``profiler/span.py``, so a ``TraceAnnotation`` in
  anyone's jax trace) and a field of a stamped event in the site's
  :attr:`ProgramRecord.builds`; the three build parts' sum goes into the
  ``compile/ms`` and ``compile/ms/<site>`` histograms with the
  ``compile/count`` counter (framework/monitor.py), so compile churn is
  a queryable distribution, not a feeling;
* **program cost** — jaxpr eqn count, XLA ``cost_analysis()`` FLOPs and
  bytes-accessed, and ``memory_analysis()`` temp/argument/output bytes,
  wherever the backend provides them (CPU provides cost analysis; a
  backend without it records ``None``, never a fake number).

From these, ``Model.fit`` derives achieved FLOP/s and MFU per flush
window (``hapi/flops_per_sec`` / ``hapi/mfu``, surfaced in the ProgBar)
and ``GenerationEngine.stats()`` derives model-FLOPs-per-token and
serving MFU — against :func:`peak_flops`, a per-device-kind peak table
overridable with ``PADDLE_TPU_PEAK_FLOPS`` (CPU has no honest peak, so
without the override only raw FLOP/s are reported).

Two integration shapes:

* :func:`aot_site` — wraps a function the way ``jax.jit`` would, but
  compiles EXPLICITLY (``trace → lower → compile``, each timed apart)
  per signature and calls the held executable directly. This is how
  the few big owned sites (train step, serving steps) register full
  cost analysis with
  exactly ONE XLA compile — jax 0.4.x does NOT share its jit dispatch
  cache with ``lower().compile()``, so querying analysis lazily from a
  normally-jitted function would compile everything twice.
* :func:`note_compile` — a timing-only note for sites where the jit
  cache must stay jax-owned (the eager op dispatch layer times its
  cache-miss first call — trace+compile+first run — and notes it here).

:func:`analyze_callable` is the one-shot helper ``cost_model.
estimate_flops`` and ``hapi.model_summary.flops`` dedupe onto.
"""
from __future__ import annotations

import collections
import logging
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

from ..profiler.span import record as _span
from . import compile_cache
from .monitor import stat_add, stat_observe

__all__ = ["ProgramRecord", "aot_site", "AotSite", "note_compile", "get",
           "snapshot", "reset", "analyze_compiled", "analyze_callable",
           "compiled_text", "peak_flops", "PEAK_FLOPS_TABLE"]

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_records: Dict[str, "ProgramRecord"] = {}
# live AotSites by name, for compiled_text(); weak, so a site dies with
# the Model / engine that owns it
_sites: "weakref.WeakValueDictionary[str, AotSite]" = \
    weakref.WeakValueDictionary()
# same bound discipline as trace_probe: a notebook sweep creating
# thousands of Models must not grow host memory without bound; past the
# cap records still accumulate for callers holding them by reference,
# only snapshot() visibility is bounded
_MAX_RECORDS = 1024
# build events kept a site (newest): an engine's sites build one program
# each; a train step rebuilt at every new batch shape keeps its last few
_MAX_BUILDS = 8
# what a build event says beside where it began (``at``)
_BUILD_KEYS = ("trace_ms", "lower_ms", "compile_ms", "cache_hits",
               "cache_misses", "first_call_ms", "eqns")

# bf16 peak FLOPs/sec per chip by device-kind substring (the bench.py
# table, hoisted here so fit()/stats() MFU and the bench children agree
# on one source). Override with PADDLE_TPU_PEAK_FLOPS (a float) — the
# escape hatch for unlisted chips AND the pinned fake peak the tests and
# bench.py --dry-run use to exercise the MFU math on CPU.
PEAK_FLOPS_TABLE = (
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v4", 275e12), ("v3", 123e12), ("v6", 918e12),
)


class ProgramRecord:
    """Per-site compile + cost bookkeeping (host ints/floats only).

    ``builds`` holds the site's newest build events, oldest first, each
    ``{"at", "trace_ms", "lower_ms", "compile_ms", "cache_hits",
    "cache_misses", "first_call_ms", "eqns"}``: ``at`` is
    ``time.perf_counter()`` where the build began (the flight recorder's
    clock), the three parts add up to what ``compile_ms_total`` gained,
    ``cache_hits`` / ``cache_misses`` are the persistent cache's lookups
    of that compile (``compile_cache.lookups``) and ``first_call_ms`` the
    wall of the first call of the fresh executable (``None`` until it
    returned). A site on the plain-``jit`` fallback has ONE wall for
    everything: its events say ``"fallback": True``, carry that wall as
    ``wall_ms`` and ``None`` for every part."""

    __slots__ = ("site", "compiles", "compile_ms_total", "last_compile_ms",
                 "eqns", "flops", "bytes_accessed", "temp_bytes",
                 "argument_bytes", "output_bytes", "generated_code_bytes",
                 "static_peak_bytes", "builds")

    def __init__(self, site: str):
        self.site = site
        self.compiles = 0
        self.compile_ms_total = 0.0
        self.last_compile_ms: Optional[float] = None
        self.builds: collections.deque = collections.deque(
            maxlen=_MAX_BUILDS)
        self.eqns: Optional[int] = None
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.temp_bytes: Optional[int] = None
        self.argument_bytes: Optional[int] = None
        self.output_bytes: Optional[int] = None
        self.generated_code_bytes: Optional[int] = None
        # ISSUE 18: the donation-aware jaxpr liveness estimate, recorded
        # at trace time NEXT TO the XLA memory figures so the dry-run
        # can cross-check the static planner against the backend
        self.static_peak_bytes: Optional[int] = None

    def as_dict(self) -> dict:
        out = {k: getattr(self, k) for k in self.__slots__}
        out["builds"] = [dict(b) for b in self.builds]
        return out

    def __repr__(self):
        return (f"<ProgramRecord {self.site!r} compiles={self.compiles} "
                f"flops={self.flops} eqns={self.eqns}>")


def _record(site: str) -> ProgramRecord:
    with _lock:
        r = _records.get(site)
        if r is None:
            r = ProgramRecord(site)
            if len(_records) < _MAX_RECORDS:
                _records[site] = r
        return r


def note_compile(site: str, wall_ms: float, eqns: Optional[int] = None,
                 analysis: Optional[dict] = None,
                 build: Optional[dict] = None) -> ProgramRecord:
    """Record one compile of ``site``: wall ms into the ``compile/ms``
    histograms (global + per-site), ``compile/count``, and — when the
    caller has them — the program's eqn count and cost/memory analysis
    onto the site's :class:`ProgramRecord` (latest compile wins: a
    retrace at a new shape supersedes the old figures). ``build`` is the
    stamped event an :class:`AotSite` keeps of it
    (:attr:`ProgramRecord.builds`)."""
    rec = _record(site)
    with _lock:
        rec.compiles += 1
        rec.compile_ms_total += float(wall_ms)
        rec.last_compile_ms = float(wall_ms)
        if build is not None:
            rec.builds.append(build)
        if eqns is not None:
            rec.eqns = int(eqns)
        if analysis:
            for k in ("flops", "bytes_accessed", "temp_bytes",
                      "argument_bytes", "output_bytes",
                      "generated_code_bytes", "static_peak_bytes"):
                if analysis.get(k) is not None:
                    setattr(rec, k, analysis[k])
        registered = _records.get(site) is rec
    stat_add("compile/count")
    stat_observe("compile/ms", float(wall_ms))
    if registered:
        # per-site histograms only for REGISTERED sites: names are
        # per-instance (one per Model / engine), and monitor histograms
        # have no name cap of their own — past _MAX_RECORDS the
        # per-site series would be exactly the unbounded host-memory
        # growth the record cap exists to prevent
        stat_observe(f"compile/ms/{site}", float(wall_ms))
    return rec


def get(site: str) -> Optional[ProgramRecord]:
    with _lock:
        return _records.get(site)


def snapshot() -> Dict[str, dict]:
    with _lock:
        return {name: r.as_dict() for name, r in _records.items()}


def compiled_text(site: str) -> Optional[str]:
    """The optimized HLO text of every executable the live
    :class:`AotSite` named ``site`` holds, concatenated — ``None`` when
    no such site is alive or it compiled nothing explicitly. This is how
    a caller confirms WHICH kernels a step really contains (a Pallas
    kernel is a ``tpu_custom_call`` there), rather than which were
    enabled. Rendered on demand; nothing is kept per compile."""
    with _lock:
        aot = _sites.get(site)
    if aot is None or not aot._compiled:
        return None
    return "\n".join(c.as_text() for c in list(aot._compiled.values()))


def reset() -> None:
    with _lock:
        _records.clear()


# ---------------------------------------------------------------------------
# cost/memory analysis of a compiled executable
# ---------------------------------------------------------------------------

def analyze_compiled(compiled) -> dict:
    """Tolerant cost+memory query of an XLA ``Compiled`` (or anything
    shaped like one). Every field is ``None`` where the backend provides
    no answer — never ``-1`` or another fake number a dashboard would
    chart as real."""
    out: Dict[str, Any] = {"flops": None, "bytes_accessed": None,
                           "temp_bytes": None, "argument_bytes": None,
                           "output_bytes": None,
                           "generated_code_bytes": None}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if ca:
            f = ca.get("flops")
            # XLA reports -1 for "unknown" on some backends — that is
            # the silent-(-1.0) bug class this registry exists to kill
            if f is not None and f >= 0:
                out["flops"] = float(f)
            b = ca.get("bytes accessed")
            if b is not None and b >= 0:
                out["bytes_accessed"] = float(b)
    except Exception as e:                               # noqa: BLE001
        logger.debug("cost_analysis unavailable: %r", e)
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for field, key in (("temp_size_in_bytes", "temp_bytes"),
                               ("argument_size_in_bytes", "argument_bytes"),
                               ("output_size_in_bytes", "output_bytes"),
                               ("generated_code_size_in_bytes",
                                "generated_code_bytes")):
                v = getattr(ma, field, None)
                if v is not None:
                    out[key] = int(v)
    except Exception as e:                               # noqa: BLE001
        logger.debug("memory_analysis unavailable: %r", e)
    return out


def static_peak_of_trace(closed_jaxpr, donated_mask=None) -> Optional[int]:
    """Donation-aware liveness peak of an already-traced program
    (analysis/liveness.py), or ``None`` when the scan cannot run —
    same honesty contract as :func:`analyze_compiled`: never a fake
    number. Host arithmetic over avals; no compile, no device."""
    try:
        from ..analysis.liveness import jaxpr_liveness
        return int(jaxpr_liveness(closed_jaxpr,
                                  donated_mask).static_peak_bytes)
    except Exception as e:                               # noqa: BLE001
        logger.debug("static liveness unavailable: %r", e)
        return None


def analyze_callable(fn, *example_args, static_argnums=(),
                     site: Optional[str] = None) -> Optional[dict]:
    """Trace+compile ``fn`` on ``example_args`` and return its program
    cost: ``{"flops", "bytes_accessed", "eqns", "temp_bytes", ...}``
    (fields ``None`` where the backend has no analysis). Returns ``None``
    when even tracing/compiling fails. The ONE helper behind
    ``cost_model.estimate_flops`` and ``hapi.model_summary.flops`` — the
    hand-rolled ``lower().compile().cost_analysis()`` snippets they used
    to duplicate live here now. Registers under ``site`` when given."""
    import jax
    try:
        jitted = fn if hasattr(fn, "lower") else \
            jax.jit(fn, static_argnums=static_argnums)
        t0 = time.perf_counter()
        traced = jitted.trace(*example_args)
        eqns = len(traced.jaxpr.jaxpr.eqns)
        static_peak = static_peak_of_trace(traced.jaxpr)
        compiled = traced.lower().compile()
        wall_ms = (time.perf_counter() - t0) * 1e3
    except Exception as e:                               # noqa: BLE001
        logger.debug("analyze_callable: trace/compile failed: %r", e)
        return None
    analysis = analyze_compiled(compiled)
    analysis["eqns"] = eqns
    analysis["static_peak_bytes"] = static_peak
    if site is not None:
        note_compile(site, wall_ms, eqns=eqns, analysis=analysis)
    return analysis


# ---------------------------------------------------------------------------
# peak FLOPs / MFU
# ---------------------------------------------------------------------------

def peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak FLOP/s of one chip of the current (or named) device kind.
    ``PADDLE_TPU_PEAK_FLOPS`` (a float) overrides everything — the knob
    for unlisted chips and for pinning a fake peak in tests. ``None``
    when nothing applies (CPU: report FLOP/s, never a made-up MFU)."""
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS", "").strip()
    if env:
        try:
            v = float(env)
            if v > 0:
                return v
        except ValueError:
            logger.debug("bad PADDLE_TPU_PEAK_FLOPS=%r ignored", env)
    if device_kind is None:
        try:
            import jax
            device_kind = jax.devices()[0].device_kind
        except Exception:                                # noqa: BLE001
            return None
    dk = str(device_kind).lower()
    for sub, peak in PEAK_FLOPS_TABLE:
        if sub in dk:
            return peak
    return None


# ---------------------------------------------------------------------------
# AOT sites: explicit compile-and-call for the big owned programs
# ---------------------------------------------------------------------------

def _static_value_key(v):
    """Value key for a static argument: (type, value) for hashables —
    1 == 1.0 == True must not alias, same rule as the dispatch layer's
    _const_key — repr for the rest."""
    try:
        hash(v)
    except TypeError:
        return ("repr", repr(v))
    return (type(v).__name__, v)


# process-wide trace serialization (see AotSite._compile): tracing a
# step body that reads live Layer state (functional_state) is not
# thread-safe across sites sharing one network; RLock because a traced
# body may legitimately re-enter another AotSite under a tracer
_TRACE_LOCK = threading.RLock()


class AotSite:
    """A jit site that owns its executables: per input signature it
    traces, lowers and compiles EXPLICITLY (timing each part, and the
    first call after them, into a stamped build event and registering
    the program's cost analysis), then dispatches straight to the held
    executable — drop-in for ``jax.jit(fn, static_argnums,
    donate_argnums)`` at sites whose signatures are flat and stable (the
    donated train step, the serving prefill/decode steps).

    Transparent under tracing: called with tracers (``analysis.analyze``,
    a ``make_jaxpr`` of an outer program), it delegates to the inner
    jitted function, so the pjit eqn — donation contract included —
    appears in the outer trace exactly as before.

    Any failure of the explicit path (a backend without AOT support, an
    un-flattenable argument) falls back PERMANENTLY to the plain jitted
    call for this site, still noting first-call wall time — robustness
    first, cost analysis when available.

    ``on_build(event)``, where given, is called once a build, on the
    thread that called, after the first call of the fresh executable
    returned: the owner's place to stamp what it knows of the caller
    onto the event (the serving engine: the launch's rows and slots).
    """

    _MAX_SIGNATURES = 64     # executables kept per site (oldest evicted)

    def __init__(self, name: str, fn, static_argnums=(), donate_argnums=(),
                 on_build: Optional[Callable[[dict], None]] = None):
        import jax
        self.site = name
        self.on_build = on_build
        # the program/* spans' argument: a TraceMe encodes its arguments
        # as ``name#k=v,...#``, so a ``#`` in a value would end them
        self._span_args = {"site": name.replace("#", "@")}
        self.static_argnums = tuple(int(i) for i in static_argnums)
        self.donate_argnums = tuple(int(i) for i in donate_argnums)
        self.jitted = jax.jit(fn, static_argnums=self.static_argnums or
                              None, donate_argnums=donate_argnums)
        self.record = _record(name)
        self._compiled: Dict[Tuple, Any] = {}
        self._flops_by_key: Dict[Tuple, Optional[float]] = {}
        # FLOPs of the program the LAST __call__ dispatched — the
        # record's .flops is latest-compile-wins, so a caller averaging
        # cost over many dispatches (fit's MFU, serving stats) must read
        # this per-dispatch value or a partial last batch would be
        # billed at the wrong program's cost
        self.last_dispatch_flops: Optional[float] = None
        self._fallback = False
        self._seen_fallback_keys: set = set()
        with _lock:
            _sites[name] = self

    # -- key building ------------------------------------------------------
    def _key(self, args):
        """(signature, tracer?) of the call: per-leaf (shape, dtype) for
        dynamic arrays, the VALUE for static-position args — statics
        select the compiled program exactly as jit's static_argnums do,
        so an array-typed static (np.int32(3) vs np.int32(4): same
        shape/dtype, different program!) must never fall into the
        shape-keyed path."""
        import jax
        statics = tuple(
            (i, _static_value_key(args[i])) for i in self.static_argnums
            if i < len(args))
        leaves, treedef = jax.tree_util.tree_flatten(self._dynamic(args))
        parts = []
        tracer = False
        for leaf in leaves:
            if isinstance(leaf, jax.core.Tracer):
                tracer = True
                break
            shape = getattr(leaf, "shape", None)
            if shape is not None:
                parts.append((tuple(shape), str(leaf.dtype)))
            else:
                parts.append(("py", _static_value_key(leaf)))
        return (statics, treedef, tuple(parts)), tracer

    def _dynamic(self, args):
        if not self.static_argnums:
            return args
        drop = set(self.static_argnums)
        return tuple(a for i, a in enumerate(args) if i not in drop)

    # -- dispatch ----------------------------------------------------------
    def __call__(self, *args):
        # per-call cost: one tree_flatten + a (shape, dtype) tuple per
        # leaf — tens of µs for a full train-state tree against the
        # multi-ms step it dispatches. A cheaper identity probe (leaf
        # count + first-leaf aval) could serve the wrong program when a
        # LATER leaf changes shape, so the full key stays. A call that
        # finds its executable runs nothing of the build's bookkeeping:
        # the parts of a build and its first call are timed in the
        # ``compiled is None`` branch alone.
        try:
            key, tracer = self._key(args)
        except Exception:                                # noqa: BLE001
            self._fallback = True
            key, tracer = None, False
        if tracer:
            # under an outer trace the executable cannot run: inline the
            # jitted call so the pjit eqn lands in the outer jaxpr
            return self.jitted(*args)
        if self._fallback or key is None:
            return self._call_fallback(key, args)
        compiled = self._compiled.get(key)
        if compiled is None:
            compiled, build = self._compile(key, args)
            if compiled is None:             # explicit path unavailable
                return self._call_fallback(key, args)
            return self._first_call(compiled, build, key, args)
        self.last_dispatch_flops = self._flops_by_key.get(key)
        return compiled(*self._dynamic(args))

    def _first_call(self, compiled, build: dict, key, args):
        """The first call of a fresh executable, timed into its build
        event: the call's own wall — whatever the runtime does before it
        returns (loading the executable onto the device) — with no sync
        added, so the device work it enqueues is not in it."""
        self.last_dispatch_flops = self._flops_by_key.get(key)
        t0 = time.perf_counter()
        with _span("program/first_call", "startup", self._span_args):
            out = compiled(*self._dynamic(args))
        build["first_call_ms"] = (time.perf_counter() - t0) * 1e3
        if self.on_build is not None:
            self.on_build(build)
        return out

    def _donated_mask(self, args):
        """Donation mask over the traced program's flat invars: the
        jitted fn's dynamic args in order, each arg's leaves marked by
        whether its ORIGINAL argnum (static args counted, per jax.jit
        semantics) is donated."""
        if not self.donate_argnums:
            return None
        import jax
        try:
            mask = []
            for i, a in enumerate(args):
                if i in self.static_argnums:
                    continue
                n = len(jax.tree_util.tree_leaves(a))
                mask.extend([i in self.donate_argnums] * n)
            return mask
        except Exception:                                # noqa: BLE001
            return None

    def _compile(self, key, args):
        """(executable, its build event) — ``(None, None)`` where the
        explicit path is unavailable."""
        site = self._span_args
        try:
            # ONE trace at a time, process-wide: the serving/hapi step
            # bodies trace through functional_state(net, ...), which
            # temporarily rebinds the network's layer state — two
            # engine scheduler threads tracing over a SHARED model
            # concurrently corrupt each other's captures ("compiled for
            # 79 inputs but called with 43", then a backend abort).
            # Compiles are rare and the executable DISPATCH stays
            # outside the lock, so fleets serialize only their cold
            # start. The persistent cache's lookups are counted process-
            # wide: under the lock, their difference is this program's.
            with _TRACE_LOCK:
                at = time.perf_counter()
                with _span("program/trace", "startup", site):
                    traced = self.jitted.trace(*args)
                    eqns = len(traced.jaxpr.jaxpr.eqns)
                t_traced = time.perf_counter()
                with _span("program/lower", "startup", site):
                    lowered = traced.lower()
                t_lowered = time.perf_counter()
                hits, misses = compile_cache.lookups()
                with _span("program/compile", "startup", site):
                    compiled = lowered.compile()
                t_compiled = time.perf_counter()
                hits_after, misses_after = compile_cache.lookups()
        except Exception as e:                           # noqa: BLE001
            logger.debug("AotSite %s: explicit compile failed (%r); "
                         "falling back to plain jit", self.site, e)
            self._fallback = True
            return None, None
        build = {"at": at, "trace_ms": (t_traced - at) * 1e3,
                 "lower_ms": (t_lowered - t_traced) * 1e3,
                 "compile_ms": (t_compiled - t_lowered) * 1e3,
                 "cache_hits": hits_after - hits,
                 "cache_misses": misses_after - misses,
                 "first_call_ms": None, "eqns": eqns}
        analysis = analyze_compiled(compiled)
        analysis["static_peak_bytes"] = static_peak_of_trace(
            traced.jaxpr, self._donated_mask(args))
        note_compile(self.site, (t_compiled - at) * 1e3, eqns=eqns,
                     analysis=analysis, build=build)
        if len(self._compiled) >= self._MAX_SIGNATURES:
            oldest = next(iter(self._compiled))
            self._compiled.pop(oldest)
            self._flops_by_key.pop(oldest, None)
        self._compiled[key] = compiled
        self._flops_by_key[key] = analysis.get("flops")
        return compiled, build

    def _call_fallback(self, key, args):
        """Plain jitted call; first call per signature still timed and
        noted (trace+compile+first-run wall — the dispatch-layer
        approximation) so ``compile/ms``/``compile/count`` stay live.
        Its build event holds that ONE wall as ``wall_ms`` and no parts,
        marked ``fallback``: no reader adds a lump to a part."""
        first = key is not None and key not in self._seen_fallback_keys
        t0 = time.perf_counter()
        if first:
            # same shared-model trace race as _compile: the first call
            # per signature is the one that traces
            with _TRACE_LOCK:
                out = self.jitted(*args)
        else:
            out = self.jitted(*args)
        if first:
            self._seen_fallback_keys.add(key)
            wall_ms = (time.perf_counter() - t0) * 1e3
            build = {"at": t0, **dict.fromkeys(_BUILD_KEYS),
                     "fallback": True, "wall_ms": wall_ms}
            note_compile(self.site, wall_ms, build=build)
            if self.on_build is not None:
                self.on_build(build)
        # best effort on the fallback path: latest-compile figures
        self.last_dispatch_flops = self.record.flops
        return out

    def __repr__(self):
        return (f"<AotSite {self.site!r} signatures={len(self._compiled)} "
                f"fallback={self._fallback}>")


def aot_site(name: str, fn, static_argnums=(), donate_argnums=(),
             on_build: Optional[Callable[[dict], None]] = None) -> AotSite:
    """Build an :class:`AotSite` — the registry-instrumented replacement
    for ``jax.jit(fn, static_argnums=..., donate_argnums=...)`` at owned
    program sites."""
    return AotSite(name, fn, static_argnums=static_argnums,
                   donate_argnums=donate_argnums, on_build=on_build)
