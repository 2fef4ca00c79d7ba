"""Shape-class-keyed kernel-selection cache (r3 verdict item 9).

Reference analog: phi's autotune cache — algorithm choice memoised per
kernel+shape signature (paddle/phi/kernels/autotune/cache.h, switch_autotune.h:
N warmup steps measure candidates, the winner is cached and replayed).

TPU mapping: kernel choice here means WHICH lowering serves an op — the
Pallas kernel, the lax/XLA composite, or a streaming variant. The choice
must be static per jit trace, so selection happens at dispatch time
(ops/registry.py override predicates) via this cache:

- keys are SHAPE CLASSES — dims bucketed to powers of two — so one
  measurement covers a family of shapes, like the reference's cache
  keyed on (dims, dtype) tuples;
- entries persist per device kind under ``<checkout>/.cache/`` (the
  compile cache's root) so a crossover measured once keeps serving
  later processes on the same chip generation;
- ``measure()`` times candidate thunks on concrete arrays (eager mode /
  warmup), stores the winner; ``choose()`` is the hot-path lookup with a
  heuristic default and hit/miss counters.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, Optional

from ..framework.monitor import stat_add, stat_observe
from ..profiler import span as _prof

__all__ = ["shape_class", "choose", "measure", "record", "stats",
           "clear", "cache_path", "set_device_kind"]

_lock = threading.Lock()
_entries: Dict[str, str] = {}
_loaded_for: Optional[str] = None
_device_kind: Optional[str] = None
_stats = {"hits": 0, "misses": 0, "measures": 0}


def _bucket(n: int) -> int:
    """Round up to a power of two — one cache entry per shape family."""
    if n <= 0:
        return 0
    return 1 << (int(n) - 1).bit_length()


def shape_class(*dims, **tags) -> str:
    """Canonical key fragment: pow2-bucketed dims + literal tags
    (dtype, causal flags, ...)."""
    parts = [str(_bucket(d)) if isinstance(d, int) else str(d)
             for d in dims]
    parts += [f"{k}={tags[k]}" for k in sorted(tags)]
    return "x".join(parts)


def set_device_kind(kind: Optional[str]) -> None:
    """Override the device-kind namespace (tests; pre-backend setup).
    ``None`` resets to autodetection from the jax backend."""
    global _device_kind, _loaded_for
    with _lock:
        _device_kind = kind
        _loaded_for = None


def _kind() -> str:
    global _device_kind
    if _device_kind is None:
        try:
            import jax
            _device_kind = jax.devices()[0].device_kind.replace(" ", "_")
        except Exception:
            _device_kind = "unknown"
    return _device_kind


def cache_path() -> str:
    # same in-checkout root as the persistent XLA compilation cache
    # (framework/compile_cache.py): one directory carries all persistent
    # tuning state. PADDLE_AUTOTUNE_CACHE_DIR moves the autotune entries.
    from ..framework.compile_cache import cache_root
    root = os.environ.get("PADDLE_AUTOTUNE_CACHE_DIR", cache_root())
    return os.path.join(root, f"autotune_{_kind()}.json")


def _ensure_loaded() -> None:
    global _loaded_for
    kind = _kind()
    if _loaded_for == kind:
        return
    _entries.clear()
    try:
        with open(cache_path()) as f:
            _entries.update({str(k): str(v)
                             for k, v in json.load(f).items()})
    except (OSError, ValueError):
        pass
    _loaded_for = kind


def _persist() -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(_entries, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only home: cache stays in-process


def choose(op: str, key: str, default: str) -> str:
    """Hot-path lookup: the recorded winner for (op, shape class), or
    ``default`` (the heuristic crossover) when nothing is recorded."""
    with _lock:
        _ensure_loaded()
        got = _entries.get(f"{op}/{key}")
        if got is None:
            _stats["misses"] += 1
            stat_add("autotune_cache_miss")
            return default
        _stats["hits"] += 1
        stat_add("autotune_cache_hit")
        return got


def record(op: str, key: str, winner: str, persist: bool = True) -> None:
    with _lock:
        _ensure_loaded()
        _entries[f"{op}/{key}"] = winner
        if persist:
            _persist()


def measure(op: str, key: str, candidates: Dict[str, Callable],
            n_warmup: int = 1, n_iters: int = 3,
            persist: bool = True) -> str:
    """Time candidate thunks (must return device arrays; blocked on), store
    and return the winner. Call with CONCRETE inputs only — the reference's
    warmup-steps measurement, done explicitly rather than inside traces."""
    import jax
    t_measure = time.perf_counter()
    timings = {}
    with _prof.record(f"autotune_measure/{op}", "cache",
                      args={"key": key}):
        for name, thunk in candidates.items():
            try:
                for _ in range(n_warmup):
                    jax.block_until_ready(thunk())
                t0 = time.perf_counter()
                for _ in range(n_iters):
                    out = thunk()
                jax.block_until_ready(out)
                timings[name] = (time.perf_counter() - t0) / n_iters
            except Exception:
                continue  # a candidate that cannot run never wins
    # the measurement IS the compile+warmup cost the cache amortizes —
    # surface it so "how long did autotune take" has an answer
    stat_observe(f"autotune_measure_ms/{op}",
                 (time.perf_counter() - t_measure) * 1e3)
    if not timings:
        raise RuntimeError(f"no runnable candidate for {op}/{key}")
    winner = min(timings, key=timings.get)
    record(op, key, winner, persist=persist)
    with _lock:
        _stats["measures"] += 1
    return winner


def stats() -> dict:
    with _lock:
        out = dict(_stats)
        out["entries"] = len(_entries)
        return out


def clear(persist: bool = False) -> None:
    with _lock:
        _entries.clear()
        for k in _stats:
            _stats[k] = 0
        if persist:
            try:
                os.remove(cache_path())
            except OSError:
                pass
