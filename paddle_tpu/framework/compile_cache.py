"""Persistent XLA compilation cache (framework-level).

The first jit of a heavy graph costs seconds to minutes. XLA can
serialize compiled executables to disk and reload them keyed on (HLO,
compile options, jaxlib version, cache path), so every process after the
first skips the compile entirely.

Where the entries live — one rule, no other:

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory. jax reads the
  variable itself; nothing here points the cache anywhere else.
* not set: ``<checkout>/.cache/xla`` (git-ignored) — a FIXED path,
  because the path is part of the cache key: a directory derived from
  ``~``, a temp dir, a pid or the time never hits again on a machine
  that starts each run with a new home.

The autotune cache (ops/autotune_cache.py) sits under the same
``<checkout>/.cache`` root, so one directory carries all persistent
tuning state and a cold and a warm machine can be told apart by looking
at it.

The cache is ON by default: ``enable()`` runs at package import unless
``FLAGS_compile_cache=0`` (framework/__init__.py), and again in every
``bench.py`` child. The one exception is a process pinned to the CPU
(``JAX_PLATFORMS=cpu`` — the tests, the dry-run canary): XLA:CPU logs
two screens of machine-feature warnings on every reload of a cached
executable, and a CPU run is a rehearsal nobody repeats on the same
checkout, so the import hook leaves it off there and only an explicit
``enable()`` arms it. Not because its programs are cheap: a step program
of interpreted kernels is seconds to compile on the CPU, which is why
``tests/conftest.py`` arms one cache for a whole test run (its workers
and children build the same toys). An unwritable directory leaves it off
with the reason in ``status()``.

Reference analog: the reference caches serialized CUDA autotune/program
state per machine; jax's compilation cache is the XLA-era equivalent.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

from .flags import flag_value

__all__ = ["cache_root", "default_dir", "enable", "disable", "status",
           "entries", "maybe_enable", "lookups"]

_lock = threading.Lock()
_state = {"enabled": False, "dir": None, "reason": None}
# jax's own count of this process's persistent-cache lookups, since the
# first lookups() call: [hits, misses] (see lookups())
_lookups = [0, 0]
_listening = False

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    """The persistent cache root shared by every paddle_tpu cache family
    (autotune entries, XLA executables): ``<checkout>/.cache``."""
    return os.path.join(_CHECKOUT, ".cache")


def default_dir() -> str:
    """Where XLA executables land: ``JAX_COMPILATION_CACHE_DIR`` if the
    environment sets it, else ``<cache_root()>/xla``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(cache_root(), "xla")


def enable(min_compile_time_secs: Optional[float] = None) -> bool:
    """Turn the persistent cache on for this process, at
    :func:`default_dir`. Returns True when the directory is usable;
    False (with ``status()["reason"]`` set) when it cannot be created.
    Safe to call repeatedly.

    ``min_compile_time_secs``: only compiles at least this long are
    persisted. None keeps jax's own floor (~1 s) — the right production
    default: micro-compiles cost more to serialize than to redo and
    would grow the dir without bound. Pass 0 to persist everything
    (tests, the dry-run canary, tiny-model runs)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    d = default_dir()
    try:
        os.makedirs(d, exist_ok=True)
    except OSError as e:
        with _lock:
            _state.update(enabled=False, dir=None,
                          reason=f"cache dir unwritable: {e}")
        return False
    # with JAX_COMPILATION_CACHE_DIR set at start-up jax already holds
    # this value and nothing is written; otherwise this applies the
    # in-checkout path (or a variable set after jax was imported)
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    # the master switch: a prior disable() must be reversible
    jax.config.update("jax_enable_compilation_cache", True)
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
    # jax's cache initializes AT MOST ONCE per process: if any jit ran
    # before enable(), the module latched its settings of that moment.
    # Reset it so the next compile re-initializes against these.
    compilation_cache.reset_cache()
    with _lock:
        _state.update(enabled=True, dir=d, reason=None)
    return True


def disable() -> None:
    """Stop persisting (already-written entries stay on disk)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with _lock:
        _state.update(enabled=False, reason="disabled")


def status() -> dict:
    with _lock:
        return dict(_state)


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _lookups[0] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _lookups[1] += 1


def lookups() -> tuple:
    """``(hits, misses)`` of the persistent cache in this process, from
    jax's own monitoring events (``/jax/compilation_cache/cache_hits``:
    an executable was retrieved; ``cache_misses``: one was compiled and
    written) — ONE process-wide listener, registered at the first call.
    Read it before and after a compile: builds are serialised
    (``program_registry._TRACE_LOCK``), so the difference is that
    program's. A step with Pallas kernels makes several lookups; a
    compile under jax's persistence floor, or with the cache off, makes
    none."""
    global _listening
    with _lock:
        if not _listening:
            import jax
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
        return _lookups[0], _lookups[1]


def entries(cache_dir: Optional[str] = None) -> int:
    """Number of serialized-executable entries on disk (the ``-cache``
    files jax writes)."""
    d = cache_dir or _state["dir"] or default_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    return sum(n.endswith("-cache") for n in names)


def maybe_enable() -> bool:
    """Import-time hook: arm the cache unless ``FLAGS_compile_cache`` is
    switched off (env-seeded like every flag) or the process is pinned
    to the CPU (see the module docstring)."""
    if not flag_value("FLAGS_compile_cache"):
        return False
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        with _lock:
            _state.update(
                enabled=False, dir=None,
                reason="JAX_PLATFORMS=cpu: not armed at import "
                       "(compile_cache.enable() arms it)")
        return False
    return enable()
