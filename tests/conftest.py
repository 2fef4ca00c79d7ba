"""Test configuration.

Per SURVEY.md §4's TPU-native translation: tests run on the CPU PjRt backend
(the "fake device", analog of the reference's fake_cpu_device.h) with 8
virtual devices so multi-chip sharding paths execute without TPU hardware.
"""
import os

# Both are read at the first backend initialisation, which is still
# ahead of us; the jax.config.update below holds the platform choice even
# if something imported jax earlier.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, (
    f"test env must see 8 virtual CPU devices, got {jax.devices()}")


# One persistent compile cache for the run. On the CPU a step program of a
# toy engine is seconds to compile (interpreted kernels), the suite's files
# and the children they start build the same toys at the same shapes, and
# each of the six workers and every child would compile them for itself:
# with the cache the 26 engine and child-process files took 3,002 s in sum
# and 592 s of wall, six at a time and cold, against 3,391 s and 657 s
# without (PR 45, CHANGES.md). The process that finds no
# JAX_COMPILATION_CACHE_DIR makes a directory for the session and removes
# it at the end; the workers and children it starts inherit the variable
# (jax reads it at start-up) and so does whoever set one from outside.
# jax's own floor stays (a compile under a second is not stored: at 0 the
# files that fill the cache paid ~50 s each for writing 4,007 entries).
_own_cache_dir = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import tempfile
    _own_cache_dir = tempfile.mkdtemp(prefix="t1-xla-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _own_cache_dir

from paddle_tpu.framework import compile_cache  # noqa: E402

compile_cache.enable()

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import signal  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

from _toys import engines, served_model  # noqa: E402,F401  (fixtures)


@pytest.fixture
def cache_env():
    """Let a test move JAX_COMPILATION_CACHE_DIR and arm the cache, then
    put the process back as it was (the import hook leaves a CPU-pinned
    test process unarmed; jax's 1 s persistence floor)."""
    import jax
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    was_on = compile_cache.status()["enabled"]
    yield
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    if was_on:
        compile_cache.enable()
    else:
        compile_cache.disable()

# Every test has this much wall clock for its setup and its call together,
# and no way to ask for more: tier-1 runs under one limit for the whole
# suite, and a test of minutes is paid again by every later PR. A test that
# cannot fit is marked ``slow`` (the driver deselects the mark; whoever runs
# it by hand runs it without a limit), with what still covers it written
# beside the mark.
TEST_LIMIT_S = 120

_stacks_to = None          # the run's own stderr (a descriptor), whatever
                           # a test's capture does to descriptor 2


def pytest_configure(config):
    global _stacks_to
    _stacks_to = os.dup(2)                    # capture is suspended here


def pytest_unconfigure(config):
    if _own_cache_dir is not None:
        import shutil
        shutil.rmtree(_own_cache_dir, ignore_errors=True)


@contextlib.contextmanager
def time_limit(name, seconds, limit=None):
    """Fail ``name`` once ``seconds`` of wall clock have passed inside the
    block (what is left of ``limit``, where an earlier block has spent
    some of it). The alarm raises in the main thread between two
    bytecodes; a wait that never comes back to Python (a lock, a compile)
    is not interrupted, so ``faulthandler`` writes every thread's stack to
    the log at the same moment: it then says where the test waits."""
    def expired(signum, frame):
        pytest.fail(f"{name} ran past its limit of {limit or seconds:g} s")

    faulthandler.dump_traceback_later(seconds, file=_stacks_to)
    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)
        faulthandler.cancel_dump_traceback_later()


_deadline = pytest.StashKey[float]()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    if item.get_closest_marker("slow"):
        return (yield)
    item.stash[_deadline] = time.monotonic() + TEST_LIMIT_S
    with time_limit(item.nodeid, TEST_LIMIT_S):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    if item.get_closest_marker("slow"):
        return (yield)
    left = max(item.stash[_deadline] - time.monotonic(), 1e-3)
    with time_limit(item.nodeid, left, TEST_LIMIT_S):
        return (yield)


# ---------------------------------------------------------------------------
# smoke subset: `pytest -m smoke` selects one fast module per layer of the
# API stack. Its user is MIGRATION.md ("Verifying a migration"): someone
# porting a model checks the surface in under a minute; tier-1 runs
# everything not marked ``slow``.
# ---------------------------------------------------------------------------
_SMOKE_MODULES = {
    "test_small_parity",      # op-level numeric parity vs torch
    "test_infermeta",         # shape/dtype inference + dispatch checks
    "test_top_namespaces",    # API surface parity
    "test_optimizer_amp",     # optimizers, lr schedulers, AMP O1/O2
    "test_ops_manipulation",  # reshape/concat/split family
    "test_regressions",       # past-bug pins
    "test_functional_smoke",  # call-path sweep of every F.* wrapper
    "test_io_samplers",       # samplers/datasets/collate
    "test_matrix_nms",        # detection post-processing
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.nodeid.split("::", 1)[0].rsplit("/", 1)[-1]
        if mod.removesuffix(".py") in _SMOKE_MODULES:
            item.add_marker(pytest.mark.smoke)
