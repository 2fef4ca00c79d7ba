"""Engine and steps: ``GenerationEngine.__init__``, s: the span
``startup/engine_build`` as ``engine_stats["startup"]["build_ms"]``; its
children (``phases_ms``: the parameter snapshot, the Pallas smoke test,
the pool, the plan gate's trace of the largest step, the scheduler) go
to the log. Nothing where the program times no build (every commit
before PR 52)."""
from benchmark.lib import harness as H
from benchmark.layer_metrics import setup_programs_built as B


def read(r):
    st = B.startup(r)
    if st is None:
        return None
    H.log("engine build: " + ", ".join(
        f"{name} {ms / 1e3:.2f}" for name, ms in st["phases_ms"].items())
        + f" s of {st['build_ms'] / 1e3:.2f}")
    return st["build_ms"] / 1e3
