"""Process-level pieces every driver shares: the clock the set-up time
is taken from, logging, the device's self-description, the profiler
slice."""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time

# run.py imports this module before anything heavy: close enough to the
# process's start for a set-up time measured in seconds
PROCESS_START = time.monotonic()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT_DIR = os.path.join(ROOT, "benchmark", "out")


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - PROCESS_START:7.1f}s] {msg}", flush=True)


def sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.2))


def out_path(name: str) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    return os.path.join(OUT_DIR, name)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_cell(workload: str) -> tuple:
    """(cell entry, configuration, traffic mix) of a workload named in
    ``BENCHMARK.json``; ``KeyError`` if it is not there."""
    bench = load_json("BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    config = load_json({c["name"]: c for c in bench["configs"]}
                       [cell["config"]]["file"])
    return cell, config, load_json(f"benchmark/traffic/{cell['traffic']}.json")


def require_tpu(chips: int):
    """The devices, or exit code 2 with no result line: a measurement
    path that finds no chip fails, it never falls back."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: jax reports platform {devs[0].platform!r}, not a "
              f"TPU: nothing is run and no result is printed", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), jax sees "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def device_report(devs) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    log(f"memory_stats of device 0: {stats[0]}")
    # on a TPU the scratch XLA reserves for loaded programs
    # (``peak_bytes_reserved``: a train step's activations live there) is
    # HBM held beside the arrays the allocator counts
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


@contextlib.contextmanager
def profiler_slice(name: str):
    """Trace what runs inside the block. Yields a dict that afterwards
    holds the trace directory, the slice's length on the host clock and
    ``perf_s``, the ``perf_counter`` reading at the trace's time zero
    (the profiler stamps events in nanoseconds from its own start)."""
    import jax
    trace_dir = out_path(f"trace-{name}")
    info = {"dir": trace_dir}
    info["perf_s"] = time.perf_counter()
    jax.profiler.start_trace(trace_dir)
    t0 = time.monotonic()
    info["armed_s"] = time.perf_counter() - info["perf_s"]
    try:
        yield info
    finally:
        info["window_s"] = time.monotonic() - t0
        jax.profiler.stop_trace()
