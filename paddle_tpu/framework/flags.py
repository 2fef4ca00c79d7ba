"""Process-level flag registry.

Analog of the reference's exported gflags
(/root/reference/paddle/fluid/platform/flags.cc) surfaced to Python through
``get_flags``/``set_flags`` (python/paddle/fluid/framework.py:7112,7136).
Flags may be seeded from the environment (``FLAGS_*`` vars) exactly like
gflags' env fallback.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable


class _Flag:
    __slots__ = ("name", "default", "value", "help", "type")

    def __init__(self, name, default, help_str=""):
        self.name = name
        self.default = default
        self.help = help_str
        self.type = type(default)
        env = os.environ.get(name)
        self.value = self._parse(env) if env is not None else default

    def _parse(self, text: str):
        if self.type is bool:
            return text.strip().lower() in ("1", "true", "yes", "on")
        if self.type in (int, float):
            return self.type(text)
        return text


_REGISTRY: Dict[str, _Flag] = {}


def define_flag(name: str, default, help_str: str = "") -> None:
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    if name not in _REGISTRY:
        _REGISTRY[name] = _Flag(name, default, help_str)


def _canon(name: str) -> str:
    return name if name.startswith("FLAGS_") else "FLAGS_" + name


def get_flags(flags) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = _canon(f)
        if key not in _REGISTRY:
            raise ValueError(f"Flag {f} not registered")
        out[key] = _REGISTRY[key].value
    return out


def set_flags(flags: Dict[str, Any]) -> None:
    for name, value in flags.items():
        key = _canon(name)
        if key not in _REGISTRY:
            raise ValueError(f"Flag {name} not registered")
        flag = _REGISTRY[key]
        flag.value = flag.type(value) if flag.type is not type(None) else value


def flag_value(name: str):
    return _REGISTRY[_canon(name)].value


def all_flags() -> Iterable[str]:
    return list(_REGISTRY)


# Core flags (subset of the reference's 56, the ones with TPU meaning).
define_flag("FLAGS_check_nan_inf", False,
            "Sweep op outputs for NaN/Inf after each eager op "
            "(reference: framework/details/nan_inf_utils_detail.cc). "
            "Also seeds Model.fit(numerics=None) to 'halt' — the "
            "windowed, zero-sync analog of the reference's "
            "abort-on-first-NaN (profiler/numerics.py)")
define_flag("FLAGS_benchmark", False, "Print per-op timing in eager mode")
define_flag("FLAGS_check_shapes", True,
            "InferMeta-style pre-dispatch shape validation with call-site "
            "errors (reference: phi/infermeta/)")
define_flag("FLAGS_use_standalone_executor", True,
            "Kept for API parity; the XLA executor is always standalone")
define_flag("FLAGS_eager_jit_ops", True,
            "Route eager op calls through cached jax.jit wrappers")
define_flag("FLAGS_allocator_strategy", "auto_growth",
            "Parity flag; HBM allocation is managed by PjRt")
define_flag("FLAGS_enable_profiler", False,
            "Arm the structured span profiler for the whole process at "
            "import (profiler/span.py); equivalent to wrapping main() in "
            "profiler.profile(). Env-seeded: FLAGS_enable_profiler=1")
define_flag("FLAGS_profiler_max_events", 1_000_000,
            "Span buffer cap: past it events are dropped (and counted in "
            "profiler.dropped()) instead of growing host memory")
define_flag("FLAGS_compile_cache", True,
            "Persist XLA-compiled executables to disk "
            "(framework/compile_cache.py) so repeat runs skip recompiles; "
            "armed at import. The directory is JAX_COMPILATION_CACHE_DIR "
            "if set, else <checkout>/.cache/xla. FLAGS_compile_cache=0 "
            "switches it off")
define_flag("FLAGS_static_analysis", "off",
            "Default mode for the jaxpr-level program linter "
            "(paddle_tpu/analysis): 'warn' runs the pass pipeline over "
            "every newly built hapi train step and captured static "
            "Program and logs findings; 'error' additionally raises "
            "AnalysisError on error-severity findings; 'off' disables "
            "the pre-flight (explicit Model.fit(analyze=...) still "
            "wins). Env-seeded: FLAGS_static_analysis=warn")
define_flag("FLAGS_numerics", "",
            "Default numerics-health mode for Model.fit "
            "(off|record|warn|halt): the device-side NaN/Inf audit "
            "fused into the donated train step, gradient telemetry "
            "histograms, the training flight recorder and the anomaly "
            "postmortem (profiler/numerics.py). Empty defers to "
            "FLAGS_check_nan_inf (set -> 'halt'), else 'off'")
define_flag("FLAGS_zero_stage", 0,
            "Default Model.fit(zero=) stage: 1 shards the optimizer "
            "state and the weight update across the data-parallel mesh "
            "axis inside the donated train step (reduce-scatter grads "
            "-> shard-local update -> all-gather params, hapi/zero.py; "
            "arXiv 2004.13336), cutting per-replica train-state HBM "
            "~dp-fold; 0 keeps the replicated step. Env-seeded: "
            "FLAGS_zero_stage=1")
define_flag("FLAGS_grad_comm", "fp32",
            "Default Model.fit(grad_comm=) gradient-exchange precision "
            "for the ZeRO-sharded step: 'int8' runs an EQuARX-style "
            "quantized reduce-scatter (per-chunk max-abs scales "
            "computed in-step, ~4x fewer wire bytes), 'fp32' the exact "
            "exchange. Ignored unless zero sharding is armed")
define_flag("FLAGS_collective_timing", True,
            "Sampled device-side collective timing "
            "(distributed/collective.py): eager collectives get a "
            "block-until-ready bracket and the ZeRO step runs an "
            "isolated same-shape probe of its reduce-scatter/all-gather "
            "pair, feeding collective_time_ms/<kind> + "
            "collective_bw_gbps/<kind> histograms and the "
            "exposed-vs-overlapped communication report")
define_flag("FLAGS_collective_timing_every", 16,
            "Sampling stride for collective timing: the first call per "
            "kind is always timed, then every Nth — a block-until-ready "
            "per call would serialize the device, so timing stays a "
            "sample, not a census")
define_flag("FLAGS_hapi_prefetch", True,
            "Route Model.fit/evaluate input through io.device_prefetch "
            "(background H2D overlapping compute); the escape hatch for "
            "iterables that must not be read ahead of consumption")
define_flag("FLAGS_flight_dump_dir", "",
            "Directory for serving FlightRecorder.auto_dump postmortem "
            "files (created on first dump). Empty falls back to the "
            "system tempdir — ops point this at persistent storage so a "
            "3am poisoned-cycle dump survives the node. Env-seeded: "
            "FLAGS_flight_dump_dir=/var/log/paddle")
define_flag("FLAGS_cudnn_deterministic", False, "Parity flag")
define_flag("FLAGS_embedding_deterministic", False, "Parity flag")
define_flag("FLAGS_conv_workspace_size_limit", 512, "Parity flag (MB)")
