"""The serving rig for a configuration that names its ``"family"``:
``lib/serve.py``'s rig with the four things that differ between
architectures — build the model, make its weights, size its pool, run its
reference — taken from ``lib/family_<family>.py``. Everything else
(``Rig.window``, ``Rig.close``, the load-generator child, the trace
slice, the sample and the verdict of ``lib/correct.py``) is
``lib/serve.py``'s, by import and subclass.

A family module provides ``build_lm(model, seed, dtype)``,
``pool_blocks_for_share(model, serving)`` and ``served_gaps(config,
sample, seed, weight_seed, quant=None)``.
"""
from __future__ import annotations

import importlib
import threading
import time

import jax

from . import correct as C
from . import harness as H
from . import serve
from . import system as SUT


def family_of(config: dict):
    return importlib.import_module(f"benchmark.lib.family_{config['family']}")


class Served(SUT.Served):
    """``system.Served`` with the pool sized by the family's rule."""

    def __init__(self, net, num_blocks: int, serving: dict, slots: int):
        from paddle_tpu.serving import FrontDoor, GenerationEngine
        self.num_blocks = int(num_blocks)
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


class Rig(serve.Rig):
    """``serve.Rig`` stood up from the configuration's family."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.model, self.serving = config["model"], config["serving"]
        self.vocab = int(self.model["vocab_size"])
        self.devs = jax.devices()[:1]
        self.counter = SUT.CompileCounter()
        H.log(f"compile cache: {SUT.compile_cache_status()}")
        family = family_of(config)
        net = family.build_lm(self.model, seed, self.serving["dtype"])
        ms = self.devs[0].memory_stats() or {}
        H.log(f"model built: {ms.get('bytes_in_use', 0) / 1e9:.2f} GB of "
              f"weights on the device")
        self.served = Served(
            net, family.pool_blocks_for_share(self.model, self.serving),
            self.serving, traffic["slots"])
        st = self.served.stats()
        H.log(f"engine: {st['kv_dtype']} pool of {st['num_blocks']} blocks x "
              f"{st['block_size']} tokens = "
              f"{st['kv_pool_capacity_bytes'] / 1e9:.2f} GB, "
              f"{traffic['slots']} slots, max_len {self.serving['max_len']}")
        serve._warm(self.served, traffic, seed, self.vocab)
        snap = self.counter.snapshot()
        H.log(f"warm-up done: {snap['registry']} programs, persistent cache "
              f"{snap['cache_hits']} hits / {snap['cache_misses']} misses; "
              f"fused sites "
              f"{sorted(s.split('#')[0][13:] for s in snap['sites'] if 'fused' in s)}")


    def window(self, traffic: dict, seed: int, seconds: float, trace: bool,
               mode: str) -> dict:
        readings = super().window(traffic, seed, seconds, trace, mode)
        scopes = getattr(family_of(self.config), "SCOPES", ())
        if trace and scopes:
            # while the engine lives: the compiled step programs' text
            # names the instructions of each scope (lib/scope_ops.py)
            from . import scope_ops
            text = fused_program_text()
            readings["scope_keys"] = {
                s: sorted(scope_ops.scope_keys(text, s)) for s in scopes}
            H.log("scope instructions in the step programs: "
                  + str({s: len(k) for s, k in readings["scope_keys"].items()}))
        return readings


def fused_program_text() -> str:
    """Optimized HLO text of every fused step program alive."""
    from paddle_tpu.framework import program_registry
    return "\n".join(program_registry.compiled_text(site) or ""
                     for site in program_registry.snapshot()
                     if site.startswith("serving/fused["))


def check_window(config: dict, readings: dict, weight_seed: int,
                 quant=None) -> tuple:
    """``serve.check_window`` with the family's reference: (correct,
    numbers) of one closed window. Run it once the engine is closed."""
    check = config["serving"]["check"]
    vocab = int(config["model"]["vocab_size"])
    ok_struct, problems = C.window_requests_ok(readings["records"], vocab)
    for p in problems[:10]:
        H.log(f"check window request: {p}")
    nonfinite = int(readings["engine_stats"]["nonfinite_cycles"])
    H.log(f"check nonfinite_cycles: {nonfinite} (limit 0) "
          f"{'ok' if nonfinite == 0 else 'FAILED'}")
    sample = C.pick_sample(serve.finished_in_window(readings),
                           readings["seed"], int(check["requests"]))
    if not sample:
        H.log("check: no request finished inside the window — nothing to "
              "compare, so not correct")
        return False, {}
    t_ref = time.monotonic()
    got = family_of(config).served_gaps(config, sample, readings["seed"],
                                        weight_seed, quant=quant)
    numbers = C.gap_summary(got["gaps"])
    if quant is not None:
        numbers.update({f"control_{k}": v for k, v in
                        C.gap_summary(got["control_gaps"]).items()})
    ok, lines = C.verdict(numbers, check["limits"])
    for line in lines:
        H.log(line)
    H.log(f"check: {len(sample)} requests, {numbers['tokens']} served tokens, "
          f"{numbers['not_argmax_share'] * 100:.2f}% not the reference's first "
          f"choice; reference took {time.monotonic() - t_ref:.1f} s")
    return bool(ok_struct and nonfinite == 0 and ok), numbers


def serve_cell(config: dict, traffic: dict, seed: int, seconds: float,
               trace: bool, mode: str) -> dict:
    rig = Rig(config, traffic, seed)
    try:
        readings = rig.window(traffic, seed, seconds, trace, mode)
    finally:
        rig.close()
    extra_device, breakdown = {}, None
    if trace:
        extra_device, breakdown = serve.reduce_slice(readings)
    correct, numbers = check_window(config, readings, seed)
    readings["check"] = numbers
    return {"correct": correct, "setup_s": readings["setup_s"],
            "readings": readings,
            "device": {**readings["device"], **extra_device},
            "breakdown": breakdown}
