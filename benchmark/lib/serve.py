"""What the two serving drivers share: stand the system up, warm the
cell's shapes, let the load-generator child drive the window, read the
counters at its edges, take the trace slice, check the served text."""
from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time

import jax

from . import correct as C
from . import harness as H
from . import system as SUT
from . import trace_reduce as TR
from . import traffic as T
from . import weights as W


def _warm(served, traffic: dict, seed: int, vocab: int) -> None:
    """Every wave is a list of ``[count, prompt_len, max_tokens]`` groups
    submitted together; distinct prompts (a stream of their own, so they
    share nothing with the window's) keep the prefix cache out of it."""
    n = 0
    for wave in traffic.get("warmup", []):
        prompts, outs = [], []
        for count, plen, max_tokens in wave:
            for _ in range(count):
                n += 1
                prompts.append(T.prompt_tokens(seed, n, plen, vocab, stream=7))
                outs.append(max_tokens)
        served.generate(prompts, outs)


def _start_child(spec: dict):
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__), "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    child.stdin.write(json.dumps(spec) + "\n")
    child.stdin.flush()
    return child


def _read(child, key: str) -> dict:
    line = child.stdout.readline()
    if not line:
        raise RuntimeError(f"the load generator exited (code {child.poll()}) "
                           f"before sending {key!r}")
    doc = json.loads(line)
    if key not in doc:
        raise RuntimeError(f"the load generator sent {doc!r}, not {key!r}")
    return doc


def cycle_spans(cycles: list, to_ns) -> list:
    """(start_ns, end_ns, label) of each phase of each scheduler cycle,
    on the trace's clock. Coarse: the scheduler's own spans are not in
    the device trace, so a gap is placed by the flight recorder's host
    stamps alone."""
    spans = []
    for c in cycles:
        t = c["t"]
        for label, ms in (("cycle: sweep+admit", c["sweep_ms"] + c["admit_ms"]),
                          ("cycle: dispatch (operands, launch)",
                           c["decode_dispatch_ms"]),
                          ("cycle: fetch (host waits for the device)",
                           c["fetch_ms"])):
            spans.append((to_ns(t), to_ns(t + ms / 1e3), label))
            t += ms / 1e3
        end = c["t"] + c["cycle_ms"] / 1e3
        if end > t:
            spans.append((to_ns(t), to_ns(end), "cycle: emit+retire"))
    return spans


class Rig:
    """The served system, stood up and warmed once. One run drives one
    window through it; the calibration tools drive several."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.model, self.serving = config["model"], config["serving"]
        self.vocab = int(self.model["vocab_size"])
        self.devs = jax.devices()[:1]
        self.counter = SUT.CompileCounter()
        H.log(f"compile cache: {SUT.compile_cache_status()}")
        net = SUT.build_lm(self.model, seed, self.serving["dtype"])
        self.served = SUT.Served(net, self.model, self.serving,
                                 traffic["slots"])
        st = self.served.stats()
        H.log(f"engine: {st['kv_dtype']} pool of {st['num_blocks']} blocks x "
              f"{st['block_size']} tokens = "
              f"{st['kv_pool_capacity_bytes'] / 1e9:.2f} GB, "
              f"{traffic['slots']} slots, max_len {self.serving['max_len']}")
        _warm(self.served, traffic, seed, self.vocab)
        snap = self.counter.snapshot()
        H.log(f"warm-up done: {snap['registry']} programs, persistent cache "
              f"{snap['cache_hits']} hits / {snap['cache_misses']} misses; "
              f"fused sites "
              f"{sorted(s.split('#')[0][13:] for s in snap['sites'] if 'fused' in s)}")

    def window(self, traffic: dict, seed: int, seconds: float, trace: bool,
               mode: str) -> dict:
        """Drive one window of ``traffic`` (prompts and arrivals from
        ``seed``) and return its readings."""
        served = self.served
        out_file = H.out_path(f"loadgen-{os.getpid()}.json")
        child = _start_child({
            "url": served.url, "mode": mode, "traffic": traffic, "seed": seed,
            "vocab": self.vocab, "seconds": seconds,
            "drain_s": traffic.get("drain_s", 0.0), "out": out_file})
        slice_info = None
        try:
            _read(child, "ready")
            t0 = time.monotonic() + float(traffic["lead_s"])
            t1 = t0 + seconds
            child.stdin.write(json.dumps({"t0": t0}) + "\n")
            child.stdin.flush()
            if trace:
                served.start_cycle_poll()
            H.sleep_until(t0)
            at_start = self.counter.snapshot()
            H.log(f"window open (set-up {t0 - H.PROCESS_START:.1f} s)")
            if trace:
                H.sleep_until(t0 + float(traffic["trace_at_s"]))
                with H.profiler_slice("serve") as slice_info:
                    time.sleep(float(traffic["trace_slice_s"]))
            H.sleep_until(t1)
            at_end = self.counter.snapshot()
            H.log("window closed")
            _read(child, "done")
            child.wait(timeout=60)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        with open(out_file) as f:
            records = json.load(f)["records"]
        os.remove(out_file)
        compiles = SUT.compiles_between(at_start, at_end)
        H.log(f"compiles inside the window: {compiles}")
        readings = {
            "mode": mode, "t0": t0, "t1": t1, "seed": seed,
            "setup_s": t0 - H.PROCESS_START, "records": records,
            "compiles": compiles, "engine_stats": served.stats(),
            "door_stats": served.door_stats(),
            "device": H.device_report(self.devs), "model": self.model,
            "serving": self.serving, "traffic": traffic,
            "device_kind": self.devs[0].device_kind}
        if trace:
            readings["cycles"] = served.stop_cycle_poll()
            readings["slice"] = slice_info
        return readings

    def close(self) -> None:
        """Stop the server and free the device: the reference runs after
        this and needs the room the pool held. Whatever of the program's
        state is still referenced somewhere (telemetry collectors, server
        threads) is deleted outright — the benchmark keeps nothing large
        on the device across this point (small arrays, such as the
        program's PRNG key, stay)."""
        self.served.close()
        self.served = None
        gc.collect()
        left = [a for a in jax.live_arrays() if a.nbytes >= 1 << 20]
        for a in left:
            a.delete()
        ms = self.devs[0].memory_stats() or {}
        H.log(f"engine closed: {len(left)} large device arrays deleted, "
              f"{ms.get('bytes_in_use', 0) / 1e9:.2f} GB still in use")


def reduce_slice(readings: dict) -> tuple:
    """Add the trace's reduction to ``readings``; returns what the result
    line's ``device`` and ``breakdown`` take from it."""
    info = readings["slice"]
    reduced = TR.reduce_trace(TR.latest_xplane(info["dir"]), info["window_s"])
    # the trace counts nanoseconds from the profiler's start, the flight
    # recorder stamps perf_counter: tie them by the reading at that start
    to_ns = lambda perf_s: int((perf_s - info["perf_s"]) * 1e9)
    lo = to_ns(info["perf_s"] + info["armed_s"])
    hi = to_ns(info["perf_s"] + info["armed_s"] + info["window_s"])
    readings["trace"] = reduced
    readings["trace_cycles"] = [c for c in readings["cycles"]
                                if lo <= to_ns(c["t"]) < hi]
    inside = sum(1 for s, _ in reduced["intervals"] if lo <= s <= hi)
    H.log(f"trace: {reduced['busy_s']:.3f} s busy of {reduced['window_s']:.3f} s; "
          f"{inside}/{len(reduced['intervals'])} busy intervals start inside "
          f"the slice on the converted clock; "
          f"{len(readings['trace_cycles'])} scheduler cycles in it")
    breakdown = {
        "device_ops": TR.top_ops(reduced),
        "idle_gaps": TR.label_gaps(
            TR.gaps(reduced), cycle_spans(readings["trace_cycles"], to_ns),
            other="between scheduler cycles")}
    return {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}, \
        breakdown


def finished_in_window(readings: dict) -> list:
    t0, t1 = readings["t0"], readings["t1"]
    return [r for r in readings["records"]
            if r["done"] is not None and r["finish"] == "length"
            and t0 <= r["done"] < t1 and len(r["tokens"]) == r["max_tokens"]]


def check_window(config: dict, readings: dict, weight_seed: int,
                 quant=None) -> tuple:
    """(correct, numbers) of one closed window: what timing cannot change
    about its requests, then the sample's gaps against the reference.
    Run it once the engine is closed. With ``quant`` the control's
    numbers ride along as ``control_*``."""
    model, check = config["model"], config["serving"]["check"]
    vocab = int(model["vocab_size"])
    ok_struct, problems = C.window_requests_ok(readings["records"], vocab)
    for p in problems[:10]:
        H.log(f"check window request: {p}")
    nonfinite = int(readings["engine_stats"]["nonfinite_cycles"])
    H.log(f"check nonfinite_cycles: {nonfinite} (limit 0) "
          f"{'ok' if nonfinite == 0 else 'FAILED'}")
    sample = C.pick_sample(finished_in_window(readings), readings["seed"],
                           int(check["requests"]))
    if not sample:
        H.log("check: no request finished inside the window — nothing to "
              "compare, so not correct")
        return False, {}
    t_ref = time.monotonic()
    weights = W.make_weights(weight_seed, model, config["serving"]["dtype"])
    got = C.served_gaps(weights, int(model["num_attention_heads"]), sample,
                        readings["seed"], vocab, int(check["width"]),
                        int(check["rows_per_call"]), quant=quant)
    del weights
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
        extra_device, breakdown = reduce_slice(readings)
    correct, numbers = check_window(config, readings, seed)
    readings["check"] = numbers
    return {"correct": correct, "setup_s": readings["setup_s"],
            "readings": readings,
            "device": {**readings["device"], **extra_device},
            "breakdown": breakdown}


def window_token_times(records: list, t0: float, t1: float) -> tuple:
    """(number of tokens stamped inside [t0, t1), every inter-token gap
    in ms whose later token lies inside it)."""
    n, gaps = 0, []
    for r in records:
        ts = r["t"]
        for i, t in enumerate(ts):
            if t0 <= t < t1:
                n += 1
                if i:
                    gaps.append((t - ts[i - 1]) * 1e3)
    return n, gaps


def whole_cycle_rate(records: list, t0: float, t1: float) -> tuple:
    """(tokens, seconds) of the window closed on token stamps: from the
    first token stamped at or after ``t0`` to the first stamped at or
    after ``t1``, the first included and the second not.

    A backlog's tokens arrive in bursts, one per scheduler cycle (0.7 s
    at gpt2-large); a window with fixed edges holds a whole number of
    bursts that changes by one with the phase it opens at, which is 2%
    of the rate and no property of the system. Closed on stamps, the
    window holds whole cycles: all of their tokens over all of their
    time."""
    stamps = sorted(t for r in records for t in r["t"])
    opening = next((t for t in stamps if t >= t0), None)
    closing = next((t for t in stamps if t >= t1), None)
    if opening is None or closing is None or closing <= opening:
        raise ValueError("no token was stamped after the window's edges: "
                         "the drain after the window is too short")
    return sum(1 for t in stamps if opening <= t < closing), closing - opening
