"""``serve_backlog_family`` for a model generated by diffusion over
blocks: the same closed-loop backlog through the HTTP front door (the
rig, the window, the trace slice and the numbers are
``lib/serve_family.py``'s and ``drivers/serve_backlog_family.py``'s, by
import), with a check that is teacher-forced on the states the program
saw.

That check needs, for every served token, the pass of its block in which
it was fixed. The load generator keeps a token's id and arrival time
only, so before the rig is closed each finished record is joined with
the engine's retired trace of the request that generated the same ids
(``RequestTrace.token_ids`` / ``token_passes``, collected by a retire
hook on the flight recorder; prompts are distinct and outputs are
hundreds of tokens, so the ids identify the request), and the passes go
into the record as ``record["passes"]``, which ``family_sdar.served_gaps``
reads.
"""
from __future__ import annotations

import time

from benchmark.drivers import serve_backlog_family as base
from benchmark.lib import correct as C
from benchmark.lib import harness as H
from benchmark.lib import serve
from benchmark.lib import serve_family
from benchmark.lib import stats as S


def collect_passes(engine) -> dict:
    """Arm the retire hook: ``{tuple(token ids): passes}`` of every request
    the engine retires from now on."""
    seen = {}
    engine.flight_recorder.add_retire_hook(
        lambda trace: seen.__setitem__(tuple(trace.token_ids),
                                       list(trace.token_passes)))
    return seen


def join_passes(records: list, seen: dict) -> int:
    """``record["passes"]`` for every finished record whose ids a retired
    trace holds; returns how many were joined."""
    n = 0
    for r in records:
        passes = seen.get(tuple(r["tokens"]))
        if r["done"] is not None and passes is not None:
            r["passes"] = passes
            n += 1
    return n


def check_window(config: dict, readings: dict, weight_seed: int,
                 quant=None) -> tuple:
    """``serve_family.check_window`` with the order of the passes held to
    a limit too: (correct, numbers) of one closed window."""
    check = config["serving"]["check"]
    vocab = int(config["model"]["vocab_size"])
    ok_struct, problems = C.window_requests_ok(readings["records"], vocab)
    for p in problems[:10]:
        H.log(f"check window request: {p}")
    nonfinite = int(readings["engine_stats"]["nonfinite_cycles"])
    H.log(f"check nonfinite_cycles: {nonfinite} (limit 0) "
          f"{'ok' if nonfinite == 0 else 'FAILED'}")
    sample = C.pick_sample(
        [r for r in serve.finished_in_window(readings) if "passes" in r],
        readings["seed"], int(check["requests"]))
    if not sample:
        H.log("check: no request finished inside the window with its "
              "passes — nothing to compare, so not correct")
        return False, {}
    t_ref = time.monotonic()
    got = serve_family.family_of(config).served_gaps(
        config, sample, readings["seed"], weight_seed, quant=quant)
    numbers = summary(got["gaps"], got["order_gaps"])
    if quant is not None:
        numbers.update({f"control_{k}": v for k, v in summary(
            got["control_gaps"], got["control_order_gaps"]).items()})
    ok, lines = C.verdict(numbers, check["limits"])
    for line in lines:
        H.log(line)
    H.log(f"check: {len(sample)} requests, {numbers['tokens']} served tokens "
          f"in {numbers['passes']} passes, "
          f"{numbers['not_argmax_share'] * 100:.2f}% not the reference's "
          f"first choice, {numbers['not_first_share'] * 100:.2f}% of the "
          f"passes not at its most confident position; reference took "
          f"{time.monotonic() - t_ref:.1f} s")
    return bool(ok_struct and nonfinite == 0 and ok), numbers


def summary(gaps, order_gaps) -> dict:
    return {**C.gap_summary(gaps), "passes": int(order_gaps.size),
            "mean_order_gap": float(order_gaps.mean()),
            "max_order_gap": float(order_gaps.max()),
            "not_first_share": float((order_gaps > 0).mean())}


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    rig = serve_family.Rig(config, traffic, seed)
    try:
        seen = collect_passes(rig.served.engine)
        readings = rig.window(traffic, seed, seconds, trace, "backlog")
        joined = join_passes(readings["records"], seen)
        H.log(f"passes joined onto {joined} of "
              f"{sum(r['done'] is not None for r in readings['records'])} "
              f"finished records")
    finally:
        rig.close()
    extra_device, breakdown = {}, None
    if trace:
        extra_device, breakdown = serve.reduce_slice(readings)
    correct, numbers = check_window(config, readings, seed)
    readings["check"] = numbers
    res = {"correct": correct, "setup_s": readings["setup_s"],
           "readings": readings,
           "device": {**readings["device"], **extra_device},
           "breakdown": breakdown}
    return finish(res, trace)


def finish(res: dict, trace: bool) -> dict:
    """The window's numbers, as ``serve_backlog_family.run`` takes them
    (that file may not be edited by the PR that adds this one; a
    ``benchmark`` PR can make this its function): tokens per second over
    the window closed on token stamps, requests ended and failed."""
    r = res["readings"]
    t0, t1 = r["t0"], r["t1"]
    n_tokens, gaps = serve.window_token_times(r["records"], t0, t1)
    ended = [x for x in r["records"]
             if (x["done"] is not None and t0 <= x["done"] < t1)
             or (x["done"] is None and x["sent"] is not None
                 and t0 <= x["sent"] < t1
                 and (x["error"] or x["status"] not in (None, 200)))]
    failed = [x for x in ended if x["done"] is None
              or len(x["tokens"]) != x["max_tokens"]]
    H.log(f"window: {n_tokens} tokens, {len(gaps)} inter-token gaps, "
          f"{len(ended)} requests ended ({len(failed)} failed)")
    res["attempted"], res["failed"] = len(ended), len(failed)
    tokens, secs = serve.whole_cycle_rate(r["records"], t0, t1)
    H.log(f"window closed on token stamps: {tokens} tokens in {secs:.3f} s; "
          f"inter-token gap p50 {S.percentile(gaps, 50):.1f} ms, "
          f"p95 {S.percentile(gaps, 95):.1f} ms")
    res["end_to_end"] = {"serve_tok_s": tokens / secs}
    if trace:
        base._log_launch_mix(r)
    return res
