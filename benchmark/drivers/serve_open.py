"""Open-loop arrivals at a fixed rate through the HTTP front door.
Requests DUE inside the window are measured, from when they were due;
what has not finished one drain cap after the window has failed and
enters each tail at the cap."""
from __future__ import annotations

from benchmark.lib import harness as H
from benchmark.lib import serve
from benchmark.lib import stats as S
from benchmark.lib import traffic as T


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    res = serve.serve_cell(config, traffic, seed, seconds, trace, "open")
    r = res["readings"]
    t0, t1 = r["t0"], r["t1"]
    start = t0 - float(traffic["lead_s"])
    sched = T.open_loop_schedule(traffic, seed, seconds)
    due_in = {i for i, (off, _, _) in enumerate(sched)
              if t0 <= start + off < t1}
    by_index = {x["index"]: x for x in r["records"]}
    cap_ms = (seconds + float(traffic["drain_s"])) * 1e3
    ttft, gaps, failed, late = [], [], 0, []
    for i in sorted(due_in):
        x = by_index.get(i)
        ok = x is not None and x["done"] is not None \
            and len(x["tokens"]) == x["max_tokens"]
        if x is not None and x["sent"] is not None:
            late.append((x["sent"] - x["due"]) * 1e3)
        if not ok:
            failed += 1
            ttft.append(None)
            gaps.append(None)
            continue
        ttft.append((x["t"][0] - x["due"]) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(x["t"], x["t"][1:]))
    r["late_ms"] = late
    r["measured"] = len(due_in)
    H.log(f"window: {len(due_in)} requests due, {failed} failed, "
          f"{len(gaps)} inter-token gaps; generator late p95 "
          f"{S.percentile(late, 95) if late else float('nan'):.2f} ms")
    res["attempted"], res["failed"] = len(due_in), failed
    res["end_to_end"] = {
        "ttft_p95_ms": S.percentile(S.with_missing(ttft, cap_ms), 95),
        "itl_p95_ms": S.percentile(S.with_missing(gaps, cap_ms), 95)}
    return res
