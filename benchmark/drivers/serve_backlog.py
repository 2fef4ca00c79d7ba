"""Closed-loop backlog through the HTTP front door: every slot busy,
every finished request replaced at once. Reports what an operator buys
above the knee: tokens per second. The gap between tokens is a per-layer
metric here: its tail flips between plain decode cycles and cycles that
carry a prompt chunk and holds no bound."""
from __future__ import annotations

from benchmark.lib import harness as H
from benchmark.lib import serve
from benchmark.lib import stats as S


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    res = serve.serve_cell(config, traffic, seed, seconds, trace, "backlog")
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
    return res
