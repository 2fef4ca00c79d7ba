"""``serve_backlog`` for a configuration that names its ``"family"``: the
same closed-loop backlog through the HTTP front door, every slot busy and
every finished request replaced at once, reporting tokens per second —
with the model, its weights, its pool rule and its reference taken from
``lib/family_<family>.py`` (``lib/serve_family.py``). ``serve_backlog.py``
itself builds GPT-2 in ``serve.Rig`` and ``serve.check_window``."""
from __future__ import annotations

import time

from benchmark.lib import harness as H
from benchmark.lib import serve
from benchmark.lib import serve_family
from benchmark.lib import stats as S


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    res = serve_family.serve_cell(config, traffic, seed, seconds, trace,
                                  "backlog")
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
        _log_launch_mix(r)
    return res


def _log_launch_mix(r: dict) -> None:
    """Plain launches (decode rows only) and launches that carry a prompt
    chunk, by fifth of the window: the readers of the plain launch
    (``decode_step_ms``, ``q_row_fill``) find something to read only where
    the slice holds one."""
    t0, t1 = r["t0"], r["t1"]
    to_mono = time.monotonic() - time.perf_counter()
    bins = [[0, 0, 0.0, 0.0] for _ in range(5)]
    for c in r.get("cycles", []):
        at = c["t"] + to_mono
        if t0 <= at < t1 and c.get("launch_q"):
            b = bins[min(4, int(5 * (at - t0) / (t1 - t0)))]
            chunk = c.get("chunk_tokens", 0) > 0
            b[chunk] += 1
            b[2 + chunk] += c["cycle_ms"]
    H.log("launches by fifth of the window, plain/chunk (mean cycle ms): "
          + ", ".join(f"{p}/{k} ({pm / max(p, 1):.0f}/{km / max(k, 1):.0f})"
                      for p, k, pm, km in bins))
