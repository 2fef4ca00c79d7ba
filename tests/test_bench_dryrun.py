"""CI smoke for the observability surface: ``bench.py --dry-run``.

One tiny CPU train step under profiler.profile() must emit a metrics
summary (counters non-empty), a chrome trace with >= 3 nested span
categories, and a Prometheus exposition — the cheap canary that an
instrumentation regression trips BEFORE it costs a real benchmark round.
Runs in a subprocess like the real driver invocation. Since PR 45 the
canary is ``slow`` (outside tier-1): it took 229-282 s against the 120 s
every test has (``conftest.TEST_LIMIT_S``) and its own 300 s. It is a
second benchmark's canary (ROADMAP D4 removes ``bench.py``), and tier-1
holds each thing it asserts in the process of a test:

* the windowed host syncs of ``fit`` and the prefetch put / wait
  histograms, the compile cache's entries: ``test_async_fit.py``;
* the analyzer on the GPT-2 / ResNet zoo steps and the fit pre-flight,
  ``dispatch/retrace_cause``, the self-lint at zero findings:
  ``test_analysis.py``, ``test_selflint.py``;
* serving parity with ``generate``, live counters, prefix hits, chunked
  prefill under a small budget, one trace a ``(q, table)`` bucket, the
  step analysed clean: ``test_serving_engine.py``,
  ``test_serving_paging.py``, ``test_ragged_attention.py``; speculative
  parity, ``serving/spec_accept`` and int8 blocks: ``test_spec_decode.py``,
  ``test_serving_paging.py::TestQuantizedBlocks``;
* request traces, TTFT / TPOT, the flight recorder: ``test_serving_trace.py``;
  the ops server's ``/metrics``, ``/healthz``, ``/tracez`` and goodput:
  ``test_ops_server.py``; the front door's round trip, SSE, 429 and a
  malformed body: ``test_frontdoor.py``;
* compile cost in the registry, ``hapi/mfu`` and FLOPs a token:
  ``test_program_registry.py``; the HBM ledger: ``test_memory_tracker.py``;
* the numerics sentinel, its postmortem and its zero extra programs:
  ``test_numerics.py``; ZeRO parity and sharded optimizer bytes:
  ``test_zero_sharding.py``; mp=2 serving parity and per-device KV bytes:
  ``test_serving_sharded.py``; the host tier's hits, promotions and hit
  split: ``test_host_tier.py``; the planner's cross-check and its gate
  before any compile: ``test_plan_gate.py``, ``test_analysis.py``;
* the exports (chrome trace span categories, Prometheus text):
  ``test_profiler.py``, ``test_metrics_registry.py``;
* ``bench.py --compare``, which only the canary ran: the test below.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "bench.py")


def test_compare_gate_flags_a_doctored_artifact(tmp_path):
    """``bench.py --compare OLD NEW`` as a driver would call it: a copy
    with a fifth of the throughput gone and latency up two fifths exits 1,
    an artifact against itself exits 0 (the parent entry imports no jax:
    milliseconds a child)."""
    seeded = {"metric": "gpt2_tps", "value": 100.0, "unit": "tokens/sec",
              "extras": {
                  "gpt2": {"metric": "gpt2_tps", "value": 100.0,
                           "unit": "tokens/sec", "mfu": 0.40},
                  "serve": {"metric": "serve_lenet_latency_p50_ms",
                            "value": 10.0, "unit": "ms"}}}
    doctored = json.loads(json.dumps(seeded))
    doctored["extras"]["gpt2"]["value"] = 80.0
    doctored["extras"]["serve"]["value"] = 14.0
    (tmp_path / "a.json").write_text(json.dumps(seeded))
    (tmp_path / "b.json").write_text(json.dumps(doctored))

    def compare(new):
        return subprocess.run(
            [sys.executable, BENCH, "--compare", str(tmp_path / "a.json"),
             str(tmp_path / new)], capture_output=True, text=True,
            timeout=60)

    same, worse = compare("a.json"), compare("b.json")
    assert same.returncode == 0, same.stdout + same.stderr
    assert worse.returncode == 1, worse.stdout + worse.stderr
    assert "gpt2" in worse.stdout and "serve" in worse.stdout


@pytest.mark.slow          # see the module doc for what covers it
def test_dry_run_emits_metrics_summary():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, BENCH, "--dry-run"], env=env,
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, \
        f"--dry-run failed\nstdout: {res.stdout}\nstderr: {res.stderr[-2000:]}"
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"] is True, out
    assert out["counters"] > 0
    assert len(out["span_categories"]) >= 3, out
    # the human-readable stats summary goes to stderr
    assert "op_count/" in res.stderr
    # async fast path: the dry run fits 8 batches at log_freq=4, so the
    # windowed-sync budget is <= 8/4 + 2 flushes, and the prefetch
    # pipeline must have fed fit (put/wait histograms in the summary)
    assert 0 < out["host_syncs"] <= 4, out
    assert out["checks"]["prefetch_histograms_present"] is True, out
    assert "prefetch_put_ms" in res.stderr
    assert "prefetch_wait_ms" in res.stderr
    assert "hapi/host_sync" in res.stderr
    # compile cache: entries whenever this jax supports it (0.4.37 does);
    # on a jax without the knob the dry run records a clean no-op
    if out["compile_cache_enabled"]:
        assert out["compile_cache_entries"] > 0, out
    # PR-3 static-analysis surface: the fit pre-flight plus the GPT-2/
    # ResNet zoo steps ran the linter (>=3 analyze() runs), the zoo
    # steps reported zero error-severity findings, the retrace-cause
    # classifier populated dispatch/retrace_cause (tracing two networks
    # guarantees per-op shape variety), and the repo self-lint is clean
    assert out["analysis_runs"] >= 3, out
    assert out["checks"]["zoo_steps_clean"] is True, out
    assert out["checks"]["analysis_findings_counted"] is True, out
    assert out["retrace_causes"].get("shape", 0) > 0, out
    assert out["selflint_findings"] == 0, out
    assert "analysis/findings" in res.stderr
    assert "dispatch/retrace_cause" in res.stderr
    # serving surface: the continuous-batching canary completed every
    # request token-identical to models.generate, its metrics are live,
    # the repeated system prompt hit the prefix cache (whole blocks
    # skipped), a 40-token prompt chunked under the 8-token prefill
    # budget, the fused step analyzed clean (donation-safe,
    # host-sync-free — the Pallas call included) and every (q, table)
    # bucket traced exactly once — plus the serving-host-sync self-lint
    # staying green (selflint_findings == 0 above walks the whole package)
    assert out["serving"]["requests"] == 8, out
    assert out["checks"]["serving_completed"] is True, out
    assert out["checks"]["serving_parity"] is True, out
    assert out["checks"]["serving_counters_live"] is True, out
    assert out["checks"]["serving_prefix_hit"] is True, out
    assert out["checks"]["serving_chunked_prefill"] is True, out
    assert out["checks"]["serving_step_clean"] is True, out
    assert out["checks"]["serving_one_trace_per_bucket"] is True, out
    assert out["serving"]["prefix_hits"] >= 4, out
    assert out["serving"]["prefill_tokens_saved"] >= 64, out
    assert out["serving"]["prefill_chunks"] >= 5, out
    assert out["serving"]["chunked_prefill_tokens"] >= 40, out
    assert "serving/ttft_ms" in res.stderr
    assert "serving/tokens_per_sec" in res.stderr
    assert "serving/kv_blocks_in_use" in res.stderr
    assert "serving/prefix_hit" in res.stderr
    assert "serving/prefill_chunks" in res.stderr
    assert "serving/chunk_tokens" in res.stderr
    # ISSUE-12 speculative decoding + int8 KV blocks: greedy spec
    # output token-identical to the plain fused engine (cold and warm
    # waves), serving/spec_accept live with > 1 token per decode cycle
    # on the agreeing draft, exactly one trace per spec (q, table)
    # bucket with zero warm retraces (no storm from verify rows), and
    # the int8-block engine agreeing token-for-token with fp32
    assert out["checks"]["spec_parity"] is True, out
    assert out["checks"]["spec_accept_live"] is True, out
    assert out["checks"]["spec_one_trace_per_bucket"] is True, out
    assert out["checks"]["spec_int8_agrees"] is True, out
    assert out["spec"]["accept_rate"] == 1.0, out
    assert out["spec"]["tokens_per_cycle"] > 1.0, out
    # untrained canary model: near-tie argmaxes may flip a couple of
    # tokens under int8 noise; trained-margin exactness is pinned in
    # test_serving_paging.py::TestQuantizedBlocks
    assert out["spec"]["int8_token_agreement"] >= 0.75, out
    assert "serving/spec_accept" in res.stderr
    assert "serving/spec_tokens_per_cycle" in res.stderr
    # ISSUE-6 serving SLO observability: the canary completed every
    # request with lifecycle-ordered traces, per-engine TTFT/TPOT in
    # stats(), a live serving/tpot_ms histogram and a non-empty
    # always-on flight recorder
    assert out["checks"]["serving_traces_complete"] is True, out
    assert out["checks"]["serving_tpot_live"] is True, out
    assert out["checks"]["serving_flight_recorder"] is True, out
    assert "serving/tpot_ms" in res.stderr
    assert "serving/cycle_ms" in res.stderr
    assert "serving/batch_occupancy" in res.stderr
    # PR-16 SLO plane / ops surface: the zero-dependency ops HTTP
    # server booted on an ephemeral port during the serving canary,
    # a live GET /metrics parsed back non-empty WITH the slo_attainment
    # series, /healthz answered 200 while serving and flipped to 503
    # after engine close, /tracez carried the tail-sampled traces and
    # the SLO report, and stats() published SLO-gated goodput
    assert out["checks"]["ops_server_scrape"] is True, out
    assert out["checks"]["ops_server_healthz"] is True, out
    assert out["checks"]["ops_server_tracez"] is True, out
    assert out["checks"]["ops_server_goodput"] is True, out
    # PR-19 HTTP front door: an ephemeral-port /v1/completions canary
    # round-tripped a non-streamed completion byte-identical to the
    # in-process stream (usage included), streamed one request over SSE
    # ending in [DONE], drew a per-tenant 429 with retry_after_s from
    # the token bucket, and survived a malformed-JSON body (400) with
    # the server thread still answering afterwards
    assert out["checks"]["frontdoor_roundtrip"] is True, out
    assert out["checks"]["frontdoor_sse_stream"] is True, out
    assert out["checks"]["frontdoor_429_shed"] is True, out
    assert out["checks"]["frontdoor_survives_malformed"] is True, out
    fd = out["frontdoor"]
    assert fd["served"] >= 2, fd
    assert fd["shed"].get("starved", 0) >= 1, fd
    # ISSUE-7 compute/memory observability: every owned jit site
    # registered its compile cost (compile/ms + compile/count live), the
    # train step's XLA cost analysis produced hapi/flops_per_sec and —
    # under the dry run's pinned fake peak — hapi/mfu, both serving
    # engines derived model-FLOPs-per-token from their decode records,
    # the HBM ledger holds the train state with serving-cycle/pool
    # watermarks on the timeline, and the --compare regression gate
    # flagged the doctored artifact while the self-compare exited 0
    assert out["checks"]["registry_compiles_recorded"] is True, out
    assert out["checks"]["hapi_mfu_present"] is True, out
    assert out["checks"]["serving_flops_per_token"] is True, out
    assert out["checks"]["memory_ledger_live"] is True, out
    assert out["checks"]["bench_compare_gate"] is True, out
    assert out["compile_count"] > 0, out
    assert out["hapi_mfu"] is not None and out["hapi_mfu"] > 0, out
    assert out["serving"]["model_flops_per_token"] > 0, out
    assert out["memory_ledger_bytes"] > 0, out
    assert out["compare_gate_rc"] == {"self": 0, "regression": 1}, out
    assert "compile/ms" in res.stderr
    assert "hapi/mfu" in res.stderr
    assert "hapi/flops_per_sec" in res.stderr
    # ISSUE-10 training numerics health: the clean numerics='record'
    # fit left the gradient telemetry live (hapi/grad_norm +
    # hapi/grad_clip_ratio) with ZERO additional compiled programs on a
    # warm re-fit (the audit is fused into the donated step, asserted
    # via the PR-7 registry compile/count), the injected-inf warn run
    # tripped the NaN/Inf sentinel at the exact step within one flush
    # window with a round-tripping anomaly postmortem JSON, and
    # hapi/host_sync stayed at the PR-2 windowed budget throughout
    assert out["checks"]["numerics_sentinel"] is True, out
    assert out["checks"]["numerics_postmortem"] is True, out
    assert out["checks"]["numerics_sync_budget"] is True, out
    assert out["checks"]["numerics_zero_extra_programs"] is True, out
    assert out["checks"]["numerics_grad_norm_live"] is True, out
    num = out["numerics"]
    assert num["anomaly_step"] == num["inject_step"], num
    assert num["nonfinite_steps"] > 0, num
    assert "hapi/grad_norm" in res.stderr
    assert "hapi/nonfinite_steps" in res.stderr

    # ISSUE-11 ZeRO canary: on the dp=4 mesh (the conftest forces 8
    # host devices, so the canary never skips here) fit(zero=1) trained
    # allclose-identical params to the replicated donated step, and the
    # PR-7 ledger billed per-replica opt-state bytes at ~1/dp of the
    # replicated run (one quantization-chunk stripe of padding allowed)
    assert out["checks"]["zero_parity"] is True, out
    assert out["checks"]["zero_opt_state_sharded"] is True, out
    zc = out["zero"]
    assert zc["skipped"] is False, zc
    assert zc["opt_bytes"] < zc["replicated_opt_bytes"] / 2, zc

    # ISSUE-15 tensor-parallel serving canary: on the mp=2 mesh (never
    # skipped here — the conftest's 8 forced host devices reach the
    # subprocess via env) the sharded paged engine generated greedy
    # output token-identical to the single-device engine, and the
    # per-device KV block bytes on the ledger are exactly 1/mp of the
    # single-device pool
    assert out["checks"]["mp_parity"] is True, out
    assert out["checks"]["mp_kv_bytes_per_device"] is True, out
    mc = out["mp"]
    assert mc["skipped"] is False, mc
    assert mc["kv_bytes_per_device"] * 2 == mc["single_device_kv_bytes"], mc

    # ISSUE-20 hierarchical KV cache: the tiered canary demoted warm
    # prefix blocks to the host pool under device-pool pressure, a
    # later request with the same preamble hit the HOST tier (prefix
    # blocks promoted back over async H2D, bit-identical — greedy
    # token parity with an untiered engine holds), the promotion
    # counters are live, and the aggregate serving/prefix_hit split
    # into hbm/host/miss sums to one
    assert out["checks"]["tiered_host_hit"] is True, out
    assert out["checks"]["tiered_promotion_live"] is True, out
    assert out["checks"]["tiered_parity"] is True, out
    td = out["tiered"]
    assert td["host_hits"] > 0, td
    assert td["demoted"] > 0 and td["promoted"] > 0, td
    split = td["hit_split"]
    assert abs(sum(split.values()) - 1.0) < 1e-9, split
    assert split["prefix_hit_host"] > 0, split
    assert "serving/tier_hit_host" in res.stderr

    # ISSUE-18 static memory planner: the donation-aware liveness
    # estimate bracketed XLA's memory_analysis on EVERY program the dry
    # run compiled where both figures exist (a real GPT train step and
    # the serving buckets among them), the doctored 64 KiB budget made
    # engine construction raise PlanError naming the fattest program
    # point with compile/count UNCHANGED (fit-before-compile), and the
    # generous budget attached a fitting plan
    assert out["checks"]["planner_crosscheck"] is True, out
    assert out["checks"]["planner_gate_raises"] is True, out
    assert out["checks"]["planner_gate_zero_compiles"] is True, out
    assert out["checks"]["planner_generous_fits"] is True, out
    pl = out["planner"]
    assert pl["n_crosschecked"] >= 10, pl
    assert any("train_step" in s for s in pl["ratios"]), pl
    assert any(s.startswith("serving/") for s in pl["ratios"]), pl
    assert pl["gate"]["raised"] is True, pl
    assert pl["gate"]["peak_point"], pl
    assert pl["gate"]["plan"]["fits"] is False, pl
    assert pl["gate_extra_compiles"] == 0, pl
