"""The examples/ scripts must keep running end to end (they are the
migration-facing quickstarts; reference analog: the book tests under
python/paddle/fluid/tests/book/)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, extra_env=None, timeout=420):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    proc = subprocess.run([sys.executable, *args], cwd=str(tmp_path),
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


@pytest.mark.parametrize("script,args,expect", [
    ("train_vision.py", ["--synthetic", "--epochs", "1",
                         "--batch-size", "16"], "saved vision_ckpt"),
    ("static_graph.py", [], "int8-sim max diff"),
])
def test_example_runs(script, args, expect, tmp_path):
    out = _run([os.path.join(REPO, "examples", script), *args], tmp_path)
    assert expect in out


def test_serve_example(tmp_path):
    _run([os.path.join(REPO, "examples", "serve_model.py"), "--export"],
         tmp_path)
    out = _run([os.path.join(REPO, "examples", "serve_model.py")],
               tmp_path)
    assert "16 concurrent requests" in out


def test_serve_gpt2_example(tmp_path):
    out = _run([os.path.join(REPO, "examples", "serve_gpt2.py"),
                "--clients", "10", "--slots", "4", "--train-steps", "20"],
               tmp_path, timeout=600)
    assert "served 10 requests" in out
    assert "aggregate" in out and "tokens/s" in out
    assert "ttft p50" in out
    assert "tpot p50" in out                 # per-engine decode cadence
    assert "engine.stats():" in out          # the operator snapshot
    assert "prefix hit ratio" in out         # the shared preamble's hits
    assert "prefill chunks" in out           # fed through the fused step


def test_serve_gpt2_example_mp(tmp_path):
    """--mp 2 routes through the TENSOR-PARALLEL engine
    (GenerationEngine(mesh=)), not just sharded per-request
    generation: the end-of-run report must carry the per-device pool
    stats line with 1/mp of the KV bytes on each device."""
    out = _run([os.path.join(REPO, "examples", "serve_gpt2.py"),
                "--clients", "6", "--slots", "4", "--train-steps", "20",
                "--mp", "2"],
               tmp_path, timeout=600,
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "served 6 requests" in out
    assert "serving tensor-parallel over 2 device(s)" in out
    assert "tensor-parallel: mp=2" in out
    assert "per-device KV pool" in out
    assert "1/2 of the single-device bytes" in out
    assert "prefix hit ratio" in out


def test_serve_gpt2_example_spec_int8(tmp_path):
    """--spec + --kv-dtype int8: speculative decoding over quantized
    KV blocks, with the accept-rate / tokens-per-cycle / block-capacity
    lines in the end-of-run report."""
    out = _run([os.path.join(REPO, "examples", "serve_gpt2.py"),
                "--clients", "6", "--slots", "4", "--train-steps", "20",
                "--spec", "--kv-dtype", "int8"],
               tmp_path, timeout=600)
    assert "served 6 requests" in out
    assert "spec: accept rate" in out
    assert "tokens/cycle" in out
    assert "block capacity" in out
    assert "int8 blocks" in out
    assert "same budget at fp32" in out


def test_ops_surface_example(tmp_path):
    """The PR-16 ops quickstart: the SLO series come back over real
    HTTP, health answers 200 live and 503 once the engine closes, and
    tracez carries the tail-sampled traces + burn rates + goodput."""
    out = _run([os.path.join(REPO, "examples", "ops_surface.py")],
               tmp_path, timeout=600)
    assert "ops server live at http://127.0.0.1:" in out
    assert "served 6 requests" in out
    assert "slo_attainment: live" in out
    assert "slo_burn_rate: live" in out
    assert "goodput_rps: live" in out
    assert "slo_latency_ms_bucket: live" in out
    assert "healthz: 200 ok" in out
    assert "tracez: 6 recent traces" in out
    assert "attainment 100.00%" in out
    assert "healthz after close: 503" in out


def test_serve_http_example(tmp_path):
    """The PR-19 front-door quickstart: mixed-tenant traffic over real
    sockets — SSE-streamed interactive lane beside non-streamed batch
    lane on one port, the rate-limited tenant shed with 429s, and the
    per-tenant TTFT / goodput split in the end-of-run report."""
    out = _run([os.path.join(REPO, "examples", "serve_http.py"),
                "--interactive", "4", "--batch", "4"],
               tmp_path, timeout=600)
    assert "front door live at http://127.0.0.1:" in out
    assert "POST /v1/completions beside GET /metrics" in out
    assert "served 4 interactive (SSE) + 4 batch requests over HTTP" in out
    assert "tenant 'starved': 3 requests shed with 429" in out
    assert "Retry-After" in out
    assert "wire ttft[alice]" in out
    assert "wire ttft[bulk-corp]" in out
    assert "engine tenants[alice]" in out
    assert "shed per tenant {'starved': 3}" in out


def test_generate_text_example(tmp_path):
    out = _run([os.path.join(REPO, "examples", "generate_text.py")],
               tmp_path, timeout=600)
    assert "ragged left-padded batch" in out
    assert "beam k=4" in out


def test_gpt2_sharded_example(tmp_path):
    out = _run([os.path.join(REPO, "examples", "train_gpt2_sharded.py"),
                "--dp", "4", "--mp", "2", "--tiny", "--steps", "2"],
               tmp_path,
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "step 1: loss" in out
