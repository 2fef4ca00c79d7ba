"""The examples/ scripts must keep running end to end (they are the
migration-facing quickstarts; reference analog: the book tests under
python/paddle/fluid/tests/book/): ``serve_gpt2.py`` plain, tensor-parallel, and
speculative over int8 blocks."""
import os

from _examples import REPO, run as _run


def test_serve_gpt2_example(tmp_path):
    out = _run([os.path.join(REPO, "examples", "serve_gpt2.py"),
                "--clients", "10", "--slots", "4", "--train-steps", "20"],
               tmp_path)
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
               tmp_path,
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
               tmp_path)
    assert "served 6 requests" in out
    assert "spec: accept rate" in out
    assert "tokens/cycle" in out
    assert "block capacity" in out
    assert "int8 blocks" in out
    assert "same budget at fp32" in out
