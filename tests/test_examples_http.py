"""The examples/ scripts must keep running end to end (they are the
migration-facing quickstarts; reference analog: the book tests under
python/paddle/fluid/tests/book/): the ops surface and the HTTP front door,
over real sockets."""
import os

from _examples import REPO, run as _run


def test_ops_surface_example(tmp_path):
    """The PR-16 ops quickstart: the SLO series come back over real
    HTTP, health answers 200 live and 503 once the engine closes, and
    tracez carries the tail-sampled traces + burn rates + goodput."""
    out = _run([os.path.join(REPO, "examples", "ops_surface.py")],
               tmp_path)
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
               tmp_path)
    assert "front door live at http://127.0.0.1:" in out
    assert "POST /v1/completions beside GET /metrics" in out
    assert "served 4 interactive (SSE) + 4 batch requests over HTTP" in out
    assert "tenant 'starved': 3 requests shed with 429" in out
    assert "Retry-After" in out
    assert "wire ttft[alice]" in out
    assert "wire ttft[bulk-corp]" in out
    assert "engine tenants[alice]" in out
    assert "shed per tenant {'starved': 3}" in out
