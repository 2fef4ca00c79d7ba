"""The arithmetic of the recurrence's costs, the five new readers on
synthetic readings (launch records beside a hand-made table of device time
by section), and what they say of a program that stamps no state counters
and names no mixer section (the parent): nothing, without raising."""
import json
import os

import pytest

from benchmark import run as RUN
from benchmark.lib import family_falcon_h1 as F
from benchmark.lib import kernel_costs_falcon_h1 as KF
from benchmark.lib import peaks as P

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "falcon-h1-34b-pp12.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = ("section_ms.ssm_scan", "section_ms.ssm_conv", "section_ms.ssm_proj",
       "ssm_scan_roofline", "state_bytes_per_live_token")
CELL = "falcon-h1-34b-pp12.decode"
SLOT = 6 * (4_194_304 + 61_440)                # state a slot, 6 layers


def _reader(name):
    return RUN.load_module("layer_metrics", name).read


def test_the_costs_are_the_configurations_arithmetic():
    assert KF.state_bytes(MODEL) == 32 * 128 * 256 * 4 == 4_194_304
    row = (2 * 4096 + 2 * 512 + 32) * 4
    assert KF.scan_bytes(64, 64, MODEL) == 6 * 64 * (2 * 4_194_304 + row)
    assert KF.scan_flops(1, MODEL) == 6 * 32 * 128 * 256 * 5
    assert F.state_bytes_per_slot(MODEL) == SLOT
    # the file's own arithmetic: every published width unchanged
    published = {"hidden_size": 5120, "num_attention_heads": 20,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "intermediate_size": 21504, "mamba_d_ssm": 4096,
                 "mamba_n_heads": 32, "mamba_d_head": 128,
                 "mamba_d_state": 256, "mamba_n_groups": 2,
                 "mamba_d_conv": 4, "mamba_chunk_size": 128,
                 "vocab_size": 261120}
    for holder in (CONFIG, MODEL):
        assert {k: holder[k] for k in published} == published
        assert holder["num_hidden_layers"] == 6
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    multipliers = ["embedding_multiplier", "lm_head_multiplier",
                   "attention_in_multiplier", "attention_out_multiplier",
                   "key_multiplier", "ssm_in_multiplier",
                   "ssm_out_multiplier"]
    assert all(MODEL[k] == CONFIG[k] for k in multipliers)
    assert len(MODEL["ssm_multipliers"]) == 5 \
        and len(MODEL["mlp_multipliers"]) == 2       # fourteen in all
    assert MODEL["state_dtype"] == "float32"


def _readings():
    # two matched launches: 64 decode rows; 63 decode rows beside a chunk
    # of 1,000 rows of one sequence
    plain = dict(state_slots=64, ssm_rows=64, ssm_chunk_rows=0,
                 state_live_bytes=64 * SLOT, kv_live_bytes=64 * 1100 * 12288,
                 kv_live_tokens=64 * 1100)
    chunk = dict(state_slots=64, ssm_rows=63 + 1000, ssm_chunk_rows=1000,
                 state_live_bytes=64 * SLOT, kv_live_bytes=64 * 1115 * 12288,
                 kv_live_tokens=64 * 1115)
    ms = 1_000_000
    return {"trace_cycles": [plain, chunk], "model": MODEL,
            "serving": CONFIG["serving"], "device_kind": "TPU v5 lite",
            "launch_trace": {
                "launches": {7: (0, 30 * ms, "jit_fused_step_q512_t128(1)"),
                             8: (31 * ms, 95 * ms,
                                 "jit_fused_step_q2048_t128(2)")},
                "records": {7: plain, 8: chunk},
                "sections": {7: {"ssm_scan": 6 * ms, "ssm_conv": 1 * ms,
                                 "ssm_proj": 3 * ms, "mlp": 12 * ms},
                             8: {"ssm_scan": 9 * ms, "ssm_conv": 2 * ms,
                                 "ssm_proj": 8 * ms, "mlp": 30 * ms}},
                "busy": {7: 29 * ms, 8: 60 * ms}, "gaps": [],
                "gap_idle": []}}


def test_the_readers_on_synthetic_readings():
    r = _readings()
    assert _reader("section_ms.ssm_scan")(r) == pytest.approx(7.5)
    assert _reader("section_ms.ssm_conv")(r) == pytest.approx(1.5)
    assert _reader("section_ms.ssm_proj")(r) == pytest.approx(5.5)
    peaks = P.peaks_for("TPU v5 lite")
    by_bytes = (KF.scan_bytes(64, 64, MODEL) + KF.scan_bytes(64, 1063, MODEL)
                ) / peaks["hbm_bytes_per_s"]
    by_flops = KF.scan_flops(64 + 1063, MODEL) / peaks["bf16_flops_per_s"]
    assert by_bytes > by_flops                       # memory-bound
    got = _reader("ssm_scan_roofline")(r)
    assert got == pytest.approx(100 * by_bytes / 0.015)
    assert 0 < got <= 100
    assert _reader("state_bytes_per_live_token")(r) == pytest.approx(
        (2 * 64 * SLOT + 64 * 2215 * 12288) / (64 * 2215))
    assert 30_000 < _reader("state_bytes_per_live_token")(r) < 40_000


def test_the_roofline_cannot_pass_100_whatever_the_records_say():
    """The least bytes the algorithm needs take at least their time at the
    chip's bandwidth: a section's device time under that would say the
    device moved bytes faster than it can, whatever implements the scan.
    At the roofline itself the share reads 100."""
    r = _readings()
    peaks = P.peaks_for("TPU v5 lite")
    least = (KF.scan_bytes(64, 64, MODEL) + KF.scan_bytes(64, 1063, MODEL)
             ) / peaks["hbm_bytes_per_s"]
    for n in (7, 8):
        r["launch_trace"]["sections"][n]["ssm_scan"] = int(least / 2 * 1e9)
    assert _reader("ssm_scan_roofline")(r) == pytest.approx(100.0, rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_mixer_reads_nothing(name):
    """The parent (and every model without state): launch records without
    the state keys, no mixer section among the sections."""
    r = _readings()
    for rec in r["trace_cycles"]:
        for k in ("state_slots", "ssm_rows", "ssm_chunk_rows",
                  "state_live_bytes"):
            rec.pop(k)
    for by in r["launch_trace"]["sections"].values():
        for k in ("ssm_scan", "ssm_conv", "ssm_proj"):
            by.pop(k)
    assert _reader(name)(r) is None
    r["launch_trace"] = None                         # no slice at all
    assert _reader(name)(r) is None
    assert _reader(name)({"trace_cycles": []}) is None
    assert _reader(name)({}) is None


def test_the_cell_lists_what_its_readers_find():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine
    assert {"gqa_attention_roofline", "kv_append_ms", "kv_blocks_per_fetch",
            "wide_q_block_share", "section_ms.mlp", "q_row_fill",
            "section_unplaced_share", "hbm_peak_gb.serve"} <= mine
    # no experts, no latent cache, no window, no blocks of diffusion
    assert not mine & {"moe_step_ms", "mla_attention_roofline",
                       "window_attn_ms", "tokens_per_pass",
                       "kv_bytes_per_live_token", "section_ms.router"}
    [w] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "falcon-h1-34b-pp12", "decode-heavy-backlog-s64", 1)
    assert all("workloads" in m for m in bench["per_layer"])
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert {layers[n] for n in NEW[:4]} == {"state-space mixer"}
    assert layers["state_bytes_per_live_token"] == "KV pool"
    [e] = [m for m in bench["end_to_end"] if m["name"] == "serve_tok_s"]
    assert CELL in e["workloads"]


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "decode-heavy-backlog-s64.json")) as f:
        tr = json.load(f)
    assert (tr["driver"], tr["slots"], tr["clients"]) == (
        "serve_backlog_family", 64, 64)
    assert tr["prompt_tokens"] == [256, 1024]
    assert tr["output_tokens"] == [512, 2048]
    assert (tr["plan_seed"], tr["lead_s"], tr["drain_s"]) == (0, 5.0, 3.0)
    assert (tr["trace_at_s"], tr["trace_slice_s"]) == (24.0, 8.0)
    serving = CONFIG["serving"]
    assert tr["prompt_tokens"][1] + tr["output_tokens"][1] \
        == serving["max_len"] == serving["check"]["width"]
    assert serving["state_slots"] == tr["slots"]
