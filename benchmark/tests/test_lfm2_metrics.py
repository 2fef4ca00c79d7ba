"""The arithmetic of LFM2-MoE's costs, the two new readers on synthetic
readings (launch records beside a hand-made table of device time by
section and by kernel), and what they say of a program that stamps no
``cache_layers`` and names no mixer section (the parent): nothing, without
raising."""
import json
import os

import pytest

from benchmark import run as RUN
from benchmark.lib import family_lfm2 as F
from benchmark.lib import kernel_costs_lfm2 as KL
from benchmark.lib import kernel_costs_sdar as KS
from benchmark.lib import peaks as P

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2-24b-a2b-pp4.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = ("hybrid_attention_roofline", "short_conv_roofline")
CELL = "lfm2-24b-a2b-pp4.decode"
CONV_PARAMS = 2048 * 6144 + 3 * 2048 + 2048 * 2048      # 16.78 M


def _reader(name):
    return RUN.load_module("layer_metrics", name).read


def test_the_costs_are_the_configurations_arithmetic():
    assert KL.conv_layers(MODEL) == 8
    assert KL.conv_operator_params(MODEL) == CONV_PARAMS == 16_783_360
    assert KL.kv_bytes_per_token(MODEL, 2) == 2048
    assert KL.attention_read_bytes(1000, 2, MODEL, 2) == 1000 * 4096
    assert KL.attention_flops(10, 2, MODEL) == 10 * 32 * 64 * 4 * 2
    assert KL.short_conv_bytes(100, MODEL, 2) == 8 * (
        CONV_PARAMS * 2 + 100 * 2 * 2048 * 2)
    assert KL.short_conv_flops(100, MODEL) == 100 * 8 * 2 * CONV_PARAMS
    assert F.cache_layers(MODEL) == 2
    assert F.state_bytes_per_slot(MODEL) == 8 * 2 * 2048 * 4 == 131_072
    # the file's own arithmetic: every published width unchanged
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "conv_L_cache": 3,
                 "intermediate_size": 11776, "moe_intermediate_size": 1536,
                 "num_experts": 64, "num_experts_per_tok": 4,
                 "num_dense_layers": 2, "use_expert_bias": True,
                 "norm_topk_prob": True, "vocab_size": 65536,
                 "norm_eps": 1e-5, "conv_bias": False,
                 "routed_scaling_factor": 1}
    for holder in (CONFIG, MODEL):
        assert {k: holder[k] for k in published} == published
        assert holder["num_hidden_layers"] == 10
    assert CONFIG["rope_parameters"]["rope_theta"] == MODEL["rope_theta"] \
        == 1_000_000
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert len(CONFIG["layer_types"]) == 40          # whole, as published
    assert MODEL["layer_types"] == CONFIG["layer_types"][:10] == [
        "conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    # the names the shared readers take say what the source's say
    assert MODEL["n_routed_experts"] == MODEL["num_experts"]
    assert MODEL["experts_held"] == [0, 64]
    assert MODEL["first_k_dense_replace"] == MODEL["num_dense_layers"]
    assert MODEL["head_dim"] * MODEL["num_attention_heads"] \
        == MODEL["hidden_size"]
    assert MODEL["state_dtype"] == "float32"
    # the catalog's every key is in the file as published
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if '"LFM2-24B-A2B"' in line)
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == {"num_hidden_layers"}
        assert CONFIG["source"] == row["source_url"]


def _readings():
    # two matched launches, each 127 decode rows beside a 1,024-row chunk
    a = dict(kv_tokens=600_000, kv_row_tokens=5_000_000, cache_layers=2,
             ssm_rows=1151, state_slots=128, ssm_chunk_rows=1024)
    b = dict(kv_tokens=640_000, kv_row_tokens=7_000_000, cache_layers=2,
             ssm_rows=1150, state_slots=127, ssm_chunk_rows=1024)
    ms = 1_000_000
    return {"trace_cycles": [a, b], "model": MODEL,
            "serving": CONFIG["serving"], "device_kind": "TPU v5 lite",
            "trace": {"ops": {"ragged_paged_attention": 0.016,
                              "ragged-dot-none": 0.040}},
            "launch_trace": {
                "launches": {7: (0, 40 * ms, "jit_fused_step_q2048_t512(1)"),
                             8: (41 * ms, 82 * ms,
                                 "jit_fused_step_q2048_t512(1)")},
                "records": {7: a, 8: b},
                "sections": {7: {"ssm_conv": 1 * ms, "ssm_proj": 3 * ms,
                                 "mlp": 2 * ms},
                             8: {"ssm_conv": 1 * ms, "ssm_proj": 3 * ms,
                                 "mlp": 2 * ms}},
                "busy": {7: 39 * ms, 8: 40 * ms}, "gaps": [],
                "gap_idle": []}}


def test_the_readers_on_synthetic_readings():
    r = _readings()
    peaks = P.peaks_for("TPU v5 lite")
    # attention: bytes 1.24 M tokens x 4,096 B = 6.2 ms at 819 GB/s; FLOPs
    # 12 M pairs x 32 x 64 x 4 x 2 = 1.0 ms: memory-bound
    by_bytes = 1_240_000 * 4096 / peaks["hbm_bytes_per_s"]
    by_flops = 12_000_000 * 32 * 64 * 4 * 2 / peaks["bf16_flops_per_s"]
    assert by_bytes > by_flops
    got = _reader("hybrid_attention_roofline")(r)
    assert got == pytest.approx(100 * by_bytes / 0.016)
    assert 0 < got <= 100
    # a fifth of what gqa_attention_roofline's formula reads here: it
    # multiplies by num_hidden_layers 10, two layers hold a cache
    gqa = 100 * sum(KS.gqa_read_bytes(c["kv_tokens"], MODEL, 2)
                    for c in r["trace_cycles"]) \
        / peaks["hbm_bytes_per_s"] / 0.016
    assert got == pytest.approx(gqa / 5)
    assert gqa > 100                      # why the cell is not on its list
    # the conv operators: FLOPs 2,301 rows x 8 x 2 x 16.78 M = 3.1 ms at
    # 197 TFLOP/s; bytes 2 x 8 x 33.6 MB + rows = 0.75 ms: compute-bound
    flops = KL.short_conv_flops(2301, MODEL) / peaks["bf16_flops_per_s"]
    bts = (KL.short_conv_bytes(1151, MODEL, 2)
           + KL.short_conv_bytes(1150, MODEL, 2)) / peaks["hbm_bytes_per_s"]
    assert flops > bts
    got = _reader("short_conv_roofline")(r)
    assert got == pytest.approx(100 * flops / 0.008)
    assert 0 < got <= 100


def test_neither_roofline_can_pass_100_whatever_the_records_say():
    """At the roofline itself each share reads 100: the least work takes
    at least its time at the chip's peaks, whatever implements it."""
    r = _readings()
    peaks = P.peaks_for("TPU v5 lite")
    r["trace"]["ops"]["ragged_paged_attention"] = \
        1_240_000 * 4096 / peaks["hbm_bytes_per_s"]
    assert _reader("hybrid_attention_roofline")(r) == pytest.approx(100.0)
    least = KL.short_conv_flops(2301, MODEL) / peaks["bf16_flops_per_s"]
    for n in (7, 8):
        r["launch_trace"]["sections"][n] = {
            "ssm_proj": int(least / 2 * 1e9), "mlp": 1}
    assert _reader("short_conv_roofline")(r) == pytest.approx(100.0,
                                                              rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_cache_less_layers_reads_nothing(name):
    """The parent (and every model whose every layer holds a cache):
    launch records without ``cache_layers`` and the mixer's keys, no
    mixer section among the sections."""
    r = _readings()
    for rec in r["trace_cycles"]:
        for k in ("cache_layers", "ssm_rows", "state_slots",
                  "ssm_chunk_rows"):
            rec.pop(k)
    for by in r["launch_trace"]["sections"].values():
        for k in ("ssm_conv", "ssm_proj"):
            by.pop(k)
    assert _reader(name)(r) is None
    r["launch_trace"] = None                         # no slice at all
    r.pop("trace")
    assert _reader(name)(r) is None
    assert _reader(name)({"trace_cycles": []}) is None
    assert _reader(name)({}) is None


def test_falcon_h1s_readings_are_not_read_as_a_short_convolution():
    """A program WITH mixer sections and ``ssm_rows`` whose configuration
    has no ``conv_L_cache`` (``falcon-h1-34b-pp12``): nothing."""
    r = _readings()
    r["model"] = {k: v for k, v in MODEL.items() if k != "conv_L_cache"}
    assert _reader("short_conv_roofline")(r) is None


def test_the_cell_lists_what_its_readers_find():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine
    assert {"moe_experts_roofline", "moe_step_ms", "moe_tokens_per_expert",
            "section_ms.ssm_conv", "section_ms.ssm_proj",
            "section_ms.router", "section_ms.mlp",
            "state_bytes_per_live_token", "wide_q_block_share",
            "launch_device_ms.chunk", "tower_row_fill.chunk",
            "section_unplaced_share", "hbm_peak_gb.serve",
            "compiles.serve"} <= mine
    # the readers that price a cache in EVERY layer, the plain-launch
    # readers (every launch carries a chunk), and what the model has not
    assert not mine & {"gqa_attention_roofline", "kv_append_ms",
                       "kv_blocks_per_fetch", "kv_read_gbs",
                       "decode_step_ms", "q_row_fill", "tower_row_fill",
                       "launch_device_ms.plain", "section_ms.ssm_scan",
                       "ssm_scan_roofline", "mla_attention_roofline",
                       "window_attn_ms", "tokens_per_pass",
                       "kv_bytes_per_live_token"}
    [w] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "lfm2-24b-a2b-pp4", "long-backlog-s128", 1)
    assert bench["workloads"][-1] is w and bench["configs"][-1]["name"] \
        == "lfm2-24b-a2b-pp4"
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW)
    assert all("workloads" in m for m in bench["per_layer"])
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["hybrid_attention_roofline"] == "kernels"
    assert layers["short_conv_roofline"] == "state-space mixer"
    [e] = [m for m in bench["end_to_end"] if m["name"] == "serve_tok_s"]
    assert CELL in e["workloads"]


def test_the_traffic_is_mimos_file_and_the_configuration_fits_it():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "long-backlog-s128.json")) as f:
        tr = json.load(f)
    assert (tr["driver"], tr["slots"], tr["clients"]) == (
        "serve_backlog_family", 128, 128)
    assert tr["prompt_tokens"] == [2048, 8192]
    assert tr["output_tokens"] == [256, 1024]
    serving = CONFIG["serving"]
    assert tr["prompt_tokens"][1] + tr["output_tokens"][1] \
        == serving["max_len"] == serving["check"]["width"] == 9216
    assert serving["state_slots"] == tr["slots"]
    assert serving["prefill_budget"] == 1024 and serving["block_size"] == 16
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2-flash-ep16.json")) as f:
        mimo = json.load(f)["serving"]
    assert {k: serving[k] for k in ("max_len", "block_size",
                                    "prefill_budget")} == {
        k: mimo[k] for k in ("max_len", "block_size", "prefill_budget")}
