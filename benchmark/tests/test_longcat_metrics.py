"""The arithmetic of LongCat-Flash's configuration file and its latent
caches, the four new readers on a recorded cycle list with a hand-made
trace reduction, and the existing expert-layer readers on the new
``model`` group."""
import json
import os

import pytest

from benchmark import run as RUN
from benchmark.lib import kernel_costs_axk1 as KA
from benchmark.lib import kernel_costs_longcat as KL

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "longcat-flash-ep32.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
CELL = "longcat-flash-ep32.decode"


def _reader(name):
    return RUN.load_module("layer_metrics", name).read


def test_the_costs_are_the_configurations_arithmetic():
    assert KL.attention_layers(MODEL) == 8
    assert KA.latent_bytes_per_token(MODEL, 2) == 1152
    assert KL.mla_read_bytes(1, MODEL, 2) == 9216            # 8 caches
    assert KL.mla_flops(1, MODEL) == 8 * 139264              # 139 kFLOP a pair
    assert KA.expert_params(MODEL) == 37748736               # 37.75 M
    assert KA.held_expert_layers(MODEL) == (16, 4)
    assert KA.moe_bytes(14, 64, MODEL, 2) == \
        14 * 37748736 * 2 + 64 * 2 * 6144 * 2
    # the configuration file's own byte arithmetic
    attn = 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144
    dense = 3 * 6144 * 12288
    layer = 2 * attn + 2 * dense + 768 * 6144 + 16 * 37748736
    total = 4 * layer + 2 * 16384 * 6144
    assert round(attn / 1e6, 2) == 90.57 and round(dense / 1e6, 2) == 226.49
    assert round(layer / 1e6, 1) == 1242.8
    assert round(total / 1e9, 2) == 5.17 and round(2 * total / 1e9, 2) == 10.35


def test_every_width_is_the_catalog_rows():
    """The file's top level against the catalog row: every number under
    the same key, but the three ``reduced``."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert CONFIG["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if CONFIG.get(k) != v)
    assert differs == sorted(CONFIG["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (4, 16, 16384)
    m = MODEL
    assert (m["hidden_size"], m["ffn_hidden_size"], m["moe_intermediate_size"],
            m["moe_topk"], m["zero_expert_num"], m["n_routed_experts"]) == (
        row["config"]["hidden_size"], row["config"]["ffn_hidden_size"],
        row["config"]["expert_ffn_hidden_size"], row["config"]["moe_topk"],
        row["config"]["zero_expert_num"],
        row["config"]["n_routed_experts"] + row["config"]["zero_expert_num"])


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-ep32", "decode-heavy-backlog-s128", 1)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"scmoe_attention_roofline", "zero_expert_share",
            "section_ms.zero_experts", "section_ms.shortcut",
            "moe_experts_roofline", "moe_row_fill"} <= mine
    assert "mla_attention_roofline" not in mine     # counts half the caches
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))


def _readings():
    # two launches: 128 decode rows at 1,200 tokens of context each, and
    # the same beside a 640-row chunk at position 0
    plain = dict(cycle=1, kv_tokens=128 * 1200, kv_row_tokens=128 * 1200,
                 moe_pairs=4 * 32, moe_experts_hit=4 * 14, moe_rows=4 * 128,
                 moe_rows_walked=4 * 14 * 8, moe_zero_pairs=4 * 512)
    rows = 640 * 641 // 2
    chunk = dict(cycle=2, kv_tokens=128 * 1200 + 640,
                 kv_row_tokens=128 * 1200 + rows, moe_pairs=4 * 190,
                 moe_experts_hit=4 * 16, moe_rows=4 * 768,
                 moe_rows_walked=4 * 16 * 16, moe_zero_pairs=4 * 3000)
    return {"trace_cycles": [plain, chunk], "cycles": [plain, chunk],
            "model": MODEL, "serving": CONFIG["serving"],
            "device_kind": "TPU v5 lite",
            "trace": {"ops": {"mla_paged_attention": 0.012,
                              "ragged-dot-none": 0.016,
                              "fusion bf16[128,6144]": 0.5}}}


def test_the_readers_on_a_recorded_cycle_list(monkeypatch):
    from benchmark.lib import host_spans as HS
    r = _readings()
    monkeypatch.setattr(HS, "slice_records", lambda r: r["cycles"])
    assert _reader("zero_expert_share")(r) == pytest.approx(
        100.0 * 4 * 3512 / (4 * 896 * 12))                   # 32.7
    by_bytes = (2 * 128 * 1200 + 640) * 9216 / 819e9
    by_flops = (2 * 128 * 1200 + 640 * 641 // 2) * 8 * 139264 / 197e12
    assert by_bytes > by_flops            # decode rows: the cache's bytes
    assert _reader("scmoe_attention_roofline")(r) == \
        pytest.approx(100 * by_bytes / 0.012)
    # A.X-K1's reader would count four caches of the eight
    assert _reader("mla_attention_roofline")(r) == \
        pytest.approx(50 * by_bytes / 0.012)
    moe_bytes = (56 + 64) * 37748736 * 2 + 4 * 222 * 2 * 6144 * 2
    assert _reader("moe_experts_roofline")(r) == \
        pytest.approx(100 * moe_bytes / 819e9 / 0.016)
    assert _reader("moe_tokens_per_expert")(r) == \
        pytest.approx(4 * 222 / (16 * 4 * 2))
    assert all(0 < _reader(n)(r) < 100 for n in (
        "scmoe_attention_roofline", "moe_experts_roofline",
        "zero_expert_share"))


def test_the_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """A program without the counter or the sections (the parent), another
    family's model group, or an untraced run."""
    from benchmark.lib import host_spans as HS
    monkeypatch.setattr(HS, "slice_records", lambda r: r.get("cycles", []))
    r = _readings()
    old = dict(r, cycles=[{k: v for k, v in c.items()
                           if k != "moe_zero_pairs"} for c in r["cycles"]])
    assert _reader("zero_expert_share")(old) is None
    other = dict(r, model={k: v for k, v in MODEL.items()
                           if k not in ("attention_layers", "zero_expert_num",
                                        "moe_topk")})
    assert _reader("zero_expert_share")(other) is None
    assert _reader("scmoe_attention_roofline")(other) is None
    for name in ("scmoe_attention_roofline", "zero_expert_share",
                 "section_ms.zero_experts", "section_ms.shortcut"):
        assert _reader(name)({}) is None
        assert _reader(name)({"model": MODEL}) is None


def test_a_fused_shortcut_reads_zero_and_an_unnamed_one_nothing(monkeypatch):
    """The add that closes the shortcut is fused into the closing
    sub-block's down projection: the compiled steps name the section, no
    trace event is its own — 0 ms, not nothing. A program that names no
    such section (the parent), or a trace whose sections were not read,
    gives ``None``; an event of the section's own is summed."""
    from benchmark.lib import launch_trace as LT
    read = _reader("section_ms.shortcut")
    times = {"mlp": 6.9}
    monkeypatch.setattr(LT, "section_ms",
                        lambda r, *names: sum(times.get(n, 0) for n in names)
                        or None)
    named = {"scope_keys": {"shortcut": ["%add.1 = f32[128,6144] add"]}}
    assert read(named) == 0.0
    assert read({"scope_keys": {"moe_experts": ["%x = f32[8] add"]}}) is None
    assert read({}) is None
    times["shortcut"] = 0.25
    assert read(named) == 0.25
    times.clear()                      # the trace named no section at all
    assert read(named) is None
