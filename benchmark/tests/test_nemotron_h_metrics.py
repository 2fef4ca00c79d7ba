"""The arithmetic of Nemotron-3-Super's configuration file, the three new
readers on a hand-made record (and that no share passes 100% at the
published shapes), the existing readers on the new ``model`` group, and
the readers' silence where there is nothing to read."""
import json
import os

import pytest

from benchmark import run as RUN
from benchmark.lib import kernel_costs_axk1 as KA
from benchmark.lib import kernel_costs_lfm2 as KL
from benchmark.lib import kernel_costs_nemotron_h as KN

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3-super-ep4.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
CELL = "nemotron3-super-ep4.decode"


def _reader(name):
    return RUN.load_module("layer_metrics", name).read


def test_the_costs_are_the_configurations_arithmetic():
    assert KN.expert_params(MODEL) == 2 * 1024 * 2688 == 5_505_024
    assert KN.state_bytes(MODEL) == 4_194_304
    assert KA.held_expert_layers(MODEL) == (128, 5)
    assert KL.kv_bytes_per_token(MODEL, 2) == 1024
    # a plain launch: 128 rows x 22 choices, a quarter of them held
    assert KN.latent_moe_bytes(5 * 128, 5 * 704, MODEL, 2) == \
        5 * 128 * 5_505_024 * 2 + 5 * 704 * 2 * 1024 * 2
    assert KN.latent_moe_flops(5 * 704, MODEL) == 5 * 704 * 2 * 5_505_024
    # A.X-K1's functions would price it 6.7x over: three matrices of the
    # hidden width a pair
    assert KA.expert_params(MODEL) == 6 * KN.expert_params(MODEL)
    row = (2 * 8192 + 2 * 1024 + 128) * 4
    assert KN.scan_bytes(128, 128, 5, MODEL) == \
        5 * (128 * 2 * 4_194_304 + 128 * row)
    assert KN.scan_flops(128, 5, MODEL) == 128 * 128 * 64 * 128 * 5.0 * 5
    # the configuration file's own byte arithmetic
    m_block = 4096 * 18560 + 8192 * 4096 + 10240 * 5 + 8192 + 3 * 128 + 4096
    a_block = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    e_block = 512 * 4096 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        + 128 * 5_505_024 + 4096
    total = 5 * m_block + a_block + 5 * e_block + 2 * 32768 * 4096 + 4096
    assert round(m_block / 1e6, 2) == 109.64
    assert round(a_block / 1e6, 2) == 35.66
    assert round(e_block / 1e6, 2) == 759.17
    assert round(total / 1e6, 1) == 4648.2
    assert round(2 * total / 1e9, 2) == 9.30
    slot = 5 * (4_194_304 + 3 * 10240 * 4)
    assert slot == 21_585_920 and round(129 * slot / 1e9, 3) == 2.785
    s = CONFIG["serving"]
    assert s["pool_blocks_max"] == s["state_slots"] * s["max_len"] \
        // s["block_size"] + s["prefill_budget"] // s["block_size"]


def test_every_width_is_the_catalog_rows():
    """The file's top level against the catalog row: every number under
    the same key, but the three ``reduced``; the ``model`` group's widths
    are the row's."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CONFIG["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if CONFIG.get(k) != v)
    assert differs == sorted(CONFIG["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (11, 128, 32768)
    c = row["config"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
                "n_routed_experts", "num_experts_per_tok",
                "moe_intermediate_size", "moe_latent_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "hybrid_override_pattern",
                "layer_norm_epsilon"):
        assert MODEL[key] == c[key], key
    assert MODEL["experts_held"] == [0, 128]
    held = c["hybrid_override_pattern"][:11]
    assert held == "MEMEMEM*EME"
    # one period in the published ratio
    assert [held.count(k) * 8 for k in "ME*"] == \
        [c["hybrid_override_pattern"].count(k) for k in "ME*"] == [40, 40, 8]
    assert MODEL["num_hidden_layers"] - MODEL["first_k_dense_replace"] \
        == held.count("E")


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3-super-ep4", "decode-heavy-backlog-s128", 1)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"latent_moe_roofline", "mamba_scan_roofline",
            "section_ms.latent_proj", "hybrid_attention_roofline",
            "moe_row_fill", "moe_tokens_per_expert", "section_ms.ssm_scan",
            "state_bytes_per_live_token"} <= mine
    # the readers that would price this model wrongly, or read nothing
    assert not mine & {"moe_experts_roofline", "ssm_scan_roofline",
                       "gqa_attention_roofline", "kv_append_ms",
                       "kv_blocks_per_fetch", "kv_read_gbs",
                       "section_ms.mlp"}
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        for key in ("why", "layer"):
            assert len(m.get(key, "")) <= 200
    assert len(cell["why"]) <= 200


def _readings():
    """Two launches at the published shapes: 128 decode rows at 1,200
    tokens of context each (every held expert of the five blocks hit, 704
    pairs a block), and the same beside a 640-row chunk at position 0."""
    plain = dict(cycle=1, kv_tokens=128 * 1200, kv_row_tokens=128 * 1200,
                 cache_layers=1, state_layers=5, state_slots=128,
                 ssm_rows=128, ssm_chunk_rows=0, moe_pairs=5 * 704,
                 moe_experts_hit=5 * 127, moe_rows=5 * 128,
                 moe_rows_walked=5 * 127 * 16)
    rows = 640 * 641 // 2
    chunk = dict(cycle=2, kv_tokens=128 * 1200 + 640,
                 kv_row_tokens=128 * 1200 + rows, cache_layers=1,
                 state_layers=5, state_slots=129, ssm_rows=768,
                 ssm_chunk_rows=640, moe_pairs=5 * 4224,
                 moe_experts_hit=5 * 128, moe_rows=5 * 768,
                 moe_rows_walked=5 * 128 * 64)
    return {"trace_cycles": [plain, chunk], "cycles": [plain, chunk],
            "model": MODEL, "serving": CONFIG["serving"],
            "device_kind": "TPU v5 lite",
            "trace": {"ops": {"ragged_paged_attention": 0.004,
                              "ragged-dot-none": 0.024,
                              "fusion bf16[128,4096]": 0.5}}}


def _launch_trace(ssm_scan_ns, latent_ns=0):
    r = _readings()
    return {"records": {1: r["cycles"][0], 2: r["cycles"][1]},
            "launches": {1: None, 2: None},
            "sections": {1: {"ssm_scan": ssm_scan_ns,
                             "latent_proj": latent_ns},
                         2: {"ssm_scan": 2 * ssm_scan_ns,
                             "latent_proj": 3 * latent_ns}}}


def test_the_readers_on_a_hand_made_record(monkeypatch):
    from benchmark.lib import host_spans as HS
    from benchmark.lib import launch_trace as LT
    r = _readings()
    monkeypatch.setattr(HS, "slice_records", lambda r: r["cycles"])
    moe_bytes = 5 * (127 + 128) * 5_505_024 * 2 \
        + 5 * (704 + 4224) * 2 * 1024 * 2
    moe_flops = 5 * (704 + 4224) * 2.0 * 5_505_024
    assert moe_bytes / 819e9 > moe_flops / 197e12       # thin groups: bytes
    assert _reader("latent_moe_roofline")(r) == \
        pytest.approx(100 * moe_bytes / 819e9 / 0.024)
    # the gated reader on the same record: 6x the weights, over 100%
    assert _reader("moe_experts_roofline")(r) > 100
    assert _reader("moe_tokens_per_expert")(r) == \
        pytest.approx(5 * (704 + 4224) / (128 * 5 * 2))
    assert _reader("moe_row_fill")(r) == pytest.approx(
        100.0 * 5 * 4928 / (5 * 127 * 16 + 5 * 128 * 64))
    att_bytes = (2 * 128 * 1200 + 640) * 1024 / 819e9
    assert _reader("hybrid_attention_roofline")(r) == \
        pytest.approx(100 * att_bytes / 0.004)
    monkeypatch.setattr(LT, "launch_trace",
                        lambda r: _launch_trace(14_000_000, 200_000))
    row = (2 * 8192 + 2 * 1024 + 128) * 4
    scan = 5 * ((128 + 129) * 2 * 4_194_304 + (128 + 768) * row)
    assert _reader("mamba_scan_roofline")(r) == \
        pytest.approx(100 * scan / 819e9 / 0.042)
    assert _reader("section_ms.latent_proj")(r) == pytest.approx(0.4)
    assert all(0 < _reader(n)(r) < 100 for n in (
        "latent_moe_roofline", "mamba_scan_roofline",
        "hybrid_attention_roofline"))


def test_no_share_passes_100_at_the_published_shapes():
    """A plain launch timed AT the floor ISSUE 50 predicts against — its
    weights and state once at the HBM's rate — reads at most 100% on both
    new rooflines: the least bytes the readers count are no more than the
    bytes that floor moves."""
    plain = _readings()["cycles"][0]
    experts = KN.latent_moe_bytes(plain["moe_experts_hit"],
                                  plain["moe_pairs"], MODEL, 2)
    assert experts <= 5 * 128 * 5_505_024 * 2 + 5 * 704 * 4096
    assert KN.latent_moe_flops(plain["moe_pairs"], MODEL) / 197e12 \
        < experts / 819e9
    scan = KN.scan_bytes(plain["state_slots"], plain["ssm_rows"],
                         plain["state_layers"], MODEL)
    assert scan <= 128 * 2 * 21_585_920            # the whole state twice
    assert KN.scan_flops(plain["ssm_rows"], 5, MODEL) / 197e12 < scan / 819e9
    assert round((9.0e9 + 2 * 128 * 21_585_920) / 1e9, 1) == 14.5


def test_the_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """A program without the stamp or the sections (the parent), another
    family's model group, or an untraced run."""
    from benchmark.lib import launch_trace as LT
    r = _readings()
    for name in ("latent_moe_roofline", "mamba_scan_roofline",
                 "section_ms.latent_proj"):
        assert _reader(name)({}) is None
        assert _reader(name)({"model": MODEL}) is None
    other = dict(r, model={k: v for k, v in MODEL.items()
                           if k not in ("moe_latent_size", "ssm_state_size")})
    assert _reader("latent_moe_roofline")(other) is None
    no_pairs = dict(r, trace_cycles=[{k: v for k, v in c.items()
                                      if not k.startswith("moe_")}
                                     for c in r["cycles"]])
    assert _reader("latent_moe_roofline")(no_pairs) is None
    # every layer has a state (Falcon-H1), or the parent: no stamp
    unstamped = _launch_trace(14_000_000)
    unstamped["records"] = {n: {k: v for k, v in rec.items()
                                if k != "state_layers"}
                            for n, rec in unstamped["records"].items()}
    monkeypatch.setattr(LT, "launch_trace", lambda r: unstamped)
    assert _reader("mamba_scan_roofline")(r) is None
    monkeypatch.setattr(LT, "launch_trace", lambda r: _launch_trace(0))
    assert _reader("mamba_scan_roofline")(r) is None
    assert _reader("section_ms.latent_proj")(r) is None
    monkeypatch.setattr(LT, "launch_trace", lambda r: None)
    assert _reader("mamba_scan_roofline")(r) is None
