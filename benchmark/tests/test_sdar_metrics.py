"""The ``sdar-30b-a3b-pp8`` configuration's arithmetic, the new cost
functions and the new readers, on recorded numbers (no chip, no trace)."""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-pp8.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalogs_with_the_depth_cut():
    cfg = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    differing = [k for k, v in row["config"].items() if cfg[k] != v]
    assert differing == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 6
    m = cfg["model"]
    for ours, theirs in (("n_routed_experts", "num_experts"),
                         ("hidden_size", "hidden_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("num_key_value_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("vocab_size", "vocab_size"),
                         ("num_experts_per_tok", "num_experts_per_tok")):
        assert m[ours] == row["config"][theirs]
    assert m["experts_held"] == [0, 128] and m["first_k_dense_replace"] == 0


def test_the_costs_are_the_configurations_arithmetic():
    from benchmark.lib import kernel_costs_axk1 as KA
    from benchmark.lib import kernel_costs_sdar as KS
    m = _config()["model"]
    # a cached token: 4 KV heads x (128 K + 128 V) x 2 B a layer
    assert KS.kv_bytes_per_token(m, 2) == 2048
    assert KS.gqa_read_bytes(1000, m, 2) == 1000 * 2048 * 6
    # a (row, token) pair: 32 query heads x 512 FLOP a layer
    assert KS.gqa_flops(1, m) == 32 * 512 * 6
    # the shared expert-layer costs read right on this model's keys
    assert KA.expert_params(m) == 3 * 2048 * 768
    assert KA.held_expert_layers(m) == (128, 6)
    layer = 2048 * 4096 * 2 + 2048 * 512 * 2 + 256 + 2 * 2048 \
        + 128 * 2048 + 128 * KA.expert_params(m)
    total = 6 * layer + 2 * 151936 * 2048 + 2048
    assert abs(total * 2 / 1e9 - 8.72) < 0.01            # GB in bf16


def _readings(cycles):
    cfg = _config()
    return {"trace_cycles": cycles, "model": cfg["model"],
            "serving": cfg["serving"], "device_kind": "TPU v5 lite",
            "trace": {"ops": {"ragged_paged_attention.1": 0.010,
                              "fusion.3": 0.5}}}


def test_the_readers_on_a_recorded_cycle_list():
    # two plain launches of 128 slots: 102 denoising slot-passes and 26
    # commits each, 1,400 tokens of context a slot
    cyc = {"kv_tokens": 128 * 1400, "kv_row_tokens": 512 * 1400,
           "denoise_slots": 102, "commit_slots": 26, "emitted": 102,
           "tokens_fixed": 102}
    r = _readings([cyc, dict(cyc)])
    assert abs(_reader("tokens_per_pass")(r) - 102 / 128) < 1e-9
    by_bytes = 2 * 128 * 1400 * 2048 * 6 / 819e9
    by_flops = 2 * 512 * 1400 * 32 * 512 * 6 / 197e12
    assert by_bytes > by_flops
    share = _reader("gqa_attention_roofline")(r)
    assert abs(share - 100 * by_bytes / 0.010) < 1e-6 and share < 100


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """A program that has no passes, no grouped heads and no ``unmask``
    scope — the parent's, or another cell's — leaves the metric out."""
    gpt = {"trace_cycles": [{"kv_tokens": 5, "kv_row_tokens": 5, "emitted": 3}],
           "model": {"num_attention_heads": 20, "hidden_size": 1280},
           "serving": {"dtype": "bfloat16"}, "device_kind": "TPU v5 lite",
           "trace": {"ops": {"ragged_paged_attention": 0.01}},
           "scope_keys": {}}
    for name in ("tokens_per_pass", "gqa_attention_roofline",
                 "unmask_step_ms"):
        assert _reader(name)(gpt) is None
        assert _reader(name)({}) is None
