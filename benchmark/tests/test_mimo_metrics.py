"""The arithmetic of MiMo-V2-Flash's two attention kernels, the four new
readers on a recorded cycle list with a hand-made trace reduction, and
what they say of a program that stamps no window counters (the parent):
nothing, without raising."""
import json
import os

import pytest

from benchmark import run as RUN
from benchmark.lib import kernel_costs_mimo as KM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "mimo-v2-flash-ep16.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = ("global_attention_roofline", "window_attention_roofline",
       "window_attn_ms", "kv_bytes_per_live_token")


def _reader(name):
    return RUN.load_module("layer_metrics", name).read


def test_the_costs_are_the_configurations_arithmetic():
    assert (KM.layers_of(MODEL, False), KM.layers_of(MODEL, True)) == (2, 5)
    assert KM.kv_bytes_per_token(MODEL, False, 2) == 2560
    assert KM.kv_bytes_per_token(MODEL, True, 2) == 5120
    assert KM.attention_read_bytes(1, MODEL, False, 2) == 2 * 2560
    assert KM.attention_read_bytes(1, MODEL, True, 2) == 5 * 5120
    assert KM.attention_flops(1, MODEL, False) == 64 * 320 * 2 * 2
    assert KM.attention_flops(1, MODEL, True) == 64 * 320 * 2 * 5
    # held uniformly a token costs 36,864 B as stored (384 lanes)
    from benchmark.lib import family_mimo as F
    assert F.stored_block_bytes(MODEL, CONFIG["serving"]) == 16 * 36864


def _readings():
    # two launches of 128 decode rows at 4,800 tokens of context each, the
    # second beside a 1,024-row chunk at position 2,000
    plain = dict(kv_tokens=128 * 4800, kv_row_tokens=128 * 4800,
                 kv_tokens_window=128 * 128, kv_row_tokens_window=128 * 128,
                 window_blocks_freed=8, kv_live_bytes=128 * 4800 * 7000,
                 kv_live_tokens=128 * 4800)
    rows = 1024 * 2000 + 1024 * 1025 // 2
    chunk = dict(kv_tokens=128 * 4800 + 3024,
                 kv_row_tokens=128 * 4800 + rows,
                 kv_tokens_window=128 * 128 + 127 + 1024,
                 kv_row_tokens_window=128 * 128 + 1024 * 128,
                 window_blocks_freed=70, kv_live_bytes=128 * 4800 * 7200,
                 kv_live_tokens=128 * 4800 + 1024)
    return {"trace_cycles": [plain, chunk], "model": MODEL,
            "serving": CONFIG["serving"], "device_kind": "TPU v5 lite",
            "trace": {"ops": {"ragged_paged_attention": 0.030,
                              "ragged_paged_attention_window": 0.004,
                              "kv_append": 0.001,
                              "fusion bf16[1024,4096]": 0.5}}}


def test_the_readers_on_a_recorded_cycle_list():
    r = _readings()
    from benchmark.lib import peaks as P
    peaks = P.peaks_for("TPU v5 lite")
    g_bytes = (2 * 128 * 4800 + 3024) * 2560 * 2 / peaks["hbm_bytes_per_s"]
    assert _reader("global_attention_roofline")(r) == pytest.approx(
        100 * g_bytes / 0.030)
    w_bytes = (2 * 128 * 128 + 1151) * 5120 * 5 / peaks["hbm_bytes_per_s"]
    w_flops = (2 * 128 * 128 + 1024 * 128) * 64 * 320 * 2 * 5 \
        / peaks["bf16_flops_per_s"]
    assert _reader("window_attention_roofline")(r) == pytest.approx(
        100 * max(w_bytes, w_flops) / 0.004)
    assert _reader("window_attn_ms")(r) == pytest.approx(2.0)
    assert _reader("kv_bytes_per_live_token")(r) == pytest.approx(
        (128 * 4800 * 14200) / (2 * 128 * 4800 + 1024))
    for name in ("global_attention_roofline", "window_attention_roofline"):
        assert 0 < _reader(name)(r) <= 100


def test_the_window_kernels_time_is_not_the_global_kernels():
    ops = _readings()["trace"]["ops"]
    assert KM.kernel_seconds(ops, False) == 0.030
    assert KM.kernel_seconds(ops, True) == 0.004


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_nothing(name):
    """The parent (and every one-group model): cycle records without the
    window keys, a trace without the window kernel."""
    r = _readings()
    r["trace_cycles"] = [{"kv_tokens": 100, "kv_row_tokens": 100}]
    r["trace"]["ops"].pop("ragged_paged_attention_window")
    assert _reader(name)(r) is None
    assert _reader(name)({"trace_cycles": []}) is None
    assert _reader(name)({}) is None


def test_the_cell_lists_what_its_readers_find():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "mimo-v2-flash-ep16.decode"
    mine = {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", ())}
    assert set(NEW) <= mine
    # one head kind, query-head bytes, stretch arithmetic of one group:
    # not what their names say here
    assert not mine & {"ragged_paged_attention_roofline", "kv_read_gbs",
                       "chunk_step_ms", "gqa_attention_roofline",
                       "kv_append_ms", "kv_blocks_per_fetch"}
    [w] = [w for w in bench["workloads"] if w["name"] == cell]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "mimo-v2-flash-ep16", "long-backlog-s128", 1)
    assert bench["workloads"][-1]["name"] == cell
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)
