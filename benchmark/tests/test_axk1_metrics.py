"""The arithmetic of A.X-K1's two kernel-sized pieces, the four new
readers on a recorded cycle list with a hand-made trace reduction, and the
scope look-up of ``lib/scope_ops.py``."""
import json
import os

import pytest

from benchmark import run as RUN
from benchmark.lib import kernel_costs_axk1 as KA
from benchmark.lib import scope_ops as SO

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "axk1-ep16.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]


def _reader(name):
    return RUN.load_module("layer_metrics", name).read


def test_the_costs_are_the_configurations_arithmetic():
    assert KA.latent_bytes_per_token(MODEL, 2) == 1152
    assert KA.mla_read_bytes(1, MODEL, 2) == 8064            # 7 layers
    assert KA.mla_flops(1, MODEL) == 7 * 139264              # 139 kFLOP a pair
    assert KA.expert_params(MODEL) == 44040192               # 44.04 M
    assert KA.held_expert_layers(MODEL) == (12, 6)
    assert KA.moe_flops(64, MODEL) == 64 * 2 * 44040192
    assert KA.moe_bytes(12, 64, MODEL, 2) == \
        12 * 44040192 * 2 + 64 * 2 * 7168 * 2
    # the configuration file's own byte arithmetic
    attn = 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    expert_layer = attn + 13 * 44040192 + 192 * 7168
    dense_layer = attn + 3 * 7168 * 18432
    total = dense_layer + 6 * expert_layer + 2 * 20480 * 7168
    assert round(attn / 1e6, 1) == 101.1
    assert round(expert_layer / 1e6, 0) == 675
    assert round(total / 1e9, 2) == 4.84


def _readings():
    # two launches: 128 decode rows at 2,500 tokens of context each, and
    # the same beside a 1,024-row chunk at position 1,000
    plain = dict(kv_tokens=128 * 2500, kv_row_tokens=128 * 2500,
                 moe_pairs=6 * 64, moe_experts_hit=6 * 11, moe_rows=6 * 128)
    rows = 1024 * 1000 + 1024 * 1025 // 2
    chunk = dict(kv_tokens=128 * 2500 + 2024,
                 kv_row_tokens=128 * 2500 + rows,
                 moe_pairs=6 * 576, moe_experts_hit=6 * 12,
                 moe_rows=6 * 1152)
    return {"trace_cycles": [plain, chunk], "model": MODEL,
            "serving": CONFIG["serving"], "device_kind": "TPU v5 lite",
            "trace": {"ops": {"mla_paged_attention": 0.040,
                              "ragged-dot-none": 0.030,
                              "fusion bf16[1024,7168]": 0.5}}}


def test_the_readers_on_a_recorded_cycle_list():
    r = _readings()
    assert _reader("moe_tokens_per_expert")(r) == \
        pytest.approx((64 + 576) * 6 / (12 * 6 * 2))          # 5.3 and 48
    by_bytes = (2 * 128 * 2500 + 2024) * 8064 / 819e9
    by_flops = (2 * 128 * 2500 + 1024 * 1000 + 1024 * 1025 // 2) \
        * 7 * 139264 / 197e12
    assert by_flops > by_bytes                # the chunk's products decide
    assert _reader("mla_attention_roofline")(r) == \
        pytest.approx(100 * by_flops / 0.040)
    moe_bytes = (66 + 72) * 44040192 * 2 + 6 * 640 * 2 * 7168 * 2
    assert _reader("moe_experts_roofline")(r) == \
        pytest.approx(100 * moe_bytes / 819e9 / 0.030)
    shares = [_reader(n)(r) for n in ("mla_attention_roofline",
                                      "moe_experts_roofline")]
    assert all(0 < s < 100 for s in shares)


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the counters (the parent), or an untraced run."""
    old = {"trace_cycles": [{"kv_tokens": 5, "active": 3}], "model": MODEL,
           "serving": CONFIG["serving"], "device_kind": "TPU v5 lite",
           "trace": {"ops": {"ragged_paged_attention": 1.0}}}
    for name in ("mla_attention_roofline", "moe_experts_roofline",
                 "moe_step_ms", "moe_tokens_per_expert"):
        assert _reader(name)(old) is None
        assert _reader(name)({}) is None


TEXT = '''
HloModule jit_fn
  %fusion.7 = bf16[1024,7168]{1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kLoop, calls=%f.7, metadata={op_name="jit(fn)/jit(main)/moe_experts/mul" source_file="x.py"}
  %sort.3 = (s32[8192]{0}, s32[8192]{0}) sort(%k, %v), dimensions={0}, metadata={op_name="jit(fn)/jit(main)/moe_experts/sort"}
  %fusion.9 = bf16[1024,7168]{1,0:T(8,128)(2,1)} fusion(%c), kind=kLoop, calls=%f.9, metadata={op_name="jit(fn)/jit(main)/rms_norm/mul"}
  ROOT %while.2 = (s32[], f32[1024,7168]{1,0}) while(%t), condition=%c.2, body=%b.2, metadata={op_name="jit(fn)/jit(main)/moe_experts/while"}
'''


def test_scope_keys_and_the_scope_s_own_time():
    keys = SO.scope_keys(TEXT, "moe_experts")
    assert keys == {"%fusion.7 = bf16[1024,7168] fusion",
                    "%sort.3 = (s32[8192], s32[8192]) sort",
                    "%while.2 = (s32[], f32[1024,7168]) while"}
    # a trace event's name is the instruction's text, layouts and all
    ev = "%fusion.7 = bf16[1024,7168]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} %a), kind=kLoop"
    assert SO.key_of(ev) in keys
    k7, k9, kw = (SO.key_of(ev), "%fusion.9 = bf16[1024,7168] fusion",
                  "%while.2 = (s32[], f32[1024,7168]) while")
    rd = "%ragged-dot-none = f32[128,2048] custom-call"
    events = [(0, 100, k9), (100, 1100, kw), (200, 500, rd), (600, 700, k7),
              (2000, 2300, k7)]
    # the while's own 600 ns + fusion.7's 400: the ragged-dot nested in
    # the while is not the scope's by name
    assert SO.scope_seconds({"/device:TPU:0": events}, keys) == \
        pytest.approx(1000e-9)
    assert SO.scope_seconds({"/device:TPU:0": events}, keys | {rd}) == \
        pytest.approx(1300e-9)
    assert SO.scope_seconds({}, keys) == 0.0
