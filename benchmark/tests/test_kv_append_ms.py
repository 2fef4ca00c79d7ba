"""``kv_append_ms`` on a small recorded ``ops`` table: the kernel's own
seconds over the slice's launches, and nothing for a program that still
appends with XLA's scatter (what the parent's trace holds)."""
import pytest

from benchmark import run as R

MODEL = {"model": {"num_hidden_layers": 36, "num_attention_heads": 20,
                   "hidden_size": 1280},
         "serving": {"block_size": 16, "dtype": "bfloat16"}}

# 8 s of gpt2-large.decode, the first ops of the slice
OPS = {"kv_append": 0.120, "ragged_paged_attention": 1.195,
       "fusion bf16[512,5120]": 0.210, "copy bf16[20,512,64]": 0.004}
PARENT_OPS = {"fusion bf16[50964480,128]": 3.271,
              "ragged_paged_attention": 1.195}

CYCLES = [
    {"cycle": 1, "kv_write_blocks": 64, "launch_rows": 64},
    # a 456-row chunk from position 37 beside 63 decode rows
    {"cycle": 2, "kv_write_blocks": 63 + 29, "launch_rows": 519},
    {"cycle": 3, "kv_write_blocks": 64, "launch_rows": 64},
    # a cycle that launched nothing
    {"cycle": 4},
]


def read(readings):
    return R.load_module("layer_metrics", "kv_append_ms").read(readings)


def test_ms_a_launch_over_the_launches_of_the_slice(capsys):
    r = dict(MODEL, trace={"ops": OPS}, trace_cycles=CYCLES)
    assert read(r) == pytest.approx(1e3 * 0.120 / 3)
    # 220 blocks of 36 x 80 KB, read and written, in 0.12 s
    rate = 2 * 220 * 36 * 20 * 16 * 128 * 2 / 0.120 / 1e9
    assert f"{rate:.1f} GB/s" in capsys.readouterr().out
    # without the configuration there is no rate to log, the time stands
    assert read({"trace": {"ops": OPS}, "trace_cycles": CYCLES}) == \
        pytest.approx(40.0)


@pytest.mark.parametrize("readings", [
    dict(MODEL, trace={"ops": PARENT_OPS},
         trace_cycles=[{"cycle": 1, "launch_rows": 64}]),
    dict(MODEL, trace={"ops": PARENT_OPS}, trace_cycles=CYCLES),
    dict(MODEL, trace={"ops": OPS},
         trace_cycles=[{"cycle": 1, "launch_rows": 64}]),
    dict(MODEL, trace_cycles=CYCLES),
    {},
], ids=["parent", "scatter-ops", "no-counter", "no-trace", "empty"])
def test_a_program_without_the_kernel_reads_nothing(readings):
    assert read(readings) is None
