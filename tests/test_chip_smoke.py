"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at
``GPTConfig.tiny()`` (kernels interpreted, four of the eight virtual
devices for the multi-chip phases) — wrong paths, arguments and control
flow are found here, at no chip time. What only the chip can show (Mosaic
lowering, HBM, the kernels inside the compiled steps) the phases check
themselves when jax reports a TPU. ``main()`` must refuse anything else.
"""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig  # noqa: E402


def test_train_phase():
    out = chip_smoke.phase_train(GPTConfig.tiny(), batch=2, seq=16, steps=3)
    assert len(out["losses"]) == 3 and out["losses"][-1] < out["losses"][0]


def test_eager_phase():
    out = chip_smoke.phase_eager(GPTConfig.tiny(), batch=1, seq=8)
    assert out["losses"][-1] < out["losses"][0]


def test_serve_phase():
    out = chip_smoke.phase_serve(
        GPTConfig.tiny(), max_len=32, block_size=8, num_slots=2,
        num_blocks=16, prompt_lens=(8,), prefix=8, tail=6,
        burst_lens=(6, 7), new_tokens=2)
    assert out["parity"]["exact"] >= 1 and out["prefix_hits"] >= 2


def test_tp_serve_phase_on_four_virtual_devices():
    out = chip_smoke.phase_tp_serve(
        GPTConfig.tiny(), jax.devices()[:4], max_len=32, block_size=8,
        num_slots=2, num_blocks=16, prompt_lens=(6, 7), new_tokens=2)
    assert out["parity"]["exact"] >= 1


def test_zero_train_phase_on_four_virtual_devices():
    out = chip_smoke.phase_zero_train(
        GPTConfig.tiny(), jax.devices()[:4], batch=4, seq=16, steps=2)
    assert len(out["zero_losses"]) == 2


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_main_refuses_a_process_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "not a TPU" in captured.err


def test_check_greedy_accepts_near_ties_only():
    import numpy as np
    top = np.arange(64).reshape(2, 32)
    zero = np.zeros((2, 32), np.float32)
    assert chip_smoke.check_greedy(top, top, zero, "t")["exact"] == 2
    # one near-tie pick, early in a sequence: fine wherever it stands
    served, margin = top.copy(), zero.copy()
    served[1, 2], margin[1, 2] = 999, chip_smoke.NEAR_TIE / 2
    out = chip_smoke.check_greedy(served, top, margin, "t")
    assert out["exact"] == 1 and len(out["near_ties"]) == 1
    # a token the reference would not pick
    margin[1, 2] = chip_smoke.NEAR_TIE * 2
    with pytest.raises(AssertionError, match="not the reference's greedy"):
        chip_smoke.check_greedy(served, top, margin, "t")
    # too many near-ties, and no sequence left exact
    many, m = top.copy(), zero.copy()
    many[1, :5], m[1, :5] = 999, 0.001
    with pytest.raises(AssertionError, match="more than one in twenty"):
        chip_smoke.check_greedy(many, top, m, "t")
    both, m = top.copy(), zero.copy()
    both[:, 0], m[:, 0] = 999, 0.001
    with pytest.raises(AssertionError, match="no sequence matches"):
        chip_smoke.check_greedy(both, top, m, "t")


def test_axk1_serve_phase():
    """The latent-attention / routed-expert phase at toy widths."""
    import json
    data = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests", "data",
        "tiny-axk1-config.json")
    with open(data) as f:
        cfg = json.load(f)
    out = chip_smoke.phase_axk1_serve(
        cfg["model"], dtype="float32", max_len=64, block_size=8,
        num_slots=2, num_blocks=16, prefill_budget=16,
        prompt_lens=(5, 21), new_tokens=4,
        limits=cfg["serving"]["check"]["limits"], width=32, q_block=16)
    assert out["tokens"] == 8 and out["max_gap"] < 1e-3


def test_mimo_serve_phase():
    """The window-and-global phase at toy widths: both cache groups, a
    context of five windows."""
    import json
    data = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests", "data",
        "tiny-mimo-config.json")
    with open(data) as f:
        cfg = json.load(f)
    out = chip_smoke.phase_mimo_serve(
        cfg["model"], dtype="float32", max_len=64, block_size=8,
        num_slots=2, num_blocks=16, prefill_budget=16,
        prompt_lens=(5, 34), new_tokens=6,
        limits=cfg["serving"]["check"]["limits"], width=48, q_block=16)
    assert out["tokens"] == 12 and out["max_gap"] < 1e-3


def test_sdar_serve_phase():
    """The grouped-head / block-generation phase at toy widths."""
    import json
    data = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests", "data",
        "tiny-sdar-config.json")
    with open(data) as f:
        cfg = json.load(f)
    out = chip_smoke.phase_sdar_serve(
        cfg["model"], dtype="float32", max_len=64, block_size=8,
        num_slots=2, num_blocks=16, prefill_budget=16,
        prompt_lens=(5, 22), new_tokens=10,
        limits=cfg["serving"]["check"]["limits"], width=32, states=16,
        q_block=16)
    # the first request's last block (positions 12-15) ends in a surplus
    # position: 5 + 10 = 15; the second's text ends on a block boundary
    assert out["tokens"] == 7 + 10 and out["passes"] == 17
    assert out["max_gap"] < 1e-3 and out["max_order_gap"] < 1e-3

