"""Each driver end to end on the CPU at ``GPTConfig.tiny()`` sizes
(kernels interpreted), skipping only the harness's look for a chip —
and the command itself, which must refuse to print a result without one.
Also the two faults ``correct`` is there to catch, planted underneath
the timed path."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmark", "tests", "data")
SEED = 2 ** 31 + 4242


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _driver(name):
    from benchmark import run as R
    return R.load_module("drivers", name)


def test_the_command_prints_no_result_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2-124m.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 2
    assert "not a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("traffic,metrics", [
    ("tiny-backlog.json", {"serve_tok_s"}),
    ("tiny-open.json", {"ttft_p95_ms", "itl_p95_ms"}),
])
def test_serving_driver_rehearsal(traffic, metrics):
    tr = _load(traffic)
    res = _driver(tr["driver"]).run(_load("tiny-config.json"), tr, SEED, 3.0,
                                    False)
    assert res["correct"] is True
    assert set(res["end_to_end"]) == metrics
    assert all(v > 0 for v in res["end_to_end"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["setup_s"] > 0
    # float32 on the CPU: the engine picks the reference's own tokens
    assert res["readings"]["check"]["mean_gap"] < 1e-3


def test_training_driver_rehearsal():
    tr = _load("tiny-train.json")
    res = _driver("train").run(_load("tiny-config.json"), tr, SEED, 2.0, False)
    assert res["correct"] is True
    assert res["end_to_end"]["train_tok_s"] > 0
    assert res["readings"]["compiles"]["registry"] == 0
    assert res["readings"]["check"]["loss_gap_max"] < 0.05


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """Break the timed path underneath: every token the scheduler fetches
    from the device is shifted by one."""
    from paddle_tpu.serving import scheduler
    real = scheduler._fetch

    def shifted(device_array):
        toks = real(device_array).copy()
        n = len(toks) - 1                      # last entry: finite sentinel
        toks[:n] = (toks[:n] + 1) % 256
        return toks

    monkeypatch.setattr(scheduler, "_fetch", shifted)
    tr = _load("tiny-backlog.json")
    res = _driver(tr["driver"]).run(_load("tiny-config.json"), tr, SEED, 3.0,
                                    False)
    assert res["correct"] is False
    assert res["readings"]["check"]["mean_gap"] > \
        _load("tiny-config.json")["serving"]["check"]["limits"]["mean_gap"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    """Break the timed path underneath: the optimizer's update is the
    identity, so every step returns the parameters it was given."""
    from paddle_tpu.optimizer import optimizer as O

    def no_update(self, p, g, slots, lr, step):
        return p, {"moment1": slots["moment1"], "moment2": slots["moment2"]}

    monkeypatch.setattr(O.AdamW, "_rule", no_update)
    tr = _load("tiny-train.json")
    res = _driver("train").run(_load("tiny-config.json"), tr, SEED, 1.0, False)
    assert res["correct"] is False
    assert res["readings"]["check"]["update_norm_gap"] > 0.9


def test_the_fp8_control_fails_the_training_limits():
    """The control at a size a test can hold: the reference with every
    linear layer in fp8 (e4m3 forward, e5m2 gradients), put in the
    program's place, comes out as not correct."""
    from benchmark.lib import correct as C
    from benchmark.lib import train_check as TC
    cfg = _load("tiny-config.json")
    drv = _driver("train")
    batches = drv.token_batches(SEED, 3, 4, 32, 256)
    ref = TC.reference_steps(SEED, cfg["model"], cfg["training"], batches, 2)
    control = TC.reference_steps(SEED, cfg["model"], cfg["training"], batches,
                                 2, quant="fp8")
    ok, _ = C.verdict(drv.compare(control, ref),
                      cfg["training"]["check"]["limits"])
    assert not ok
