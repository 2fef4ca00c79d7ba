"""The new cell's driver end to end on the CPU at toy depth and widths
(``tiny-longcat-config.json``: 2 published layers = 4 sub-blocks, float32,
kernels interpreted, the pool given by ``pool_blocks``), as
``test_axk1_rehearsal.py`` does for A.X-K1 — and the fault ``correct`` is
there to catch, planted underneath the timed path."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmark", "tests", "data")
SEED = 2 ** 31 + 4646


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _run(seconds=3.0):
    from benchmark import run as R
    tr = _load("tiny-longcat-backlog.json")
    return R.load_module("drivers", tr["driver"]).run(
        _load("tiny-longcat-config.json"), tr, SEED, seconds, False)


def test_the_family_driver_rehearsal():
    res = _run()
    assert res["correct"] is True
    assert set(res["end_to_end"]) == {"serve_tok_s"}
    assert res["end_to_end"]["serve_tok_s"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["setup_s"] > 0
    # float32 on the CPU: the engine picks the reference's own tokens
    assert res["readings"]["check"]["mean_gap"] < 1e-3


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.serving import scheduler
    real = scheduler._fetch

    def shifted(device_array):
        toks = real(device_array).copy()
        toks[:4] = (toks[:4] + 1) % 256          # the four slots' tokens
        return toks

    monkeypatch.setattr(scheduler, "_fetch", shifted)
    res = _run()
    assert res["correct"] is False
    assert res["readings"]["check"]["mean_gap"] > \
        _load("tiny-longcat-config.json")["serving"]["check"]["limits"][
            "mean_gap"]


def test_the_int8_control_fails_the_toy_limits():
    """The control at a size a test can hold: the reference with every
    linear layer in int8 picks tokens the float32 reference ranks clearly
    lower."""
    import numpy as np
    from benchmark.lib import correct as C
    from benchmark.lib import family_longcat as F
    from benchmark.lib import reference_longcat as R
    cfg = _load("tiny-longcat-config.json")
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 256, size=(2, 64)).astype(np.int32)
    pos = np.tile(np.arange(32, 63), (2, 1))
    make = F.Weights(7, cfg["model"], "float32")
    plain = R.logits(make, cfg["model"], ids)
    served = plain.argmax(-1)[:, 32:63]          # the reference's own choice
    out = R.served_margins(make, cfg["model"], ids, pos, served,
                           rows_per_call=2, quant="int8", q_block=16)
    assert float(out["gap"].max()) == 0.0
    numbers = C.gap_summary((out["control_gap"] / out["std"]).reshape(-1))
    ok, _ = C.verdict(numbers, cfg["serving"]["check"]["limits"])
    assert not ok
