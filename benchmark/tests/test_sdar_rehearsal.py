"""The block-generation cell's driver end to end on the CPU at toy depth
and widths (``SDARConfig.tiny()`` sizes, float32, kernels interpreted, the
pool given by ``pool_blocks``), as ``test_axk1_rehearsal.py`` does for
A.X-K1 — and the faults ``correct`` is there to catch, planted underneath
the timed path."""
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmark", "tests", "data")
SEED = 2 ** 31 + 4343


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _run(seconds=6.0):
    from benchmark import run as R
    tr = _load("tiny-sdar-backlog.json")
    return R.load_module("drivers", tr["driver"]).run(
        _load("tiny-sdar-config.json"), tr, SEED, seconds, False)


def test_the_blocks_driver_rehearsal():
    res = _run()
    assert res["correct"] is True
    assert set(res["end_to_end"]) == {"serve_tok_s"}
    assert res["end_to_end"]["serve_tok_s"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["setup_s"] > 0
    # every finished record found its trace: the order reached the check
    done = [r for r in res["readings"]["records"] if r["done"] is not None]
    assert done and all(len(r["passes"]) == len(r["tokens"]) for r in done)
    # float32 on the CPU: the engine serves the reference's own tokens, in
    # the reference's own order
    check = res["readings"]["check"]
    assert check["mean_gap"] < 1e-3 and check["mean_order_gap"] < 1e-3
    assert check["passes"] >= check["tokens"] > 0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.serving import scheduler
    real = scheduler._fetch

    def shifted(device_array):
        toks = real(device_array).copy()
        state = toks[-2 * 4 * 4:-4 * 4]          # [slots * B] token ids
        state[:] = (state + 1) % 250 + 1
        return toks

    monkeypatch.setattr(scheduler, "_fetch", shifted)
    res = _run()
    assert res["correct"] is False
    assert res["readings"]["check"]["mean_gap"] > \
        _load("tiny-sdar-config.json")["serving"]["check"]["limits"]["mean_gap"]


def test_a_pass_recorded_out_of_order_is_not_correct(monkeypatch):
    """The tokens are the program's own, the ORDER is not: the passes of
    every block are reversed where the trace keeps them. The states the
    reference is then shown are not the ones that produced the tokens."""
    from paddle_tpu.serving import tracing
    real = tracing.RequestTrace.stamp_token

    def reversed_pass(self, t, token=None, fixed_pass=None):
        real(self, t, token, None if fixed_pass is None else 3 - fixed_pass)

    monkeypatch.setattr(tracing.RequestTrace, "stamp_token", reversed_pass)
    res = _run()
    assert res["correct"] is False


def test_the_int8_control_fails_the_toy_limits():
    """The control at a size a test can hold: the reference with every
    linear layer in int8, run on the same states, serves tokens the
    float32 reference ranks clearly lower."""
    from benchmark.drivers import serve_backlog_blocks as D
    from benchmark.lib import correct as C
    from benchmark.lib import family_sdar as F
    from benchmark.lib import reference_sdar as R
    cfg = _load("tiny-sdar-config.json")
    make = F.Weights(7, cfg["model"], "float32")
    rng = np.random.default_rng(3)
    requests = []
    for p, n in ((9, 20), (14, 18), (7, 21)):
        prompt = rng.integers(1, 250, size=p).tolist()
        own = R.generate(make, cfg["model"], prompt, n)
        requests.append((prompt, own["tokens"], own["passes"]))
    out = R.served_margins(make, cfg["model"], requests, width=64, states=32,
                           q_block=16, states_per_call=8, quant="int8")
    assert float(out["gap"].max()) == 0.0 and float(out["order_gap"].max()) == 0.0
    numbers = D.summary(out["control_gap"] / out["std"],
                        out["control_order_gap"])
    ok, _ = C.verdict(numbers, cfg["serving"]["check"]["limits"])
    assert not ok
