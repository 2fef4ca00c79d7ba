"""The new cell's driver end to end on the CPU at toy depth and widths
(``tiny-nemotron-h-config.json``: blocks ``MEM*EM``, float32, kernels
interpreted, the pool given by ``pool_blocks``), as
``test_longcat_rehearsal.py`` does for LongCat-Flash — and the fault
``correct`` is there to catch, planted underneath the timed path."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmark", "tests", "data")
SEED = 2 ** 31 + 5050


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _run(seconds=6.0):
    from benchmark import run as R
    tr = _load("tiny-nemotron-h-backlog.json")
    return R.load_module("drivers", tr["driver"]).run(
        _load("tiny-nemotron-h-config.json"), tr, SEED, seconds, False)


def test_the_family_driver_rehearsal():
    res = _run()
    assert res["correct"] is True
    assert set(res["end_to_end"]) == {"serve_tok_s"}
    assert res["end_to_end"]["serve_tok_s"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["setup_s"] > 0
    # float32 on the CPU: the engine picks the reference's own tokens
    assert res["readings"]["check"]["mean_gap"] < 1e-3
    # the engine the window ran on held 3 state layers of the 6 blocks
    state = res["readings"]["engine_stats"]["state"]
    assert state["layers"] == 3 and state["slot_bytes"] == 3 * 10496


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
        _load("tiny-nemotron-h-config.json")["serving"]["check"]["limits"][
            "mean_gap"]
