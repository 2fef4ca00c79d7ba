"""The new cell's driver end to end on the CPU at toy depth and widths
(``tiny-lfm2-config.json``: two leading ``conv`` layers and one whole
period, five layers of six without a cache; float32, kernels interpreted,
the pool given by ``pool_blocks``) — and the fault ``correct`` is there to
catch, planted underneath the timed path."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmark", "tests", "data")
SEED = 2 ** 31 + 4242


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _run(seconds=6.0):
    from benchmark import run as R
    tr = _load("tiny-lfm2-backlog.json")
    return R.load_module("drivers", tr["driver"]).run(
        _load("tiny-lfm2-config.json"), tr, SEED, seconds, False)


def test_the_family_driver_rehearsal():
    res = _run()
    assert res["correct"] is True
    assert set(res["end_to_end"]) == {"serve_tok_s"}
    assert res["end_to_end"]["serve_tok_s"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["setup_s"] > 0
    # float32 on the CPU: the engine picks the reference's own tokens
    assert res["readings"]["check"]["mean_gap"] < 1e-3
    st = res["readings"]["engine_stats"]
    assert st["state"]["layers"] == 5
    assert st["state"]["parts"] == {"conv": [2, 64]}
    assert st["kv_bytes"]["state"] == 5 * st["state"]["slot_bytes"]
    # blocks for ONE layer of six
    assert st["kv_bytes"]["blocks"] == (st["num_blocks"] + 1) * 2 * 8 * 16 * 4
    assert st["prefix_hits"] == 0 and st["cached_blocks"] == 0
    assert st["nonfinite_cycles"] == 0


def test_a_tail_left_uncleared_between_requests_is_not_correct(monkeypatch):
    """The fault the mechanism invites: a sequence that starts at position
    0 from what its slot's previous owner left in the tail (no zero in
    the program). The second generation of requests then reads a foreign
    tail in its first two rows, and the comparison says so."""
    from paddle_tpu.ops import ssm
    real = ssm.seq_layout

    def never_fresh(*a, **kw):
        lay = real(*a, **kw)
        return lay._replace(seq_fresh=lay.seq_fresh & False)

    monkeypatch.setattr(ssm, "seq_layout", never_fresh)
    res = _run()
    assert res["correct"] is False
    assert res["readings"]["check"]["mean_gap"] > \
        _load("tiny-lfm2-config.json")["serving"]["check"]["limits"][
            "mean_gap"]
