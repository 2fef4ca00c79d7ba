"""The comparison that decides ``correct``: on hand-made cases, against
its control (the reference in the next lower precision) and against a
timed path that is broken underneath."""
import json
import os

import pytest

from benchmark.lib import correct as C
from benchmark.lib import traffic as T

DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = json.load(open(os.path.join(DATA, "tiny-config.json")))
SEED = 2 ** 31 + 99


def _weights(dtype="float32"):
    from benchmark.lib import weights as W
    return W.make_weights(SEED, TINY["model"], dtype)


def _greedy_records(weights, n_req=4, prompt_len=12, n_new=10):
    """Requests whose tokens are the reference's own greedy choice."""
    import jax.numpy as jnp
    from benchmark.lib import reference_gpt2 as R
    heads = TINY["model"]["num_attention_heads"]
    records = []
    for i in range(n_req):
        text = T.prompt_tokens(SEED, i, prompt_len + i, 256)
        n0 = len(text)
        for _ in range(n_new):
            h = R.hidden_states(weights, jnp.asarray([text]), heads)
            text.append(int(jnp.argmax(R.logits_of(weights, h[:, -1]))))
        records.append({"index": i, "prompt_len": n0, "tokens": text[n0:],
                        "max_tokens": n_new, "done": 1.0, "finish": "length"})
    return records


@pytest.fixture(scope="module")
def greedy():
    w = _weights()
    return w, _greedy_records(w)


def _numbers(w, records, quant=None):
    got = C.served_gaps(w, TINY["model"]["num_attention_heads"], records,
                        SEED, 256, width=64, rows_per_call=2, quant=quant)
    return got


def test_the_references_own_tokens_have_gap_zero(greedy):
    w, records = greedy
    numbers = C.gap_summary(_numbers(w, records)["gaps"])
    assert numbers["tokens"] == 40
    assert numbers["mean_gap"] == 0.0 and numbers["max_gap"] == 0.0
    ok, _ = C.verdict(numbers, {"mean_gap": 0.01, "max_gap": 0.5})
    assert ok


def test_a_planted_wrong_token_is_over_the_gross_limit(greedy):
    w, records = greedy
    bad = [dict(r, tokens=list(r["tokens"])) for r in records]
    bad[1]["tokens"][4] = (bad[1]["tokens"][4] + 97) % 256
    numbers = C.gap_summary(_numbers(w, bad)["gaps"])
    ok, lines = C.verdict(numbers, {"mean_gap": 0.01, "max_gap": 0.5})
    assert not ok and numbers["max_gap"] > 0.5
    assert any("FAILED" in line for line in lines)


def test_the_int8_control_comes_out_as_not_correct(greedy):
    """The control at a size a test can hold: the token the int8
    reference puts first, read in the float32 reference's logits."""
    w, records = greedy
    got = _numbers(w, records, quant="int8")
    control = C.gap_summary(got["control_gaps"])
    program = C.gap_summary(got["gaps"])
    assert program["mean_gap"] == 0.0
    assert control["mean_gap"] > 0.0
    ok, _ = C.verdict(control, {"mean_gap": control["mean_gap"] / 3,
                                "max_gap": 10.0})
    assert not ok


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    recs = [{"index": i, "prompt_len": 10 + i % 7, "tokens": [1] * (3 + i % 5)}
            for i in range(30)]
    a = C.pick_sample(recs, 5, 8)
    assert a == C.pick_sample(recs, 5, 8) and len(a) == 8
    longest = max(recs, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    assert a[0]["prompt_len"] + len(a[0]["tokens"]) == \
        longest["prompt_len"] + len(longest["tokens"])
    assert a != C.pick_sample(recs, 6, 8)
    assert C.pick_sample([], 5, 8) == []


def test_window_requests_must_be_whole_and_inside_the_vocabulary():
    good = {"index": 0, "max_tokens": 3, "tokens": [1, 2, 3], "done": 1.0,
            "finish": "length"}
    assert C.window_requests_ok([good], 256) == (True, [])
    short = dict(good, tokens=[1, 2])
    outside = dict(good, tokens=[1, 2, 256])
    unfinished = dict(good, tokens=[1], done=None)       # cut by the window
    assert not C.window_requests_ok([short], 256)[0]
    assert not C.window_requests_ok([outside], 256)[0]
    assert C.window_requests_ok([unfinished], 256)[0]


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert C.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 1e-9}, ref) == \
        pytest.approx(0.1)
    # an all-but-zero leaf is measured against the median leaf, not itself
    assert C.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.01}, ref) == \
        pytest.approx(0.01, rel=1e-3)
    assert C.live_leaves({"a": 1.0, "b": 2.0, "c": 1e-9}) == ["a", "b"]
