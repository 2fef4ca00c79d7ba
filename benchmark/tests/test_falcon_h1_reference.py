"""The plain reference of Falcon-H1 against itself: its two controls (every
linear layer W8A8; the recurrent state rounded to bfloat16 after every
step) at a size a test can hold, the head applied in blocks against the
head applied whole, and what the reference is NOT: it imports nothing of
the program."""
import json
import os
import re

import numpy as np

from benchmark.lib import correct as C
from benchmark.lib import family_falcon_h1 as F
from benchmark.lib import reference_falcon_h1 as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "tests", "data",
                       "tiny-falcon-h1-config.json")) as f:
    CFG = json.load(f)
MODEL = CFG["model"]


def _case():
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 256, size=(2, 64)).astype(np.int32)
    pos = np.tile(np.arange(32, 63), (2, 1))
    return ids, pos, F.Weights(7, MODEL, "float32")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "lib",
                           "reference_falcon_h1.py")) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", text, re.M)
    assert not [m for m in imports if "paddle_tpu" in m or "ops" in m]
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan" in text            # the recurrence, a token at a time


def test_its_own_first_choices_have_no_gap_and_the_head_in_blocks_agrees():
    ids, pos, make = _case()
    plain = R.logits(make, MODEL, ids)
    served = plain.argmax(-1)[:, 32:63]
    out = R.served_margins(make, MODEL, ids, pos, served, rows_per_call=2)
    assert float(out["gap"].max()) == 0.0
    assert R.head_blocks(261120) == 8 and R.head_blocks(256) == 1
    np.testing.assert_allclose(out["std"], plain[:, 32:63].std(-1),
                               rtol=1e-4)
    np.testing.assert_array_equal(out["argmax"], served)
    np.testing.assert_allclose(out["logits_top"], plain[:, 32:63].max(-1),
                               rtol=1e-5, atol=1e-5)


def test_the_two_controls_move_the_logits_far_more_than_the_program_does():
    """The program is the reference's own logits to 1e-4 at this size
    (``tests/test_falcon_h1.py``). The int8 control moves them by a
    thousand times that and picks tokens the reference ranks lower: over
    the toy limits. The bfloat16 state — the smaller change, on a state of
    4 heads x 16 x 32 — moves them by a hundred times the program's, which
    at this size flips no first choice: its gap in tokens is measured at
    the published widths, on the chip (PERF.md)."""
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 256, size=(4, 128)).astype(np.int32)
    pos = np.tile(np.arange(16, 127), (4, 1))
    make = F.Weights(7, MODEL, "float32")
    plain = R.logits(make, MODEL, ids)
    served = plain.argmax(-1)[:, 16:127]
    moved, gaps = {}, {}
    for quant in (R.INT8, R.BF16_STATE):
        out = R.served_margins(make, MODEL, ids, pos, served,
                               rows_per_call=4, quant=quant)
        assert float(out["gap"].max()) == 0.0
        gaps[quant] = C.gap_summary(
            (out["control_gap"] / out["std"]).reshape(-1))
        moved[quant] = float(np.abs(
            R.logits(make, MODEL, ids, quant=quant) - plain).max())
    ok, _ = C.verdict(gaps[R.INT8], CFG["serving"]["check"]["limits"])
    assert not ok and gaps[R.INT8]["not_argmax_share"] > 0.02
    assert moved[R.INT8] > 5e-2 > moved[R.BF16_STATE] > 5e-3
    assert gaps[R.INT8]["mean_gap"] > gaps[R.BF16_STATE]["mean_gap"] >= 0


def test_what_a_sequence_leaves_behind_is_its_prefixes():
    """``final_states(ids, n)`` depends on the first ``n`` tokens only."""
    ids, _, make = _case()
    a = R.final_states(make, MODEL, ids[0], 40)
    other = ids[0].copy()
    other[40:] = ids[1][40:]
    b = R.final_states(make, MODEL, other, 40)
    for (ta, ha), (tb, hb) in zip(a, b):
        np.testing.assert_array_equal(ta, tb)
        np.testing.assert_array_equal(ha, hb)
    assert a[0][0].shape == (3, 192) and a[0][1].shape == (4, 16, 32)
    c = R.final_states(make, MODEL, ids[0], 41)
    assert float(np.abs(c[0][1] - a[0][1]).max()) > 1e-3
