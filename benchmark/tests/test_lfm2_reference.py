"""The plain reference of LFM2-MoE against itself: its int8 control at a
size a test can hold, the expert cap against every row through every
expert, the head applied in blocks against the head applied whole, what a
sequence leaves behind, and what the reference is NOT: it imports nothing
of the program."""
import json
import os
import re

import numpy as np

from benchmark.lib import correct as C
from benchmark.lib import family_lfm2 as F
from benchmark.lib import reference_lfm2 as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "tests", "data",
                       "tiny-lfm2-config.json")) as f:
    CFG = json.load(f)
MODEL = CFG["model"]


def _case():
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 256, size=(2, 64)).astype(np.int32)
    pos = np.tile(np.arange(32, 63), (2, 1))
    return ids, pos, F.Weights(7, MODEL, "float32")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "lib",
                           "reference_lfm2.py")) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", text, re.M)
    assert not [m for m in imports
                if "paddle_tpu" in m or "ops" in m or "axk1" in m]
    assert 'default_matmul_precision("highest")' in text
    code = text.split('"""', 2)[2]
    assert "ragged_dot" not in code and "conv_rows" not in code
    assert "jnp.pad(" in code            # the convolution: shifted products


def test_its_own_first_choices_have_no_gap_and_the_head_in_blocks_agrees():
    ids, pos, make = _case()
    plain = R.logits(make, MODEL, ids)
    served = plain.argmax(-1)[:, 32:63]
    out = R.served_margins(make, MODEL, ids, pos, served, rows_per_call=2)
    assert float(out["gap"].max()) == 0.0
    assert R.head_blocks(65536) == 8 and R.head_blocks(256) == 1
    np.testing.assert_allclose(out["std"], plain[:, 32:63].std(-1),
                               rtol=1e-4)
    np.testing.assert_array_equal(out["argmax"], served)
    np.testing.assert_allclose(out["logits_top"], plain[:, 32:63].max(-1),
                               rtol=1e-5, atol=1e-5)


def test_a_cap_that_overflows_is_doubled_until_it_holds():
    """An expert applied to the rows that chose it (``cap_share``) gives
    what every row through every expert gives — also where the first cap
    is too small for the fullest expert and the layer is repeated."""
    ids, _, make = _case()
    whole = R.hidden_states(make, MODEL, ids, rows_per_call=2)[0]
    for share in (0.5, 0.02):          # 0.02: 2 rows of 128, far too few
        capped = R.hidden_states(make, MODEL, ids, rows_per_call=2,
                                 cap_share=share)[0]
        np.testing.assert_allclose(capped, whole, atol=2e-5)
    blocked = R.hidden_states(make, MODEL, ids, rows_per_call=2,
                              q_block=16)[0]
    np.testing.assert_allclose(blocked, whole, atol=2e-5)


def test_the_int8_control_moves_the_logits_far_more_than_the_program_does():
    """The program is the reference's own logits to 1e-4 at this size
    (``tests/test_lfm2.py``). The int8 control moves them by a thousand
    times that and picks tokens the reference ranks lower: over the toy
    limits."""
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 256, size=(4, 128)).astype(np.int32)
    pos = np.tile(np.arange(16, 127), (4, 1))
    make = F.Weights(7, MODEL, "float32")
    plain = R.logits(make, MODEL, ids)
    served = plain.argmax(-1)[:, 16:127]
    out = R.served_margins(make, MODEL, ids, pos, served, rows_per_call=4,
                           quant="int8")
    assert float(out["gap"].max()) == 0.0
    gaps = C.gap_summary((out["control_gap"] / out["std"]).reshape(-1))
    ok, _ = C.verdict(gaps, CFG["serving"]["check"]["limits"])
    assert not ok and gaps["not_argmax_share"] > 0.02
    moved = float(np.abs(R.logits(make, MODEL, ids, quant="int8")
                         - plain).max())
    assert moved > 5e-2


def test_what_a_sequence_leaves_behind_is_its_prefixes():
    """``final_states(ids, n)`` depends on the first ``n`` tokens only:
    one ``[2, hidden]`` tail a ``conv`` layer, in layer order."""
    ids, _, make = _case()
    a = R.final_states(make, MODEL, ids[0], 40)
    other = ids[0].copy()
    other[40:] = ids[1][40:]
    b = R.final_states(make, MODEL, other, 40)
    assert len(a) == 5 and all(t.shape == (2, 64) for t in a)
    for ta, tb in zip(a, b):
        np.testing.assert_array_equal(ta, tb)
    c = R.final_states(make, MODEL, ids[0], 41)
    # one token on: the tail's last row of n is its first row of n + 1
    np.testing.assert_array_equal(c[0][0], a[0][1])
    assert float(np.abs(c[0][1] - a[0][1]).max()) > 1e-3
    # before the sequence's start the convolution saw zeros
    first = R.final_states(make, MODEL, ids[0], 1)
    assert float(np.abs(first[0][0]).max()) == 0.0
