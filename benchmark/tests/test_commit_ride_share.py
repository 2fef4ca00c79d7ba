"""``commit_ride_share`` and, beside it, what ``tokens_per_pass`` reads
once commits ride: on recorded cycle lists (no chip, no trace)."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# a plain launch of 128 slots, a quarter of them opening a block
RIDING = {"denoise_slots": 128, "commit_slots": 0, "ride_slots": 26,
          "emitted": 127, "tokens_fixed": 128}
# the parent's record of such a launch: every commit alone, no such key
PARENT = {"denoise_slots": 102, "commit_slots": 26, "emitted": 102,
          "tokens_fixed": 102}


@pytest.mark.parametrize("cycles,want", [
    ([RIDING, dict(RIDING)], 100.0),                  # 26 rides, none alone
    ([RIDING, dict(RIDING, ride_slots=24, commit_slots=2)], 100 * 50 / 52),
    ([dict(RIDING, ride_slots=0)], None),             # nothing committed
    ([PARENT, dict(PARENT)], None),                   # not 0: nothing to read
    ([{"emitted": 64}], None),                        # one token a step
    ([], None),
], ids=["all-ride", "two-alone", "no-commit", "parent", "one-token", "empty"])
def test_commit_ride_share_on_a_recorded_cycle_list(cycles, want):
    got = _reader("commit_ride_share")({"trace_cycles": cycles})
    assert got == want if want is None else abs(got - want) < 1e-9


def test_commit_ride_share_without_records():
    assert _reader("commit_ride_share")({}) is None


def test_a_ride_counts_once_in_tokens_per_pass():
    """The accepted reader, untouched: a riding slot-pass is one denoising
    pass and no commit, so the launch above reads 127 tokens over 128
    slot-passes where the parent's read 102 over 128."""
    read = _reader("tokens_per_pass")
    assert abs(read({"trace_cycles": [RIDING]}) - 127 / 128) < 1e-9
    assert abs(read({"trace_cycles": [PARENT]}) - 102 / 128) < 1e-9
