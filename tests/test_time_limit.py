"""The limit every test runs under (``conftest.time_limit``, armed around
each test's setup and call at ``conftest.TEST_LIMIT_S``), driven directly:
what it says when a block runs past it, and that it leaves a block inside
it, and the process's alarm, untouched."""
import os
import signal
import time

import pytest

import conftest


def test_a_block_past_its_limit_fails_with_its_name_and_the_limit(
        tmp_path, monkeypatch):
    stacks = os.open(tmp_path / "stacks", os.O_WRONLY | os.O_CREAT)
    monkeypatch.setattr(conftest, "_stacks_to", stacks)   # not the run's log
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as err:
        with conftest.time_limit("tests/test_x.py::test_sleeps", 0.2):
            time.sleep(5)
    os.close(stacks)
    assert time.monotonic() - t0 < 2            # the sleep was cut
    assert str(err.value) == \
        "tests/test_x.py::test_sleeps ran past its limit of 0.2 s"
    # and where it waited is written down, had the wait been one that an
    # alarm cannot cut
    assert "test_time_limit.py" in (tmp_path / "stacks").read_text()


def test_a_block_inside_its_limit_passes_untouched():
    before = signal.getsignal(signal.SIGALRM)
    with conftest.time_limit("tests/test_x.py::test_quick", 0.5):
        value = sum(range(10))
    assert value == 45
    time.sleep(0.7)                             # no alarm is left armed
    assert signal.getsignal(signal.SIGALRM) is before
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


def test_every_test_runs_under_one_constant():
    """This test's own alarm is armed, with at most the constant left."""
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.TEST_LIMIT_S == 120
