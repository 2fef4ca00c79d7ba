"""The examples/ scripts must keep running end to end (they are the
migration-facing quickstarts; reference analog: the book tests under
python/paddle/fluid/tests/book/): training, the static graph, a served
export, generation (the serving quickstarts are
``test_examples_serve_gpt2.py`` and ``test_examples_http.py``)."""
import os

import pytest

from _examples import REPO, run as _run


@pytest.mark.parametrize("script,args,expect", [
    ("train_vision.py", ["--synthetic", "--epochs", "1",
                         "--batch-size", "16"], "saved vision_ckpt"),
    ("static_graph.py", [], "int8-sim max diff"),
])
def test_example_runs(script, args, expect, tmp_path):
    out = _run([os.path.join(REPO, "examples", script), *args], tmp_path)
    assert expect in out


def test_serve_example(tmp_path):
    _run([os.path.join(REPO, "examples", "serve_model.py"), "--export"],
         tmp_path)
    out = _run([os.path.join(REPO, "examples", "serve_model.py")],
               tmp_path)
    assert "16 concurrent requests" in out


def test_generate_text_example(tmp_path):
    out = _run([os.path.join(REPO, "examples", "generate_text.py")],
               tmp_path)
    assert "ragged left-padded batch" in out
    assert "beam k=4" in out


def test_gpt2_sharded_example(tmp_path):
    out = _run([os.path.join(REPO, "examples", "train_gpt2_sharded.py"),
                "--dp", "4", "--mp", "2", "--tiny", "--steps", "2"],
               tmp_path,
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "step 1: loss" in out
