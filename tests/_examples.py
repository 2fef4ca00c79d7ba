"""Running one of ``examples/`` as a child process, as a reader of the
quickstarts would. ``test_examples*.py`` are three files because a file is
what the suite's workers are handed, and ten cold interpreters in one were
the longest file of the suite (PR 45)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# under conftest.TEST_LIMIT_S, so that a child is reaped before the alarm
CHILD_LIMIT_S = 110


def run(args, tmp_path, extra_env=None):
    """``python <args>`` from ``tmp_path`` on the CPU with the checkout on
    its path; the child's standard output."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    proc = subprocess.run([sys.executable, *args], cwd=str(tmp_path),
                          env=env, capture_output=True, text=True,
                          timeout=CHILD_LIMIT_S)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout
