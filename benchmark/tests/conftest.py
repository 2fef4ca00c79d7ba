"""Tests of the benchmark itself: ``pytest benchmark/tests -q``. They run
on the CPU (kernels interpreted) and are no part of the repo's tier-1
suite."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
