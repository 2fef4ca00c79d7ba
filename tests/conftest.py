"""Test configuration.

Per SURVEY.md §4's TPU-native translation: tests run on the CPU PjRt backend
(the "fake device", analog of the reference's fake_cpu_device.h) with 8
virtual devices so multi-chip sharding paths execute without TPU hardware.
"""
import os

# Both are read at the first backend initialisation, which is still
# ahead of us; the jax.config.update below holds the platform choice even
# if something imported jax earlier.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, (
    f"test env must see 8 virtual CPU devices, got {jax.devices()}")


# ---------------------------------------------------------------------------
# smoke subset (r3 verdict item 10): `pytest -m smoke` selects a <3-min
# cross-section — one fast module per layer of the stack — so CI/driver
# gates never hit the timeout wall the full ~20-min suite would.
# ---------------------------------------------------------------------------
import pytest  # noqa: E402

_SMOKE_MODULES = {
    "test_small_parity",      # op-level numeric parity vs torch
    "test_infermeta",         # shape/dtype inference + dispatch checks
    "test_top_namespaces",    # API surface parity
    "test_optimizer_amp",     # optimizers, lr schedulers, AMP O1/O2
    "test_ops_manipulation",  # reshape/concat/split family
    "test_regressions",       # past-bug pins
    "test_functional_smoke",  # call-path sweep of every F.* wrapper
    "test_io_samplers",       # samplers/datasets/collate
    "test_matrix_nms",        # detection post-processing
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.nodeid.split("::", 1)[0].rsplit("/", 1)[-1]
        if mod.removesuffix(".py") in _SMOKE_MODULES:
            item.add_marker(pytest.mark.smoke)
