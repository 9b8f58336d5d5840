"""Test config: the suite runs on the CPU backend with 8 virtual
devices (the SPMD tests' mesh), set BEFORE jax initializes a backend.

Mirrors the reference test strategy (SURVEY §4): same suite over every
backend. The chip is exercised by ``chip_smoke.py`` (one process per
chip), and the TPU compiler — for a described, not attached, device —
by ``tests/test_tpu_aot_compile.py``. ``JAX_PLATFORMS`` is the only
thing that names the platform; it is set here so that a bare
``pytest`` never takes an attached chip away from another process, and
so child processes the tests start inherit it.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the CPU has no entry in environment.DEVICE_PEAKS (an unknown device
# is an error there): the suite's roofline numbers are wiring checks
# against these NAMED constants, given as the explicit overrides
os.environ.setdefault("DL4J_TPU_PEAK_TFLOPS", "197")
os.environ.setdefault("DL4J_TPU_PEAK_HBM_GBS", "819")
os.environ.setdefault("DL4J_TPU_PEAK_ICI_GBS", "45")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: round-end harness fences (subprocess bench/dossier "
        "runs, ~8 min); deselect with -m 'not slow' for quick loops")
