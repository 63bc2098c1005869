"""Test configuration.

Tests run on the CPU: a virtual 8-device CPU mesh for the sharding/mesh
tests, set before jax initializes.  The v5e compile tests
(test_tpu_compile.py) describe the chip without attaching one.  XLA_FLAGS
carries the device count to the child processes some tests start.
"""

import os
import sys

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _x64_isolation():
    """Restore jax_enable_x64 after every test: the device epoch kernel
    flips it globally (per_epoch_jax._build_kernel), and under random
    test ordering that made the Pallas interpret tests compile under
    x64 — pathologically slow (the r3 suite 'hangs')."""
    before = jax.config.jax_enable_x64
    yield
    if jax.config.jax_enable_x64 != before:
        jax.config.update("jax_enable_x64", before)
