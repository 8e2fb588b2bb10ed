"""Test fixture: force an 8-virtual-device CPU backend.

This is the analog of the reference's in-process mini-clusters (SURVEY.md §4.3):
the full planner/executor/sharding stack runs against fake devices with no real
TPU, exactly as TestGeoMesaDataStore exercises the full planner with an
in-memory adapter. JAX's persistent compilation cache is off: the tests write
nothing into the checkout's cache directory. Device-scan failures raise
(``GEOMESA_TPU_STRICT_DEVICE``): a broken device path fails its test instead
of being answered on the host.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["GEOMESA_TPU_STRICT_DEVICE"] = "1"
# child processes the tests start see the same 8 devices
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
