"""Test harness config: force JAX onto an 8-device virtual CPU mesh.

Must run before jax is imported anywhere (pytest imports conftest first).
The driver validates real multi-chip sharding separately via
__graft_entry__.dryrun_multichip.
"""

import os

# force CPU with 8 virtual devices before any backend initializes.
# CCSX_TEST_TPU=1 opts out, running the suite on the real chip (used to
# run the Pallas differential tests with interpret=False on hardware).
_ON_TPU = os.environ.get("CCSX_TEST_TPU") == "1"
if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
