"""Test fixtures: force the JAX CPU backend with 8 simulated devices.

This is the TPU-world answer to "test multi-node without a cluster"
(SURVEY.md §4): every distributed/sharding test runs on an 8-device virtual
CPU mesh via ``--xla_force_host_platform_device_count``.  Must run before
jax initializes a backend, hence the top-level env mutation.
"""

import os

# STOKE_TEST_TPU=1 opts OUT of the cpu forcing so the on-hardware modules
# (tests/test_flash_tpu.py) can reach the real chip:
#   STOKE_TEST_TPU=1 python -m pytest tests/test_flash_tpu.py -q
_want_tpu = os.environ.get("STOKE_TEST_TPU") == "1"

if not _want_tpu:
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    d = jax.devices("cpu")
    assert len(d) == 8, f"expected 8 simulated devices, got {len(d)}"
    return d


@pytest.fixture
def rng():
    return np.random.default_rng(42)
