"""Test harness config.

Mirrors the reference's CI pattern of running distributed tests as local
processes (ci/docker/runtime_functions.sh:1366-1374): we force an 8-virtual-
device CPU platform so mesh/sharding tests exercise real SPMD partitioning
without TPU hardware.  Must run before jax initializes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()

import numpy as _np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402

# Tests run on the virtual 8-device CPU mesh.
jax.config.update("jax_platforms", "cpu")

# Full f32 matmuls for numeric checks; production/TPU runs keep jax's fast
# default (bf16 passes on the MXU), mirroring how the reference tests CPU math
# at full precision while training uses fast kernels.
jax.config.update("jax_default_matmul_precision", "float32")


@pytest.fixture(autouse=True)
def _seed_rng():
    """Reference: tests/python/unittest/common.py with_seed() — reproducible
    randomness per test.  Seeds ALL three sources the reference does:
    the framework RNG, numpy, and Python's random (mx.image augmenters
    draw from the latter — unseeded it made convergence gates flaky)."""
    import random as _pyrandom

    import mxnet_tpu as mx
    mx.random.seed(42)
    _np.random.seed(42)
    _pyrandom.seed(42)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end tests (several minutes)")


# write_convergence_log lives in tests/_util.py: importing conftest from a
# test module would re-execute this file's env side effects
