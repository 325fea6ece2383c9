"""Test harness: force an 8-device virtual CPU mesh BEFORE jax initializes.

Reference test strategy translation (SURVEY.md §4): the reference tests
"multi-node" as multi-process on localhost; here every mesh/sharding/
collective test runs on fake CPU devices via
`--xla_force_host_platform_device_count=8`.
"""
import os

# Tests run on the virtual CPU mesh whatever the machine holds, so
# force-overwrite rather than setdefault.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
# dataflow.ensure_compile_cache turns jax's persistent compile cache on by
# default; a test run compiles hundreds of throwaway tiny executables, so
# keep them off the disk (children inherit this through the environment)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def fixed_seed():
    """Deterministic RNG per test (reference: @with_seed() decorator)."""
    import mxnet_tpu as mx
    mx.random.seed(0)
    np.random.seed(0)
    yield
