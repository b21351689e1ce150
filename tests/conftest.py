"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests run on 1 device;
multi-device dry-run coverage goes through subprocesses (test_dryrun.py)."""

import jax
import pytest

from repro.configs import RunConfig, ShapeSpec

# The persistent compile cache stays off in tests: the AOT compiles for a
# described TPU (test_tpu_compile.py) would be written to it but cannot be
# read back without a chip, so the next run warns and compiles again.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def run_f32():
    return RunConfig(param_dtype="float32", compute_dtype="float32",
                     remat=False)


@pytest.fixture(scope="session")
def smoke_shape():
    return ShapeSpec("smoke", 32, 2, "train")


# the `slow` marker is registered in pytest.ini (with `-m "not slow"` as the
# default tier-1 selection)
