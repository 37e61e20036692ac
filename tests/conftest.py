"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests spawn N NCCL processes on one host (reference:
tests/core/utils.py:244-307). Under JAX single-controller SPMD the same
coverage comes from forcing 8 host-platform devices and building real meshes
over them — every sharding/collective path is exercised without TPUs.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# persistent XLA compilation cache: the suite is compile-dominated on a
# small host, and repeat runs (CI, local loops) hit the cache instead.
# SCALING_TPU_TEST_CACHE=off disables it: subprocess-isolated tests
# (tests/core/subproc.py) compile cold, because executables DESERIALIZED
# from the cache have mis-executed on the CPU backend (NaN losses, hard
# aborts on a resumed trainer's re-jit of the same step).
from scaling_tpu.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (full parity grids)",
    )


def pytest_collection_modifyitems(config, items):
    """Default runs finish fast; the slow tier holds redundant grid entries
    and extra-heavy parity runs (every capability keeps at least one fast
    representative). Enable with --runslow."""
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow tier: pass --runslow to include")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs
