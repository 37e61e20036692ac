"""Entry point: set up the virtual 8-device CPU mesh BEFORE jax loads.

The audit lowers real mesh layouts (pp=2/dp=2/mp=2) on CPU, so the same
environment the test conftest builds must exist here — and XLA_FLAGS only
takes effect if exported before the first jax import, which is why this
lives in ``__main__`` and ``analysis/__init__`` stays jax-free.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# repeat runs (the CI gate, local loops) hit the compile cache instead of
# re-paying the lowering
from ..compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from .cli import main  # noqa: E402

sys.exit(main())
