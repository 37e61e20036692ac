"""The one place that decides where JAX's persistent compile cache lives.

Every entry point (``chip_smoke.py``, ``benchmark/run.py``, the trainer
example, ``python -m scaling_tpu.serve bench``, the analysis CLI and
``tests/conftest.py``) calls ``enable_compile_cache()`` once before
its first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set the
directory is the environment's to place and no directory is set in code;
otherwise it is one fixed directory inside the checkout (the path is
part of the cache key, so a directory that moves never hits).

It is also where the program starts to listen to JAX's compile events
(``obs/compile_events.py``): whatever is traced, lowered, compiled or read
back from this cache afterwards is a row of the recorder by program name.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Switch the persistent compile cache on; returns the directory in
    use, or None when ``SCALING_TPU_TEST_CACHE=off`` disabled it (child
    processes of the tests compile cold: executables deserialized from
    the cache have mis-executed on the CPU backend, tests/core/subproc.py)."""
    import jax

    from .obs import compile_events

    compile_events.install()
    if os.environ.get("SCALING_TPU_TEST_CACHE", "").lower() == "off":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every executable: the suite and a chip call are both
    # compile-dominated, and small programs add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
