"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests spawn N NCCL processes on one host (reference:
tests/core/utils.py:244-307). Under JAX single-controller SPMD the same
coverage comes from forcing 8 host-platform devices and building real meshes
over them — every sharding/collective path is exercised without TPUs.

Three rules of the suite live here (ROADMAP, Standing contracts, "Tests"):

- ``slow`` marks a case that REPEATS another case's assertion at a second
  size or dtype (a redundant grid entry, an extra-heavy parity run); it is
  never the only guard of a behaviour. ``-m 'not slow'`` (tier-1) deselects
  them, a plain run skips them, ``--runslow`` runs them.
- No single case may run longer than ``CASE_LIMIT_S`` seconds: ``case_limit``
  fails it with its name. A case that waits on a child process, a socket or a
  signal gives that wait a bound of its own, a few times what it takes when
  healthy and 120 s at most.
- ``--dist loadfile`` gives a file to one worker, so no run is shorter than
  its longest file: no file's cases sum to more than ~120 s, and the files of
  ``HEAD_OF_THE_RUN`` (the long ones) are collected first and handed out in
  that order (pytest-xdist's own reordering by the number of cases is switched
  off), so that the run's tail is made of short files.
"""

import os
import sys
from pathlib import Path

# Where the interpreter was told to write no bytecode (PYTHONDONTWRITEBYTECODE
# in this sandbox's and the driver's environment; site-packages holds no
# __pycache__ either), every process compiles the ~900 modules behind ``import
# jax`` from source: 3.6 s a start where 1.4 s would do, and tier-1 starts
# some two hundred child processes (fake hosts, replicas, supervised benches,
# isolated tests). Keep the bytecode, in ONE ignored directory of the checkout,
# for this process and for every child that inherits its environment.
PYCACHE = Path(__file__).resolve().parents[1] / ".pycache"
if sys.dont_write_bytecode:
    sys.dont_write_bytecode = False
    sys.pycache_prefix = str(PYCACHE)
    os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
    os.environ["PYTHONPYCACHEPREFIX"] = str(PYCACHE)

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

import contextlib  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# the longest healthy case takes some 40 s under six loaded workers
CASE_LIMIT_S = 300

# files whose cases sum to more than ~40 s under the driver's command
# (CHANGES.md, PR 66, has the table), longest first: collected ahead of
# everything else, in this order on every worker
HEAD_OF_THE_RUN = (
    "tests/core/test_chip_compile.py",
    "tests/transformer/test_serving.py",
    "tests/core/test_serve/test_packed_tick.py",
    "tests/core/test_serve/test_paged_kernel.py",
    "tests/transformer/test_training_pipeline.py",
    "tests/core/test_serve/test_sparse_gqa_serving.py",
    "tests/core/test_serve/test_tick_overlap.py",
    "tests/core/test_serve/test_hybrid_serving.py",
    "tests/core/test_serve/test_kvcache.py",
    "tests/core/test_nn/test_mamba.py",
    "tests/core/test_nn/test_moe.py",
    "tests/transformer/test_training_vocab_parallel.py",
    "tests/transformer/test_training.py",
    "tests/transformer/test_clip_resnet.py",
    "tests/core/test_analysis/test_cli_gate.py",
    "tests/core/test_serve/test_sparse_latent_serving.py",
    "tests/core/test_serve/test_paged_kernel_masks_and_lanes.py",
    "tests/transformer/test_model.py",
    "tests/core/test_serve/test_bench_e2e.py",
    "tests/transformer/test_hlo_cost_pins.py",
    "tests/core/test_resilience/test_reshard.py",
    "tests/transformer/test_training_pipeline_cost.py",
    "tests/core/test_serve/test_parallel_hybrid_serving.py",
    "tests/core/test_runner/test_runner.py",
    "tests/core/test_training/test_training_stream_and_zero3.py",
    "tests/transformer/test_inference.py",
    "tests/core/test_nn/test_sparse_attention.py",
    "tests/core/test_resilience/test_multihost_elastic.py",
    "tests/transformer/test_orbax_checkpoint.py",
    "tests/core/test_serve/test_host_fleet_e2e.py",
    "tests/core/test_serve/test_hc_latent_serving.py",
    "tests/core/test_serve/test_layered_gqa_serving.py",
    "tests/core/test_serve/test_delta_engine.py",
    "tests/core/test_serve/test_layered_latent_serving.py",
    "tests/core/test_nn/test_window_latent_attention.py",
    "tests/core/test_nn/test_gated_delta.py",
    "tests/core/test_training/test_training.py",
    "tests/core/test_resilience/test_multihost.py",
    "tests/core/test_resilience/test_crash_resume.py",
    "tests/core/test_serve/test_latent_serving.py",
    "tests/transformer/test_clip_vision.py",
    "tests/core/test_serve/test_proc_fleet_e2e.py",
    "tests/core/test_serve/test_fleet_e2e.py",
)


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (full parity grids)",
    )


def pytest_configure(config):
    """A fresh checkout has no ``libpack_index.so``: build it ONCE, before
    the workers start, or six of them compile it onto one path at once and
    one loads a file half written (``OSError: file too short`` at
    collection, which ends the whole run)."""
    if not hasattr(config, "workerinput"):
        from scaling_tpu.native import native_available

        native_available()
    # pytest-xdist would hand out the files in order of their NUMBER of cases
    # (--dist loadfile / loadscope, on by default): the order that counts
    # here is the collection's, HEAD_OF_THE_RUN first
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def head_first(items, head=HEAD_OF_THE_RUN):
    """``items`` with those of the files in ``head`` moved to the front, in
    ``head``'s order; every other item keeps its place."""
    rank = {path: i for i, path in enumerate(head)}
    return sorted(items, key=lambda item: rank.get(
        item.nodeid.split("::", 1)[0], len(rank)))


def pytest_collection_modifyitems(config, items):
    """The long files first (``HEAD_OF_THE_RUN``). Then: default runs finish
    fast; the slow tier holds repeats of another case's assertion at a
    second size or dtype (every behaviour keeps a fast guard). Enable with
    --runslow."""
    items[:] = head_first(items)
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow tier: pass --runslow to include")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@contextlib.contextmanager
def case_limit(seconds, name):
    """Fail the case ``name`` if the block runs longer than ``seconds``: a
    real-time timer whose SIGALRM raises ``pytest.fail`` in the main thread
    (a wait on a child or a socket is interrupted; a call inside the compiler
    is failed when it returns). The handler and the timer that were there are
    put back on the way out."""
    def over(signum, frame):
        pytest.fail(f"{name} ran over the per-case limit of {seconds} s "
                    "(tests/conftest.py CASE_LIMIT_S)")

    if threading.current_thread() is not threading.main_thread():
        yield
        return
    handler = signal.signal(signal.SIGALRM, over)
    timer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *timer)
        signal.signal(signal.SIGALRM, handler)


@pytest.fixture(autouse=True)
def _case_limit(request):
    """Every tier-1 case; a ``slow`` one (an extra-heavy repeat, run only
    under --runslow) says its own bounds."""
    if "slow" in request.node.keywords:
        yield
        return
    with case_limit(CASE_LIMIT_S, request.node.nodeid):
        yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs
