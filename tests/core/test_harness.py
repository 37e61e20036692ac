"""The suite's own rules (tests/conftest.py): the long files are collected
first and nothing else moves; a case that runs over the per-case limit is
failed with its name, and the SIGALRM handler and timer that were there are
put back; ``slow`` says what it means where it is declared; the bytecode
this process and its children write stays in one directory of the checkout."""

import signal
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from tests import conftest

REPO = Path(__file__).resolve().parents[2]


def items_of(*nodeids):
    return [SimpleNamespace(nodeid=n) for n in nodeids]


def test_the_files_of_the_head_come_first_in_the_heads_order_and_nothing_else_moves():
    items = items_of("tests/a.py::t1", "tests/b.py::t1", "tests/a.py::t2",
                     "tests/c.py::t1[x::y]", "tests/d.py::t1", "tests/c.py::t2")
    got = conftest.head_first(items, head=("tests/d.py", "tests/c.py"))
    assert [i.nodeid for i in got] == [
        "tests/d.py::t1", "tests/c.py::t1[x::y]", "tests/c.py::t2",
        "tests/a.py::t1", "tests/b.py::t1", "tests/a.py::t2"]
    assert [i.nodeid for i in conftest.head_first(items, head=())] == [
        i.nodeid for i in items]


def test_every_file_of_the_head_exists_and_is_named_once():
    head = conftest.HEAD_OF_THE_RUN
    assert len(set(head)) == len(head)
    assert [p for p in head if not (REPO / p).is_file()] == []
    # the benchmark's tests are not this list's to order
    assert not [p for p in head if p.startswith("tests/benchmark/")]


def test_this_runs_collection_put_the_head_first(request):
    """The session that runs this very case was ordered by the hook: the files
    of the head that it collected come before every other file."""
    files = list(dict.fromkeys(
        item.nodeid.split("::", 1)[0] for item in request.session.items))
    head = [f for f in conftest.HEAD_OF_THE_RUN if f in files]
    assert files[:len(head)] == head
    # ... and pytest-xdist, where it is loaded, hands the files out in that
    # order, not in order of their number of cases
    assert getattr(request.config.option, "loadscopereorder", False) is False


def test_a_case_over_the_limit_is_failed_with_its_name():
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="some::case ran over the "
                                                    "per-case limit of 0.05 s"):
        with conftest.case_limit(0.05, "some::case"):
            time.sleep(5)   # a wait: SIGALRM interrupts it
    assert time.monotonic() - began < 2


def test_the_limit_puts_back_the_handler_and_the_timer_it_found():
    def mine(signum, frame):
        raise AssertionError("the outer timer was not put back as it was")

    outer_handler = signal.signal(signal.SIGALRM, mine)
    outer_timer = signal.setitimer(signal.ITIMER_REAL, 1000.0)
    try:
        with conftest.case_limit(50, "some::case"):
            assert signal.getsignal(signal.SIGALRM) is not mine
            assert signal.getitimer(signal.ITIMER_REAL)[0] <= 50
        assert signal.getsignal(signal.SIGALRM) is mine
        assert 990 < signal.getitimer(signal.ITIMER_REAL)[0] <= 1000
    finally:
        signal.setitimer(signal.ITIMER_REAL, *outer_timer)
        signal.signal(signal.SIGALRM, outer_handler)


def test_every_case_runs_under_the_limit():
    """The autouse fixture armed the timer for this case too."""
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.CASE_LIMIT_S


def test_slow_says_what_it_means_where_the_marker_is_declared():
    text = (REPO / "pyproject.toml").read_text()
    assert "never the only guard of a behaviour" in text
    assert "never the only guard of a behaviour" in conftest.__doc__
    assert f"CASE_LIMIT_S = {conftest.CASE_LIMIT_S}" in (
        REPO / "tests" / "conftest.py").read_text()


def test_this_process_and_its_children_keep_their_bytecode():
    """Where the environment said to write none, the bytecode goes to ONE
    ignored directory of the checkout, here and in every child that inherits
    the environment (a child that compiles jax from source pays 2 s a start)."""
    assert not sys.dont_write_bytecode
    child = subprocess.run(
        [sys.executable, "-c",
         "import sys; print(sys.dont_write_bytecode, sys.pycache_prefix)"],
        capture_output=True, text=True, timeout=60, check=True).stdout.split()
    assert child == ["False", str(sys.pycache_prefix)]
    if sys.pycache_prefix is not None:
        assert sys.pycache_prefix == str(conftest.PYCACHE) == str(REPO / ".pycache")
        assert ".pycache/" in (REPO / ".gitignore").read_text().split()
