"""The golden run the multi-host e2es replay, built once a file that asks for
it (``test_multihost.py``, ``test_multihost_elastic.py``)."""

import pytest

from .multihost_tools import read_losses, run_supervised


@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """One uninterrupted single-host supervised run: the golden loss
    trajectory every fake host (same seed, same program) must replay."""
    tmp = tmp_path_factory.mktemp("multihost_e2e")
    p, workdir = run_supervised(tmp, "baseline", num_hosts=1)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    gold = read_losses(workdir, 0)
    assert sorted(gold) == list(range(1, 9))
    return tmp, gold
