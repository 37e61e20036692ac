"""``benchmarks/lowered_hash.py`` is what PR 58-62 rested "no other cell's
program moved" on: equal lines from two checkouts. Held here at toy widths:
the same cell lowers to the same text twice, a changed program lowers to
another, and ``main`` walks the one-chip cells of ``BENCHMARK.json``.
Nothing of a real configuration is lowered here (the largest takes GBs of
host memory); the toy roots are read, never edited.
"""

import hashlib
import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from benchmark import cells
from benchmarks import lowered_hash
from scaling_tpu.nn import ActivationFunction, activation_function

DATA = cells.REPO / "tests" / "benchmark" / "data"


def toy_cells():
    """One case a (root, configuration, kind) whose files the root holds
    itself: a serve cell's traffic does not reach its lowered text, so a
    configuration's second serve cell would be the same case again."""
    seen, cases = set(), []
    for root in sorted(DATA.glob("toy*")):
        bench = cells.load_json(root / "BENCHMARK.json")
        files = {c["name"]: Path(c["file"]).name for c in bench["configs"]}
        for entry in bench["workloads"]:
            traffic = root / "traffic" / f"{entry['traffic']}.json"
            config = root / "configs" / files[entry["config"]]
            if entry["chips"] != 1 or not (traffic.is_file() and config.is_file()):
                continue
            key = (root.name, entry["config"], cells.load_json(traffic)["kind"])
            if key not in seen:
                seen.add(key)
                cases.append(pytest.param(root, entry["name"],
                                          id=f"{root.name}:{entry['name']}"))
    return cases


def load(root, name):
    return cells.load_cell(name, bench_file=root / "BENCHMARK.json", root=root)


def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("root, name", toy_cells())
def test_the_same_cell_lowers_to_the_same_text_and_says_what_it_was(root, name):
    cell = load(root, name)
    what, text = lowered_hash.lowered_text(cell)
    again, text_again = lowered_hash.lowered_text(load(root, name))
    assert what == again and sha(text) == sha(text_again)
    assert "stablehlo" in text
    if cell.kind == "train":
        assert what == "train step"
    else:
        assert what.startswith("inference pass") and "uncached" in what


def test_the_cases_reach_a_train_a_dense_a_routed_and_a_sparse_cell():
    ids = {case.id for case in toy_cells()}
    assert {"toy:toy-train", "toy:toy-serve", "toy_moe:toy-serve-moe-chat",
            "toy_sparse_gqa:toy-serve-sparse-gqa",
            "toy_sparse_latent:toy-serve-sparse"} <= ids, ids


def test_the_hash_moves_when_the_program_does(monkeypatch):
    """The toy dense cell's SwiGLU with its gate's activation made tanh."""
    before = sha(lowered_hash.lowered_text(load(DATA / "toy", "toy-serve"))[1])
    monkeypatch.setitem(activation_function._FUNCTIONS,
                        ActivationFunction.SILU, jnp.tanh)
    after = sha(lowered_hash.lowered_text(load(DATA / "toy", "toy-serve"))[1])
    assert before != after


def test_main_prints_a_line_for_each_one_chip_cell_and_no_other(monkeypatch, capsys):
    monkeypatch.chdir(cells.REPO)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # main sets it: restored after
    monkeypatch.syspath_prepend(str(cells.REPO))
    monkeypatch.setattr(lowered_hash, "lowered_text",
                        lambda cell: (f"stub of {cell.kind}", cell.name))
    lowered_hash.main()
    lines = [line.split() for line in capsys.readouterr().out.splitlines()]
    workloads = json.loads((cells.REPO / "BENCHMARK.json").read_text())["workloads"]
    one_chip = [w["name"] for w in workloads if w["chips"] == 1]
    assert [line[0] for line in lines] == one_chip and len(one_chip) >= 11
    assert "train-pharia7b-4chip" not in {line[0] for line in lines}
    for line in lines:
        # name, what was lowered, the text's length, 20 hex digits of its sha256
        assert line[-2] == str(len(line[0]))
        assert line[-1] == sha(line[0])[:20]
