"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List

ROOT = Path(__file__).resolve().parent  # benchmark/
REPO = ROOT.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict      # configs/<config>.json
    traffic: dict     # traffic/<traffic>.json
    bench: dict       # BENCHMARK.json
    root: Path        # the benchmark's directory

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def metrics(self, group: str) -> List[dict]:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports:
        those with no ``workloads`` key, or with this cell in it."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, bench_file: Path = REPO / "BENCHMARK.json",
              root: Path = ROOT) -> Cell:
    bench = load_json(bench_file)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"benchmark: no workload {name!r} in {bench_file} "
            f"(known: {[w['name'] for w in bench['workloads']]})")
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    # `file` is relative to the repository; the benchmark may have been
    # copied elsewhere, so the file is looked up inside `root` by its name
    config = load_json(root / "configs" / Path(config_entry["file"]).name)
    traffic = load_json(root / "traffic" / f"{entry['traffic']}.json")
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, bench=bench, root=root)


def load_reader(metric_name: str, root: Path = ROOT) -> Callable:
    """``metrics/<name>.json`` names its reader as ``<file>:<function>`` of
    ``readers/``; a later PR adds a reader as a new file."""
    spec = load_json(root / "metrics" / f"{metric_name}.json")
    file_name, func = spec["reader"].split(":")
    path = root / "readers" / f"{file_name}.py"
    module_spec = importlib.util.spec_from_file_location(
        f"benchmark_reader_{file_name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return getattr(module, func)
