"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

What belongs to one configuration, one traffic mix or one per-layer metric
is a file of its own under the benchmark's directory, found by name:

- ``configs/<config>.json``; its ``"reference"`` (absent: ``dense_decoder``)
  names ``reference/<name>.py``, the plain forward, and ``views/<name>.py``,
  the program's parameters and config as that reference wants them;
- ``traffic/<traffic>.json``; for kind ``serve`` its ``"generator"`` (absent:
  ``traffic_gen.generate``) names ``generators/<name>.py``;
- ``metrics/<metric>.json``, which names its reader in ``readers/``.

A later PR adds an architecture, an arrival process or a metric as new files
plus entries of ``BENCHMARK.json``; it edits nothing here.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, List, Sequence

ROOT = Path(__file__).resolve().parent  # benchmark/
REPO = ROOT.parent

DEFAULT_REFERENCE = "dense_decoder"
# what the kinds call on a reference and on a view
REFERENCE_CONTRACT = ("forward", "token_loss")
VIEW_CONTRACT = ("reference_spec", "reference_weights", "train_flops_per_token")
GENERATOR_CONTRACT = ("generate",)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: Path, part: str, name: str,
                contract: Sequence[str] = ()) -> ModuleType:
    """``<root>/<part>/<name>.py`` as a module of its own. A file that is
    not there, or lacks a function of ``contract``, ends the run with the
    file's name in the message."""
    path = Path(root) / part / f"{name}.py"
    if not path.is_file():
        there = sorted(p.stem for p in path.parent.glob("*.py") if p.stem != "__init__")
        raise SystemExit(f"benchmark: no {part}/{name}.py under {root} (there: {there})")
    spec = importlib.util.spec_from_file_location(f"benchmark_{part}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [f for f in contract if not callable(getattr(module, f, None))]
    if missing:
        raise SystemExit(f"benchmark: {part}/{name}.py lacks {missing}")
    return module


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

    @property
    def reference_name(self) -> str:
        return self.config.get("reference", DEFAULT_REFERENCE)

    @functools.cached_property
    def reference(self) -> ModuleType:
        """The plain forward of this cell's architecture: ``forward(weights,
        tokens, spec, head_positions=None)`` and ``token_loss(logits,
        targets)``, independent of the program."""
        return load_module(self.root, "reference", self.reference_name,
                           REFERENCE_CONTRACT)

    @functools.cached_property
    def view(self) -> ModuleType:
        """The one place that names the program's config fields and
        parameter leaves for this architecture: ``reference_spec(arch)``,
        ``reference_weights(params, arch)`` and ``train_flops_per_token(arch,
        param_shapes, seq_len)``, ``arch`` being the configuration file's
        ``transformer_architecture``."""
        return load_module(self.root, "views", self.reference_name, VIEW_CONTRACT)

    @functools.cached_property
    def generate(self) -> Callable:
        """The traffic's generator: ``generate(traffic, seed, seconds, vocab,
        traced_seconds=0.0)`` -> the ``traffic_gen.Request`` list."""
        name = self.traffic.get("generator")
        if name is None:
            from . import traffic_gen

            return traffic_gen.generate
        return load_module(self.root, "generators", name, GENERATOR_CONTRACT).generate


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
    files = {"config": f"configs/{Path(config_entry['file']).name}",
             "traffic": f"traffic/{entry['traffic']}.json"}
    for file_name in files.values():
        if not (root / file_name).is_file():
            raise SystemExit(f"benchmark: workload {name!r} needs {file_name}, "
                             f"which is not under {root}")
    return Cell(name=name, chips=int(entry["chips"]), bench=bench, root=root,
                **{key: load_json(root / f) for key, f in files.items()})


def load_reader(metric_name: str, root: Path = ROOT) -> Callable:
    """``metrics/<name>.json`` names its reader as ``<file>:<function>`` of
    ``readers/``; a later PR adds a reader as a new file."""
    spec = load_json(root / "metrics" / f"{metric_name}.json")
    file_name, func = spec["reader"].split(":")
    return getattr(load_module(root, "readers", file_name), func)
