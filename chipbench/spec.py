"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix. Its
files, relative to the checkout root:

* ``configs[].file`` — the model configuration as it is run; its
  ``arch_module`` key names the architecture's module (below);
* ``chipbench/traffic/<traffic>.json`` — the mix's lengths, arrivals and
  denoising schedule, read by the one generator in ``traffic.py``;
* ``chipbench/cells/<workload>.json`` — the cell's rate or request count,
  serve settings and correctness limits;
* ``chipbench/metrics/<metric>.py`` — one reader per metric.

Adding a cell, configuration, traffic mix or metric adds files and entries;
no file here changes.

A configuration of an architecture the benchmark has not run yet brings
its module as a file too, ``chipbench/archs/<arch>.py``, named by the
configuration's ``arch_module`` and loaded by path (:func:`load_module`).
Everything that depends on the architecture lives there
(``archs/__init__.py`` lists what a module provides): the leaves of the
weights' layer stack, which steps of a block the reference replays as a
Refresh and which as a Reuse, the reference's layers, the operations per
token, the ``ModelConfig`` fields it reads and the sizes of the CPU
rehearsal. The file's ``program`` may set any ``ModelConfig`` field.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

ROOT = Path(__file__).resolve().parent.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    root: Path
    bench: dict
    workload: dict          # the BENCHMARK.json workloads entry
    config: dict            # the model configuration file
    traffic: dict           # the traffic mix file
    cell: dict              # the cell file

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def arch(self):
        """The configuration's architecture module (``arch_module``)."""
        return load_module(self.root / self.config["arch_module"])

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def metrics(self, kind: str) -> List[dict]:
        """The ``end_to_end`` (``kind="end_to_end"``) or ``per_layer``
        entries this cell reports, in ``BENCHMARK.json`` order. An entry
        with a ``workloads`` key lists its cells; a per-layer entry without
        one goes wherever its ``moves`` metric is reported."""
        e2e = [m for m in self.bench["end_to_end"] if _covers(m, self.name)]
        if kind == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]


def _covers(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    root = Path(root or ROOT)
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(work)}")
    w = work[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / cfgs[w["config"]]["file"])
    traffic = load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    cell = load_json(root / "chipbench" / "cells" / f"{name}.json")
    return Cell(root, bench, w, config, traffic, cell)


def load_module(path: Path):
    """A module of the benchmark's loaded from its file, once a process for
    each path: a new architecture or metric is a new file, found by the
    name ``BENCHMARK.json`` or a configuration gives it."""
    return _load(Path(path).resolve())


@functools.cache
def _load(path: Path):
    name = "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_path(root: Path, name: str) -> Path:
    return Path(root) / "chipbench" / "metrics" / f"{name}.py"
